"""The benchmark's yardstick against hand-worked values."""
import pytest

import yardstick


def test_cnn_forward_flops_hand_worked():
    # conv1 28·28·3·3·1·16·2 = 225,792; conv2 14·14·3·3·16·32·2 =
    # 1,806,336; dense 7·7·32·10·2 = 31,360
    assert yardstick.cnn_forward_flops(28, 1, 16, 10) == 2_063_488


def test_train_flops_per_token_qwen3_0_6b():
    assert yardstick.train_flops_per_token(596_049_920) == pytest.approx(
        3.576e9, rel=1e-3)


def test_engine_round_flops():
    assert yardstick.engine_round_train_flops(
        workers=21, local_epochs=10, steps_per_epoch=9, batch=32,
        image_flops=2_063_488) == 21 * 10 * 9 * 32 * 3 * 2_063_488


@pytest.mark.parametrize("wire,per_elem", [("int8", 1 + 4), ("float32", 4 + 4),
                                           ("bf16", 2 + 4)])
def test_padded_csr_mix_counts(wire, per_elem):
    w, k, f = 21, 5, 15_680
    got = yardstick.padded_csr_mix(w, k, f, wire)
    scales = 4 * w if wire == "int8" else 0
    assert got["ops"] == 2 * w * k * f
    assert got["bytes"] == w * f * per_elem + w * k * 8 + scales


def test_roofline_time_picks_the_binding_bound():
    peaks = yardstick.chip_peaks("TPU v5 lite")
    t, bound = yardstick.roofline_time(2e9, 8.19e9, peaks)
    assert bound == "memory" and t == pytest.approx(0.01)
    t, bound = yardstick.roofline_time(197e12, 1.0, peaks)
    assert bound == "compute" and t == pytest.approx(1.0)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(ValueError, match="no published peaks"):
        yardstick.chip_peaks("cpu")
