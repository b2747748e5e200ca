"""Roofline analyzer units: HLO collective-bytes parser + term math."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.roofline import (HBM_BW, ICI_BW, PEAK_FLOPS, analyze,
                                   chip_peaks, collective_bytes,
                                   compiled_bytes, shape_bytes)

HLO = """
HloModule jit_step

fused_computation {
  ...
}

ENTRY main {
  %p0 = bf16[16,512]{1,0} parameter(0)
  %ag = bf16[256,512]{1,0} all-gather(%p0), dimensions={0}
  %ar = f32[1024]{0} all-reduce(%x), to_apply=%add
  %ars = bf16[8,64]{1,0} reduce-scatter(%y), dimensions={0}
  %a2a = bf16[4,8,128]{2,1,0} all-to-all(%z), dimensions={0}
  %cp = u32[16]{0} collective-permute(%w), source_target_pairs={{0,1}}
  %tup = (f32[2,2]{1,0}, f32[2,2]{1,0}) all-reduce(%a, %b), to_apply=%add
  %ags = bf16[32,32]{1,0} all-gather-start(%q), dimensions={0}
  %agd = bf16[32,32]{1,0} all-gather-done(%ags)
  ROOT %out = bf16[256,512]{1,0} copy(%ag)
}
"""


def test_shape_bytes():
    assert shape_bytes("bf16[16,512]") == 16 * 512 * 2
    assert shape_bytes("f32[1024]") == 4096
    assert shape_bytes("pred[8]") == 8
    assert shape_bytes("f8e4m3fn[10,10]") == 100
    assert shape_bytes("token[]") == 0          # unknown dtype ignored
    assert shape_bytes("f32[]") == 4


def test_compiled_bytes_counts_arguments_and_donation():
    import jax
    x = jnp.zeros((256, 128), jnp.float32)
    f = lambda a: a * 2.0 + 1.0
    plain = compiled_bytes(jax.jit(f).lower(x).compile())
    donated = compiled_bytes(
        jax.jit(f, donate_argnums=0).lower(x).compile())
    for m in (plain, donated):
        assert m["argument"] >= x.nbytes and m["output"] >= x.nbytes
        assert m["peak"] == (m["argument"] + m["output"] + m["temp"]
                             - m["alias"])
    assert donated["alias"] >= plain["alias"]


def test_collective_bytes_parser():
    out = collective_bytes(HLO)
    assert out["all-gather"] == 256 * 512 * 2 + 32 * 32 * 2  # incl. -start
    assert out["all-reduce"] == 1024 * 4 + 2 * (2 * 2 * 4)   # tuple counted
    assert out["reduce-scatter"] == 8 * 64 * 2
    assert out["all-to-all"] == 4 * 8 * 128 * 2
    assert out["collective-permute"] == 16 * 4


def test_analyze_terms_and_bottleneck():
    cost = {"flops": 197e12, "bytes accessed": 819e9 * 2}
    r = analyze("a", "s", "single", 4, cost, "", model_flops=4 * 197e12 / 2,
                peak_bytes=1 << 30)
    assert abs(r.t_compute - 1.0) < 1e-9       # 4 chips × peak, 4× flops
    assert abs(r.t_memory - 2.0) < 1e-9
    assert r.t_collective == 0.0
    assert r.bottleneck == "memory"
    assert abs(r.useful_ratio - 0.5) < 1e-9


def test_analyze_collective_override():
    r = analyze("a", "s", "single", 2, {"flops": 0, "bytes accessed": 0},
                "", model_flops=0, peak_bytes=0,
                coll_override={"all-to-all": ICI_BW})
    assert abs(r.t_collective - 1.0) < 1e-9
    assert r.bottleneck == "collective"


def test_chip_peaks_keyed_by_device_kind():
    """v5e's published peaks under the kind JAX reports for the chip; the
    dry-run's constants are that entry."""
    p = chip_peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["int8_ops"] == 393e12
    assert p["hbm_bytes"] == 16e9 and p["hbm_bw"] == 819e9
    assert p["ici_link_bw"] * p["ici_links"] * 8 == 1600e9   # 1,600 Gbit/s
    assert (PEAK_FLOPS, HBM_BW, ICI_BW) == (
        p["bf16_flops"], p["hbm_bw"], p["ici_link_bw"])


def test_chip_peaks_unknown_kind_is_an_error():
    with pytest.raises(ValueError, match="no published peaks"):
        chip_peaks("cpu")
