"""The Moonlight cell (a small cut on the CPU): the timed path's first
steps agree with the plain reference, and the float8 control and each
planted fault the comparison can see are refused by the cell's limits
(``biased_gates`` is not among them: at step 1 the bias is 0, and after a
cycle its gamma-sized steps move the gates by less than bfloat16 does); a
run through the harness is correct and drops no (token, expert) pair; the
grouped-matmul roofline reads the TPU's megablox ops."""
import os
import time

import jax
import pytest

import control
import run as harness

SEED = 2**31 + 13
SEEN = ("half_batch", "no_exchange", "capacity_drop", "held_only")


def moonlight_cell():
    """moonlight.pods2.gossip4 with the DeepSeek-V3 block at toy widths:
    3 layers (the dense one and 2 MoE), 4 of 16 experts held (4-7), top-6,
    vocabulary 512, 2 rows of 64 tokens a pod."""
    found = harness.find_cell(harness.load_json(
        os.path.join(harness.ROOT, "BENCHMARK.json")),
        "moonlight.pods2.gossip4")
    found["config"].update(
        hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        kv_lora_rank=32, intermediate_size=128, moe_intermediate_size=32,
        n_routed_experts=4, n_routed_experts_published=16, held_start=4,
        vocab_size=512, num_hidden_layers=3, seq=64, global_batch=4)
    return found


@pytest.fixture(scope="module")
def cell():
    found = moonlight_cell()
    driver = harness.load_module(os.path.join(harness.BENCH, "drivers",
                                              "pods_moe.py"),
                                 "perfbench_driver_pods_moe")
    config, traffic = found["config"], found["traffic"]
    got = driver.first_steps(config, traffic, SEED, jax.devices())
    want = driver.reference(config, traffic, SEED, got)
    return found, driver, got, want


def fails(checks):
    return any(v > lim for _, v, lim in checks)


def test_program_matches_the_reference(cell):
    found, driver, got, want = cell
    checks = driver.compare(found["config"], got, want)
    assert not fails(checks), checks
    assert int(got["counts"]["dropped"].sum()) == 0
    # at the first step (the same weights) the program's loads of the
    # held experts are the reference's, but for a rare near-tie that
    # rounding decides apart; later steps route by weights that differ
    held = got["counts"]["loads"][0, :, :, 4:8]
    assert abs(held - want["loads"][0]).sum() <= 0.01 * held.sum()


@pytest.mark.parametrize("what", ("control",) + SEEN)
def test_control_and_faults_are_refused(cell, what):
    found, driver, got, want = cell
    config, traffic = found["config"], found["traffic"]
    kw = {"dtype": control.LOWER[config["reference_dtype"]]} \
        if what == "control" else {"fault": what}
    bad = driver.reference(config, traffic, SEED, got, **kw)
    checks = driver.compare(config, driver.control_as_got(got, bad), want)
    assert fails(checks), checks


def test_harness_run_is_correct():
    line = harness.run_cell(moonlight_cell(), seed=SEED + 1, seconds=0.5,
                            trace=False, devices=jax.devices(),
                            t_start=time.perf_counter())
    assert line["correct"] is True, line["checks"]
    assert line["checks"]["dropped_pairs"]["value"] == 0
    assert line["metrics"]["tokens_per_s"]["value"] > 0


def test_expert_roofline_reads_the_megablox_kernels():
    """The grouped-matmul roofline reads the TPU's megablox ops (``gmm``,
    ``tgmm``) as one grouped matmul's work each: 11 ops a step and layer
    at exactly their least time read 100%; other ops are not counted, and
    a trace without grouped matmuls gives no reading."""
    import numpy as np

    import moe_yardstick
    import yardstick

    reader = harness.load_module(os.path.join(
        harness.BENCH, "metrics", "expert_gmm_roofline.moonlight.py"),
        "expert_gmm_roofline_moonlight")
    steps, pods, layers, held = 2, 2, 4, 8
    loads = np.full((steps, pods, layers, held), 96)
    d, f = 2048, 1408
    kind = next(iter(yardstick.PEAKS))
    w = moe_yardstick.grouped_matmul(pods * held * 96, d, f, pods * held)
    t, _ = yardstick.roofline_time(w["ops"], w["bytes"],
                                   yardstick.chip_peaks(kind))
    names = ["%gmm.3 = bf16[98304,1408]{1,0} custom-call(...)"] * 7 + [
        "%tgmm.1 = bf16[16,2048,1408]{2,1,0} custom-call(...)"] * 4
    ops = [{"name": n, "start": 0, "dur": t * 1e9}
           for _ in range(steps * layers) for n in names]
    ops.append({"name": "%fusion.12 = bf16[2,8192,2048] fusion(...)",
                "start": 0, "dur": 5e6})
    ctx = {"held_loads": loads, "steps": steps, "device_kind": kind,
           "expert_shape": (d, f, pods * held),
           "trace": {"ops": {"/device:TPU:0": ops}}}
    assert reader.read(ctx) == pytest.approx(100.0, rel=1e-6)
    ctx["trace"] = {"ops": {"/device:TPU:0": ops[-1:]}}
    assert reader.read(ctx) is None
