"""The trace reduction, on hand-made records and on a small slice of a
trace recorded on a v5e (``data/trace_v5e_slice.json.gz``: the engine
cell's ops, programs and harness spans over a short stretch)."""
import os

import pytest

import trace_reduce as tr

DATA = os.path.join(os.path.dirname(__file__), "data")


def op(name, start, dur):
    return {"name": name, "start": float(start), "dur": float(dur)}


def test_union_and_cover():
    merged = tr.union([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert merged == [[0, 3], [5, 8]]
    assert tr.covered(merged, 2, 6) == 2.0


def test_reduce_busy_window_gaps_and_top_ops():
    ops = [op("%while.1 = (s32[]) while(s32[] %p), body=%b", 0, 100),
           op("%fusion.2 = f32[8]{0} fusion(f32[8]{0} %a)", 0, 40),
           op("%fusion.3 = f32[8]{0} fusion(f32[8]{0} %a)", 50, 50),
           op("%copy.4 = f32[8]{0} copy(f32[8]{0} %x)", 160, 40)]
    mods = [op("jit_run_chunk(123)", 0, 100), op("jit_relu(9)", 160, 40)]
    spans = [{"name": "eval", "start": 100.0, "dur": 50.0},
             {"name": "check", "start": 150.0, "dur": 10.0}]
    rec = {"devices": {"/device:TPU:0": {"ops": ops, "modules": mods}},
           "host_spans": spans}
    out = tr.reduce(rec, devices=1)
    assert out["window_s"] == pytest.approx(200e-9)
    assert out["busy_s"] == pytest.approx(140e-9)
    names = [n for n, _ in out["breakdown"]["device_ops"]]
    assert names[0] == "fusion.3 f32[8]" and not any("while" in n
                                                   for n in names)
    assert out["breakdown"]["idle_gaps"] == [["eval", pytest.approx(60e-9)]]
    assert tr.module_time(mods, "run_chunk") == 100.0
    assert tr.module_count(mods, "relu") == 1


def test_kernel_ops_and_exposed_time():
    ops = [op("%gossip_mix_quant_pallas.4 = f32[21,256]{1,0} custom-call()",
              0, 10),
           op("%collective-permute.1 = f32[8]{0} collective-permute()",
              20, 30),
           op("%fusion.9 = f32[8]{0} fusion()", 40, 20)]
    assert [o["dur"] for o in tr.kernel_ops(
        ops, ("gossip_mix_quant_pallas",))] == [10.0]
    assert tr.exposed(ops, lambda o: "collective-permute" in o["name"]) \
        == 20.0


def test_recorded_v5e_slice():
    rec = tr.load_saved(os.path.join(DATA, "trace_v5e_slice.json.gz"))
    out = tr.reduce(rec, devices=1)
    assert 0 < out["busy_s"] <= out["window_s"]
    mods = out["modules"]["/device:TPU:0"]
    assert tr.module_time(mods, "run_chunk") > 0
    ops = out["ops"]["/device:TPU:0"]
    assert tr.kernel_ops(ops, ("gossip_mix_quant_pallas",))
    for name, seconds in out["breakdown"]["device_ops"]:
        assert " = " not in name and seconds > 0
    labels = {s["name"] for s in rec["host_spans"]} | {"dispatch"}
    assert {g[0] for g in out["breakdown"]["idle_gaps"]} <= labels
