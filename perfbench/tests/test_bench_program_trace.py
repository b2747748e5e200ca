"""The readers of the program's own spans, compile markers and stage map
(``program_trace`` and the metrics built on it), on hand-made records and
on a slice of a trace recorded on a v5e with the program's spans
(``data/trace_v5e_program_slice.json.gz``: the engine cell's ops and
programs over one eval boundary and the chunk after it, the harness's and
the program's host spans, and the scope map of the instructions in it)."""
import os
import types

import pytest

import program_trace as pt
import run as harness
import trace_reduce as tr

DATA = os.path.join(os.path.dirname(__file__), "data")
STAGES = ("split_keys", "scenario_view", "peer_sample", "transport",
          "damage_check", "local_train", "attack_inject", "trust_update",
          "fire_merge")


def op(name, start, dur):
    return {"name": name, "start": float(start), "dur": float(dur)}


def span(name, start, dur, **args):
    return {"name": name, "start": float(start), "dur": float(dur),
            "args": args}


class FakeTrace:
    """Stands in for ``repro.telemetry.trace`` in the harness process."""

    def __init__(self, scopes=None, listening=True):
        self.scopes, self._listening = scopes, listening

    def scope_map(self, name):
        return self.scopes if name == "run_chunk" else None

    def noted_stages(self, name):
        return STAGES

    def listening(self):
        return self._listening


def ctx_for(tmp_path, ops, modules, recs, *, rounds=4, steps=0,
            bounds=None):
    trace_dir = str(tmp_path / "trace")
    pt._cache[trace_dir] = recs
    rec = {"devices": {"/device:TPU:0": {"ops": ops, "modules": modules}},
           "host_spans": []}
    red = tr.reduce(rec, devices=1)
    if bounds is not None:
        red["bounds"] = bounds
    return {"window": types.SimpleNamespace(trace_dir=trace_dir),
            "trace": red, "rounds": rounds, "steps": steps}


def read(name, ctx):
    m = {"name": name, "unit": "x"}
    got = harness.read_layer_metrics([m], ctx)
    return got[name]["value"] if got else None


def test_idle_inside_nested_and_clipped_spans():
    ops = [op("%a = f32[] add()", 0, 10), op("%b = f32[] add()", 30, 10)]
    spans = [span("evaluate", 5, 40), span("evaluate.copy", 8, 10)]
    # [5, 45] less busy [5, 10] and [30, 40]
    assert pt.idle_inside(ops, spans, 0, 100) == 25.0
    assert pt.idle_inside(ops, spans, 0, 35) == 20.0


def test_wait_readers_count_idle_inside_their_spans(tmp_path, monkeypatch):
    ops = [op("%f = f32[] fusion()", 0, 100),
           op("%g = f32[] fusion()", 150, 100),
           op("%h = f32[] fusion()", 400, 100)]
    recs = [span("evaluate", 90, 70), span("evaluate.copy", 100, 20),
            span("batch", 250, 100, step=3), span("batch", 380, 40, step=4)]
    ctx = ctx_for(tmp_path, ops, [], recs, rounds=2, steps=2,
                  bounds=(0.0, 500.0))
    # evaluate: [90, 160] less busy [90, 100] and [150, 160] = 50 ns
    assert read("eval_wait_ms.engine", ctx) == pytest.approx(50 / 1e6 / 2)
    # batch: [250, 350] idle all, [380, 420] idle to 400
    assert read("batch_wait_ms.pods", ctx) == pytest.approx(120 / 1e6 / 2)


def test_wait_readers_are_silent_without_program_spans(tmp_path):
    ops = [op("%f = f32[] fusion()", 0, 100)]
    ctx = ctx_for(tmp_path, ops, [], [], rounds=4, steps=4)
    assert read("eval_wait_ms.engine", ctx) is None
    assert read("batch_wait_ms.pods", ctx) is None
    ctx["window"].trace_dir = None
    assert read("eval_wait_ms.engine", ctx) is None


def test_stage_readers_sum_leaf_ops_inside_the_program(tmp_path,
                                                       monkeypatch):
    ops = [op("%while.1 = (s32[]) while(%t), body=%b", 0, 100),
           op("%fusion.2 = f32[8]{0} fusion(%a), kind=kLoop", 0, 40),
           op("%gossip_mix_quant_pallas.3 = f32[8]{0} custom-call(%a)",
              40, 10),
           op("%copy.4 = f32[8]{0} copy(%fusion.2)", 50, 20),
           op("%fusion.5 = f32[8]{0} fusion(%a)", 70, 5),
           op("%add.6 = s32[] add(%i, %c)", 75, 5),
           op("%fusion.2 = f32[8]{0} fusion(%x)", 200, 30)]
    mods = [op("jit_run_chunk(77)", 0, 100), op("jit_evaluate(5)", 200, 30)]
    scopes = {"fusion.2": "local_train", "gossip_mix_quant_pallas.3":
              "transport", "copy.4": "local_train", "fusion.5":
              "trust_update", "add.6": None}
    ctx = ctx_for(tmp_path, ops, mods, [], rounds=2)
    monkeypatch.setattr(pt, "module", lambda: FakeTrace(scopes))
    assert read("local_train_ms.engine", ctx) == pytest.approx(60 / 1e6 / 2)
    assert read("transport_ms.engine", ctx) == pytest.approx(10 / 1e6 / 2)
    assert read("trust_ms.engine", ctx) == pytest.approx(5 / 1e6 / 2)
    per = pt.stages(ctx)
    assert per["program"] == 100.0 and per[None] == 5.0
    # the while loop spans its body and is not counted; the stages and
    # the unmapped rest make up the program's leaf time
    assert sum(v for k, v in per.items() if k != "program") == 80.0


def test_stage_readers_are_silent_without_a_scope_map(tmp_path,
                                                      monkeypatch):
    ops = [op("%fusion.2 = f32[8]{0} fusion(%a)", 0, 40)]
    mods = [op("jit_run_chunk(77)", 0, 100)]
    ctx = ctx_for(tmp_path, ops, mods, [], rounds=2)
    monkeypatch.setattr(pt, "module", lambda: None)
    assert read("local_train_ms.engine", ctx) is None
    ctx = ctx_for(tmp_path / "b", ops, mods, [], rounds=2)
    monkeypatch.setattr(pt, "module", lambda: FakeTrace(None))
    assert read("transport_ms.engine", ctx) is None


def test_compile_counts_markers_ending_in_the_window(tmp_path,
                                                     monkeypatch):
    ops = [op("%f = f32[] fusion()", 100, 1000)]
    recs = [span("compile", 50, 1, kind="compile", seconds=2e-8, fun="f"),
            span("compile", 200, 1, kind="load", seconds=1e-7, fun="g"),
            span("compile", 300, 1, kind="retrace", seconds=1e-7, fun="h"),
            span("compile", 900, 1, kind="compile", seconds=1e-6, fun="k")]
    ctx = ctx_for(tmp_path, ops, [], recs, bounds=(100.0, 1100.0))
    monkeypatch.setattr(pt, "module", lambda: FakeTrace())
    assert read("compiles.engine", ctx) == 2
    assert read("compiles.pods", ctx) == 2
    marks = pt.compile_markers(recs)
    assert [(m["start"], m["end"]) for m in marks] == [
        (pytest.approx(31.0), 51.0), (pytest.approx(101.0), 201.0),
        (pytest.approx(-99.0), 901.0)]
    ctx = ctx_for(tmp_path / "b", ops, [], [], bounds=(100.0, 1100.0))
    assert read("compiles.engine", ctx) == 0
    monkeypatch.setattr(pt, "module", lambda: FakeTrace(listening=False))
    assert read("compiles.engine", ctx) is None
    monkeypatch.setattr(pt, "module", lambda: None)
    assert read("compiles.pods", ctx) is None


def test_long_gaps_name_the_compile_or_the_innermost_span():
    ops = [op("%a = f32[] add()", 0, 10), op("%b = f32[] add()", 500, 10),
           op("%c = f32[] add()", 900, 10)]
    recs = [span("eval", 5, 600, round=8), span("evaluate", 20, 400),
            span("evaluate.accuracy", 40, 300),
            span("compile", 850, 1, kind="compile", seconds=2e-7,
                 fun="jit(run_chunk)")]
    gaps = pt.long_gaps(ops, recs, 0, 910, min_s=1e-7)
    assert [(g["label"], g["seconds"]) for g in gaps] == [
        ("evaluate.accuracy", pytest.approx(490e-9)),
        ("compile:compile", pytest.approx(390e-9))]
    assert gaps[1]["args"]["fun"] == "jit(run_chunk)"


def test_recorded_v5e_slice_with_program_spans():
    rec = tr.load_saved(os.path.join(DATA,
                                     "trace_v5e_program_slice.json.gz"))
    assert tuple(rec["stages"]) == STAGES
    dev = rec["devices"]["/device:TPU:0"]
    per, total = pt.stage_ns(dev["ops"], dev["modules"], "run_chunk",
                             rec["scopes"])
    assert total > 0 and set(per) <= set(STAGES) | {None}
    # every op of the program in the slice is in the scope map, and the
    # convolutions of local training are most of its time
    inside = sum(per.values())
    assert per.get(None, 0.0) <= 0.05 * inside
    assert per["local_train"] > 0.9 * inside
    recs = rec["program_spans"]
    names = [r["name"] for r in recs if r["name"] != "compile"]
    assert names == ["eval", "evaluate", "evaluate.copy",
                     "evaluate.accuracy", "evaluate.fetch", "chunk",
                     "dispatch", "wait"]
    ev, = pt.named(recs, "evaluate")
    outer, = pt.named(recs, "eval")
    assert outer["start"] <= ev["start"] and \
        ev["start"] + ev["dur"] <= outer["start"] + outer["dur"]
    assert pt.named(recs, "chunk")[0]["args"]["length"] == 4
    # the eager evaluation retraces its small jaxprs but compiles nothing
    assert {m["args"]["kind"] for m in pt.named(recs, "compile")} == \
        {"retrace"}
    assert pt.compile_markers(recs) == []
    lo, hi = tr.window_bounds(rec)
    idle = pt.idle_inside(dev["ops"], [ev], lo, hi)
    assert 0 < idle < ev["dur"]
    assert idle <= pt.idle_inside(
        dev["ops"], [s for s in rec["host_spans"] if s["name"] == "eval"],
        lo, hi)
