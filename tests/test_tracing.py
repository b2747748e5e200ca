"""Host spans, compile markers and scope maps (``repro.telemetry.trace``).

Every profiler capture of the test suite lives in this file: a capture is
process-wide, so two at once would collide.

* spans: ``defta:<name>`` annotations with their arguments as event stats,
  nested on one thread, from the engine driver, ``evaluate``, the token
  batcher and ``train.run_model`` (whose ``step_s`` is the ``step`` span's
  own interval);
* compile markers: a fresh program writes ``compile`` (and ``retrace``)
  markers, a call that hits the in-memory cache writes none;
* scope maps: every instruction of a two-stage scanned program, fusions
  included, maps to the stage that produced it, and the real engine round
  maps to all nine of its stages.
"""
import glob
import os

import jax
import jax.numpy as jnp
import pytest
from jax.profiler import ProfileData

from repro.core.defta import run_defta
from repro.data.loader import TokenBatcher
from repro.telemetry import trace


def _capture(tmp_path, fn):
    """Run ``fn`` under the profiler; return its value and the ``defta:``
    events of the host plane, per thread line, sorted by start."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                         "*", "*.xplane.pb")))[-1]
    events = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(trace.PREFIX):
                    events.append({"name": ev.name[len(trace.PREFIX):],
                                   "line": line.name,
                                   "start": ev.start_ns,
                                   "end": ev.start_ns + ev.duration_ns,
                                   "args": dict(ev.stats)})
    events.sort(key=lambda e: e["start"])
    return out, events


def _named(events, name):
    return [e for e in events if e["name"] == name]


def _inside(child, parent):
    return (child["line"] == parent["line"]
            and parent["start"] <= child["start"]
            and child["end"] <= parent["end"])


def test_engine_and_evaluate_spans_nest_with_their_args(env, tmp_path):
    data, task, cfg, train = env
    run = lambda: run_defta(jax.random.PRNGKey(0), task, cfg, train, data,
                            epochs=4, eval_every=2, test_x=data["test_x"],
                            test_y=data["test_y"])
    _, ev = _capture(tmp_path, run)
    chunks = _named(ev, "chunk")
    assert [c["args"] for c in chunks] == [{"round": 0, "length": 2},
                                           {"round": 2, "length": 2}]
    for c in chunks:
        kids = [e["name"] for e in ev if e is not c and _inside(e, c)
                and e["name"] in ("dispatch", "wait")]
        assert kids == ["dispatch", "wait"]
    evals = _named(ev, "eval")
    assert [e["args"] for e in evals] == [{"round": 2}, {"round": 4}]
    for e in evals:
        inner = [x for x in _named(ev, "evaluate") if _inside(x, e)]
        assert len(inner) == 1
        kids = [x["name"] for x in ev if x["name"].startswith("evaluate.")
                and _inside(x, inner[0])]
        assert kids == ["evaluate.copy", "evaluate.accuracy",
                        "evaluate.fetch"]
    assert chunks[0]["end"] <= evals[0]["start"] <= chunks[1]["start"]


def test_batch_and_run_model_step_spans(tmp_path):
    from repro.launch import train

    args = train.parse_args(["--arch", "paper-small", "--steps", "3",
                             "--fl", "--pods", "2", "--gossip-every", "2",
                             "--batch", "4", "--seq", "16"])

    def both():
        TokenBatcher(64, 8, 2, seed=1).batch_at(7)
        return train.run_model(args)

    res, ev = _capture(tmp_path, both)
    assert [b["args"] for b in _named(ev, "batch")] == [
        {"step": 7}, {"step": 0}, {"step": 1}, {"step": 2}]
    steps = _named(ev, "step")
    assert [s["args"] for s in steps] == [{"step": i} for i in range(3)]
    gossip = _named(ev, "gossip")
    assert [g["args"] for g in gossip] == [{"step": 1}]
    assert _inside(gossip[0], steps[1])
    syncs = _named(ev, "loss_sync")
    assert [any(_inside(x, s) for s in steps) for x in syncs] == [True] * 3
    # step_s is the step span's own perf_counter interval
    assert len(res["step_s"]) == 3
    for got, s in zip(res["step_s"], steps):
        assert got == pytest.approx((s["end"] - s["start"]) / 1e9,
                                    abs=2e-3)


def test_compile_markers_for_fresh_programs_only(tmp_path):
    with trace.span("warm"):
        pass
    assert trace.listening()
    fn = jax.jit(lambda x: jnp.tanh(x) * 3.0 + 1.25)
    x = jnp.arange(7.0)

    def first():
        with trace.span("call", n=1):
            return fn(x).block_until_ready()

    _, ev = _capture(tmp_path / "a", first)
    kinds = {m["args"]["kind"] for m in _named(ev, "compile")
             if "lambda" in m["args"]["fun"]}
    assert {"retrace", "compile"} <= kinds
    for m in _named(ev, "compile"):
        assert m["args"]["seconds"] >= 0
    call = _named(ev, "call")[0]
    assert all(_inside(m, call) for m in _named(ev, "compile")
               if "lambda" in m["args"]["fun"])

    def again():
        with trace.span("call", n=2):
            return fn(x).block_until_ready()

    _, ev = _capture(tmp_path / "b", again)
    assert _named(ev, "call")[0]["args"] == {"n": 2}
    assert not _named(ev, "compile")


def test_span_seconds_is_its_own_interval():
    with trace.span("timed", step=3) as sp:
        pass
    assert 0 <= sp.seconds < 0.1


def _two_stage_program():
    w = jnp.linspace(-1.0, 1.0, 64).reshape(8, 8)

    def body(c, x):
        with jax.named_scope("alpha"):
            c = jnp.tanh(c @ w) * x
        with jax.named_scope("beta"):
            c = jnp.exp(-jnp.abs(c)) @ w.T + jnp.cumsum(c, axis=0)
        return c, None

    return jax.jit(lambda c, xs: jax.lax.scan(body, c, xs)[0])


def _body_instructions(text: str):
    """The instructions of the while loop's body computation."""
    body = text.split("body=%", 1)[1].split(",", 1)[0].split(" ")[0]
    comp = text.split(f"%{body} ", 1)[1].split("\n}\n", 1)[0]
    out = {}
    for line in comp.splitlines()[1:]:
        m = trace._INSTR.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def test_scope_map_of_a_two_stage_scan_covers_every_instruction():
    f = _two_stage_program()
    c, xs = jnp.ones((8, 8)), jnp.linspace(0.5, 1.0, 5)
    f(c, xs).block_until_ready()
    trace.note_program("two_stage", f, (c, xs), stages=("alpha", "beta"))
    assert trace.noted_stages("two_stage") == ("alpha", "beta")
    scopes = trace.scope_map("two_stage")
    body = _body_instructions(f.lower(c, xs).compile().as_text())
    fusions = [n for n, rest in body.items() if " fusion(" in rest]
    assert fusions
    for name, rest in body.items():
        op = trace._OP_NAME.search(rest)
        if op is None:
            # a copy, tuple, constant or loop parameter: the stage its
            # neighbours agree on, or none (the scan counter's constant)
            near = {scopes.get(o) for o in trace._OPERAND.findall(rest)}
            near |= {scopes[n] for n, r in body.items()
                     if f"%{name}," in r or f"%{name})" in r}
            assert scopes[name] is None or scopes[name] in near, name
            continue
        path = op.group(1).split("/")
        want = next((p for p in path if p in ("alpha", "beta")), None)
        # the scan's own counter and slicing belong to no stage
        assert scopes[name] == want, (name, op.group(1))
    assert all(scopes[n] in ("alpha", "beta") for n in fusions
               if "/while/body/closed_call/" in body[n])
    assert {"alpha", "beta"} <= {scopes[n] for n in fusions}


def test_hlo_scopes_fills_instructions_without_metadata():
    text = "\n".join([
        'ENTRY %main (p: f32[4]) -> f32[4] {',
        '  %p = f32[4]{0} parameter(0)',
        '  %a.1 = f32[4]{0} add(%p, %p), metadata={op_name="jit(f)/s1/add"}',
        '  %copy.2 = f32[4]{0} copy(%a.1)',
        '  %b.3 = f32[4]{0} fusion(%copy.2), kind=kLoop, '
        'metadata={op_name="jit(f)/while/body/s2/mul"}',
        '  %c.4 = s32[] add(%p, %p), metadata={op_name="jit(f)/while/add"}',
        '  ROOT %t = (f32[4]{0}, s32[]) tuple(%b.3, %c.4)',
        '}'])
    got = trace.hlo_scopes(text, ("s1", "s2"))
    assert got == {"p": "s1", "a.1": "s1", "copy.2": "s1", "b.3": "s2",
                   "c.4": None, "t": "s2"}


def test_scope_map_of_the_engine_round_finds_all_nine_stages(env):
    from repro.core.engine import stage_names

    data, task, cfg, train = env
    run_defta(jax.random.PRNGKey(1), task, cfg, train, data, epochs=2,
              scenario="paper_noise", gossip_backend="sparse")
    stages = trace.noted_stages("run_chunk")
    assert len(stages) == 9 and stages[-1] == "fire_merge"
    scopes = trace.scope_map("run_chunk")
    assert set(stages) <= set(scopes.values())
    mapped = [s for s in scopes.values() if s is not None]
    assert len(mapped) > 0.8 * len(scopes)


def test_scope_map_without_a_note_is_none():
    assert trace.scope_map("never_noted") is None
    assert trace.noted_stages("never_noted") == ()
