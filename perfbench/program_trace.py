"""The program's own spans and compile markers in a traced window, and one
program's device time split by pipeline stage.

The program (``repro.telemetry.trace``) writes ``defta:<name>`` annotations
on the profiler's host plane, on the device events' clock: spans where
the work happens (``chunk``, ``dispatch``, ``wait``, ``eval``,
``evaluate`` and its ``evaluate.copy`` / ``evaluate.accuracy`` /
``evaluate.fetch``, ``batch``, ``step``, ``gossip``, ``loss_sync``) with
their arguments as event stats, and instant ``compile`` markers (``kind``
retrace, compile or load; ``seconds``) at the end of each. ``load`` reads
them again from the window's ``.xplane.pb``; ``trace_reduce`` keeps only
the harness's ``bench:`` spans.

Stage time: the program notes the jitted ``run_chunk`` it dispatches, and
``repro.telemetry.trace.scope_map`` maps each HLO instruction of it to the
``jax.named_scope`` stage that produced it. Each leaf op event inside a
``jit_run_chunk`` program event is summed under its instruction's stage.

Every reader returns None where the program writes none of this (a
checkout from before these spans), so the metrics fall silent there.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
import sys

import trace_reduce

PREFIX = "defta:"
# control-flow ops whose event spans the ops of their bodies (a Pallas
# ``custom-call`` is a leaf)
CONTAINER = re.compile(r"(?<![\w-])(?:while|conditional|call)\(")

_cache: dict = {}


def load(trace_dir: str) -> list:
    """The ``defta:`` events of the newest trace under ``trace_dir``,
    sorted by start: ``{"name", "start", "dur", "args"}`` (ns)."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        return []
    out = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    out.append({"name": ev.name[len(PREFIX):],
                                "start": ev.start_ns, "dur": ev.duration_ns,
                                "args": dict(ev.stats)})
    out.sort(key=lambda s: s["start"])
    return out


def records(ctx):
    """The window's ``defta:`` events (read once per trace), or None if the
    run was not traced or the program wrote none."""
    trace_dir = ctx["window"].trace_dir
    if not trace_dir:
        return None
    if trace_dir not in _cache:
        _cache[trace_dir] = load(trace_dir)
    return _cache[trace_dir] or None


def module():
    """The program's ``repro.telemetry.trace``, or None where the checkout
    has none."""
    try:
        from repro.telemetry import trace
    except ImportError:
        return None
    return trace


def named(recs, name: str):
    return [r for r in recs if r["name"] == name]


def compile_markers(recs, kinds=("compile", "load")):
    """Compile markers of ``kinds``, each with its interval rebuilt as
    ``[end - seconds, end]``."""
    out = []
    for r in named(recs, "compile"):
        if r["args"].get("kind") in kinds:
            end = r["start"] + r["dur"]
            out.append(dict(r, start=end - 1e9 * float(r["args"]["seconds"]),
                            end=end))
    return out


def compiles(ctx):
    """Backend compiles and cache loads that ended inside the window; None
    unless this process wrote compile markers."""
    trace = module()
    if trace is None or not trace.listening() or not ctx["window"].trace_dir:
        return None
    lo, hi = ctx["trace"]["bounds"]
    found = [m for m in compile_markers(records(ctx) or [])
             if lo <= m["end"] <= hi]
    for m in found:
        print(f"[perfbench] compile in window: {m['args']}", file=sys.stderr)
    return len(found)


def idle_inside(ops, spans, lo: float, hi: float) -> float:
    """Device-idle ns inside the union of ``spans``, clipped to [lo, hi]."""
    busy = trace_reduce.union([(o["start"], o["start"] + o["dur"])
                               for o in ops])
    total = 0.0
    for s, e in trace_reduce.union([(r["start"], r["start"] + r["dur"])
                                    for r in spans]):
        s, e = max(s, lo), min(e, hi)
        if e > s:
            total += (e - s) - trace_reduce.covered(busy, s, e)
    return total


def wait_ms(ctx, span_name: str, per: str):
    """Device-idle ms inside the program's ``span_name`` spans, per
    ``ctx[per]`` (rounds or steps) of the window; None without them.
    Logs the window's long idle gaps (``long_gaps``) once per trace."""
    recs = records(ctx)
    spans = named(recs, span_name) if recs else []
    if not spans or not ctx[per]:
        return None
    ops = next(iter(ctx["trace"]["ops"].values()), [])
    lo, hi = ctx["trace"]["bounds"]
    if ("gaps", ctx["window"].trace_dir) not in _cache:
        _cache["gaps", ctx["window"].trace_dir] = True
        for g in long_gaps(ops, recs, lo, hi):
            print(f"[perfbench] idle gap {g['seconds']:.4f} s at "
                  f"{g['at']:.3f} s: {g['label']} {g['args']}",
                  file=sys.stderr)
    return idle_inside(ops, spans, lo, hi) / 1e6 / ctx[per]


def long_gaps(ops, recs, lo: float, hi: float, min_s: float = 0.1):
    """Device-idle stretches of [lo, hi] longer than ``min_s``, each named
    by what the host was doing: a compile marker that overlaps it, else
    the innermost (shortest) ``defta:`` span over its middle. ``at`` is
    seconds from ``lo``."""
    busy = trace_reduce.union([(o["start"], o["start"] + o["dur"])
                               for o in ops])
    gaps, t = [], lo
    for s, e in busy + [[hi, hi]]:
        if min(s, hi) - t > min_s * 1e9:
            gaps.append((t, min(s, hi)))
        t = max(t, e)
    marks = compile_markers(recs, ("retrace", "compile", "load"))
    out = []
    for s, e in gaps:
        mid = (s + e) / 2
        hit = sorted((m for m in marks if m["start"] < e and m["end"] > s),
                     key=lambda m: max(s, m["start"]) - min(e, m["end"]))[:1]
        if not hit:
            hit = sorted((r for r in recs if r["name"] != "compile"
                          and r["start"] <= mid <= r["start"] + r["dur"]),
                         key=lambda r: r["dur"])[:1]
        label = (f"compile:{hit[0]['args'].get('kind')}" if hit and
                 hit[0]["name"] == "compile" else
                 hit[0]["name"] if hit else "(no span)")
        out.append({"at": (s - lo) / 1e9, "seconds": (e - s) / 1e9,
                    "label": label, "args": hit[0]["args"] if hit else {}})
    return out


def instruction(op_text: str) -> str:
    return op_text.split(" = ", 1)[0].lstrip("%").strip()


def stage_ns(ops, modules, program: str, scopes: dict):
    """``({stage or None: ns}, program ns)``: the device time of each leaf
    op that starts inside a ``jit_<program>`` event, under its
    instruction's stage (None: the instruction names no stage or is not
    in ``scopes``), and the program events' own time."""
    spans = sorted((m["start"], m["start"] + m["dur"]) for m in modules
                   if m["name"].split("(", 1)[0] == f"jit_{program}")
    starts = [s for s, _ in spans]
    out: dict = {}
    for o in ops:
        i = bisect.bisect_right(starts, o["start"]) - 1
        if i < 0 or o["start"] >= spans[i][1]:
            continue
        if CONTAINER.search(o["name"].partition(" = ")[2]):
            continue
        stage = scopes.get(instruction(o["name"]))
        out[stage] = out.get(stage, 0.0) + o["dur"]
    return out, sum(e - s for s, e in spans)


def stages(ctx, program: str = "run_chunk"):
    """``{stage or None: ns}`` of ``program`` over the window (and
    ``"program"``: its events' ns), or None where the program noted no
    scope map. Printed to stderr as a table once per trace."""
    key = (ctx["window"].trace_dir, program)
    if key in _cache:
        return _cache[key]
    trace, scopes = module(), None
    try:
        if trace is not None and ctx["window"].trace_dir:
            scopes = trace.scope_map(program)
    except Exception as e:          # a reader that raises ends the run
        print(f"[perfbench] no scope map of {program}: {e!r}",
              file=sys.stderr)
    if not scopes:
        _cache[key] = None
        return None
    name = next(iter(ctx["trace"]["ops"]), None)
    ops = ctx["trace"]["ops"].get(name, [])
    mods = ctx["trace"]["modules"].get(name, [])
    per, total = stage_ns(ops, mods, program, scopes)
    if not total:
        _cache[key] = None
        return None
    print_table(per, total, trace.noted_stages(program), ctx.get("rounds"))
    _cache[key] = dict(per, program=total)
    return _cache[key]


def print_table(per: dict, total: float, names, rounds):
    rows = list(names) + [s for s in per if s is not None
                          and s not in names] + [None]
    log = lambda msg: print(f"[perfbench] {msg}", file=sys.stderr)
    log(f"stage device time of the program ({total / 1e6:.3f} ms"
        + (f", {rounds} rounds" if rounds else "") + "):")
    for s in rows:
        ns = per.get(s, 0.0)
        per_round = f" {ns / 1e6 / rounds:10.4f} ms/round" if rounds else ""
        log(f"  {s or '(no stage)':<16}{ns / 1e6:12.3f} ms "
            f"{100 * ns / total:6.2f}%{per_round}")
    gap = total - sum(per.values())
    log(f"  {'(between ops)':<16}{gap / 1e6:12.3f} ms "
        f"{100 * gap / total:6.2f}%")


def stage_ms(ctx, *names):
    """Device ms per round of ``run_chunk`` ops mapped to ``names``."""
    per = stages(ctx)
    if per is None or not ctx["rounds"]:
        return None
    return sum(per.get(n, 0.0) for n in names) / 1e6 / ctx["rounds"]
