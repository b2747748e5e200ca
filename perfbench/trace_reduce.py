"""Reduction of a JAX profiler trace of the measured window to the numbers
the per-layer metrics read.

``load`` turns the ``.xplane.pb`` that ``jax.profiler`` writes into plain
records: for each TPU plane its op events (``XLA Ops``: the HLO
instruction text, start and duration in ns) and its program events
(``XLA Modules``: ``jit_<function>(<hash>)``), and the harness's own host
spans (``bench:<name>`` annotations on the host plane, on the same clock).
On a v5e the op events carry no ``jax.named_scope`` path, so stages cannot
be told apart inside one program; programs and Pallas kernels (by their
instruction name, e.g. ``gossip_mix_quant_pallas.40``) can. ``reduce``
works on those records alone, so the tests drive it with a small recorded
trace kept as JSON.
"""
from __future__ import annotations

import glob
import gzip
import json
import os

SPAN_PREFIX = "bench:"


def load(trace_dir: str) -> dict:
    """Plain records of the newest trace under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    rec = {"devices": {}, "host_spans": []}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            ops, modules = [], []
            for line in plane.lines:
                if line.name not in ("XLA Ops", "XLA Modules"):
                    continue
                dest = ops if line.name == "XLA Ops" else modules
                for ev in line.events:
                    dest.append({"name": ev.name, "start": ev.start_ns,
                                 "dur": ev.duration_ns})
            rec["devices"][plane.name] = {"ops": ops, "modules": modules}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        rec["host_spans"].append({
                            "name": ev.name[len(SPAN_PREFIX):],
                            "start": ev.start_ns, "dur": ev.duration_ns})
    return rec


def union(intervals):
    """Merged, sorted [(start, end)] of possibly overlapping intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def covered(merged, lo: float, hi: float) -> float:
    """Length of [lo, hi] that ``merged`` intervals cover."""
    return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in merged)


def window_bounds(rec: dict):
    """The traced window: the span of all device ops and host spans (the
    profiler runs exactly over the window)."""
    ends = []
    for dev in rec["devices"].values():
        ends += [(o["start"], o["start"] + o["dur"]) for o in dev["ops"]]
    ends += [(s["start"], s["start"] + s["dur"]) for s in rec["host_spans"]]
    if not ends:
        return None
    return min(s for s, _ in ends), max(e for _, e in ends)


def module_time(modules, name: str) -> float:
    """Device ns of the runs of program ``jit_<name>``."""
    return sum(m["dur"] for m in modules
               if m["name"].split("(", 1)[0] == f"jit_{name}")


def module_count(modules, name: str) -> int:
    return sum(1 for m in modules
               if m["name"].split("(", 1)[0] == f"jit_{name}")


def kernel_ops(ops, names):
    """The op events of the named Pallas kernels (the instruction name of
    a ``pallas_call`` is the kernel function's)."""
    return [o for o in ops
            if any(o["name"].lstrip("%").startswith(n) for n in names)]


def exposed(ops, match) -> float:
    """Device ns of ops selected by ``match`` during which no other op
    runs on that device."""
    mine = [(o["start"], o["start"] + o["dur"]) for o in ops if match(o)]
    other = union([(o["start"], o["start"] + o["dur"]) for o in ops
                   if not match(o)])
    return sum((e - s) - covered(other, s, e) for s, e in union(mine))


def idle_gaps(ops, spans, lo: float, hi: float, top: int = 10):
    """The longest stretches of [lo, hi] with no op on the device, each
    named by the host span that covers most of it (``dispatch`` if none:
    the host was inside a call into the program)."""
    merged = union([(o["start"], o["start"] + o["dur"]) for o in ops])
    gaps, t = [], lo
    for s, e in merged:
        if s > t:
            gaps.append((t, min(s, hi)))
        t = max(t, e)
    if t < hi:
        gaps.append((t, hi))
    named = []
    for s, e in gaps:
        best, label = 0.0, "dispatch"
        for sp in spans:
            c = max(0.0, min(e, sp["start"] + sp["dur"])
                    - max(s, sp["start"]))
            if c > best:
                best, label = c, sp["name"]
        named.append((label, (e - s) / 1e9))
    named.sort(key=lambda g: -g[1])
    return [[n, v] for n, v in named[:top]]


CONTAINERS = ("while(", "conditional(", "call(")


def short_name(op_text: str) -> str:
    """``%fusion.418 = f32[32,14,14,21,32]{...} fusion(...)`` ->
    ``fusion.418 f32[32,14,14,21,32]``: the instruction and its result."""
    head, _, rest = op_text.partition(" = ")
    shape = rest.split("{", 1)[0].split(" ", 1)[0] if rest else ""
    return (head.lstrip("%") + (" " + shape if shape else "")).strip()


def leaf_ops(ops):
    """Ops that are not control-flow containers (a while loop's event
    spans the ops of its body, so counting it would count them twice)."""
    return [o for o in ops if not any(c in o["name"] for c in CONTAINERS)]


def reduce(rec: dict, devices: int) -> dict:
    """Busy and window seconds, the top device ops, the longest idle
    gaps, and the per-device op lists the metric readers need."""
    bounds = window_bounds(rec)
    names = sorted(rec["devices"])[:devices]
    lo, hi = bounds if bounds else (0.0, 0.0)
    busy = [covered(union([(o["start"], o["start"] + o["dur"])
                           for o in rec["devices"][n]["ops"]]), lo, hi)
            for n in names]
    ops0 = rec["devices"][names[0]]["ops"] if names else []
    by_name: dict = {}
    for o in leaf_ops(ops0):
        n = short_name(o["name"])
        by_name[n] = by_name.get(n, 0.0) + o["dur"]
    top = sorted(by_name.items(), key=lambda kv: -kv[1])[:10]
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": (sum(busy) / len(busy) / 1e9) if busy else 0.0,
        "ops": {n: rec["devices"][n]["ops"] for n in names},
        "modules": {n: rec["devices"][n]["modules"] for n in names},
        "spans": rec["host_spans"],
        "bounds": (lo, hi),
        "breakdown": {
            "device_ops": [[n, v / 1e9] for n, v in top],
            "idle_gaps": idle_gaps(ops0, rec["host_spans"], lo, hi),
        },
    }


def reduce_dir(trace_dir: str, devices: int) -> dict:
    return reduce(load(trace_dir), devices)


def save(rec: dict, path: str):
    with gzip.open(path, "wt") as fh:
        json.dump(rec, fh)


def load_saved(path: str) -> dict:
    with gzip.open(path, "rt") as fh:
        return json.load(fh)
