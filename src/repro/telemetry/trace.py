"""Host spans, compile markers and scope maps: the telemetry plane's host half.

``span(name, **args)`` writes a ``jax.profiler.TraceAnnotation`` named
``defta:<name>`` with ``args`` as its event stats, so a profiler trace holds
what the host was doing on the same clock as the device's events; nesting
on one thread gives the parent. There is no flag and no buffer: the
profiler is the buffer, and with it off a span is a no-op annotation and
two ``perf_counter`` reads (``span.seconds`` is its own interval).

The first span installs one ``jax.monitoring`` duration listener that
writes an instant ``defta:compile`` annotation (``kind``, ``seconds``,
``fun``) when JAX finishes a retrace (``kind="retrace"``), a backend
compile (``"compile"``) or a backend compile served by the persistent
cache (``"load"``). The annotation sits at the event's end; its interval
is ``[end - seconds, end]``.

``scope_map(name)`` maps each HLO instruction of a program noted with
``note_program`` to the pipeline stage (``jax.named_scope``) that produced
it, read from the ``op_name`` metadata of the program compiled again from
its argument specs (a compile-cache hit). Profiler op events carry the
instruction name, so device time can be summed per stage.
"""
from __future__ import annotations

import re
import threading
import time
from typing import Dict, Optional, Sequence

import jax

PREFIX = "defta:"

COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "retrace",
    "/jax/core/compile/backend_compile_duration": "compile",
}
LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"

_listener = {"installed": False}
_local = threading.local()
_programs: Dict[str, tuple] = {}


def _on_duration(event: str, seconds: float, **kw):
    if event == LOAD_EVENT:
        # fired inside the backend compile it serves; that compile's
        # marker is then a load
        _local.loaded = True
        return
    kind = COMPILE_EVENTS.get(event)
    if kind is None:
        return
    if kind == "compile" and getattr(_local, "loaded", False):
        kind, _local.loaded = "load", False
    with jax.profiler.TraceAnnotation(PREFIX + "compile", kind=kind,
                                      seconds=float(seconds),
                                      fun=str(kw.get("fun_name", ""))):
        pass


def install():
    """Register the compile-marker listener (once per process)."""
    if not _listener["installed"]:
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _listener["installed"] = True


def listening() -> bool:
    """Whether this process writes compile markers."""
    return _listener["installed"]


class span:
    """``with span("chunk", round=r):`` — a ``defta:chunk`` profiler
    annotation with ``round`` as an event stat; ``.seconds`` is the
    span's own ``perf_counter`` interval once it has closed."""
    __slots__ = ("_note", "t0", "t1")

    def __init__(self, name: str, **args):
        if not _listener["installed"]:
            install()
        self._note = jax.profiler.TraceAnnotation(PREFIX + name, **args)

    def __enter__(self):
        self._note.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.t1 = time.perf_counter()
        self._note.__exit__(*exc)
        return False

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


def _spec(x):
    if not isinstance(x, jax.Array):
        return x
    return jax.ShapeDtypeStruct(
        x.shape, x.dtype, weak_type=x.weak_type,
        sharding=x.sharding if x.committed else None)


def note_program(name: str, fn, args: Sequence, kwargs: Optional[dict] = None,
                 stages: Sequence[str] = ()):
    """Keep what ``scope_map(name)`` compiles again: the jitted ``fn``, the
    specs of ``args`` (read from the arrays' shapes, dtypes, weak types
    and shardings, which a donated array keeps), static ``kwargs`` and the
    stage names. The latest note of a name replaces the one before."""
    _programs[name] = (fn, jax.tree.map(_spec, tuple(args)),
                       dict(kwargs or {}), tuple(stages))


def noted_stages(name: str):
    """The stage names noted with program ``name`` (empty if none)."""
    return _programs[name][3] if name in _programs else ()


_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = (.*)$")
_OPERAND = re.compile(r"%([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


_WRAPPED = re.compile(r"^[\w.]+\((.*)\)$")


def _unwrap(component: str) -> str:
    """A path component with JAX's transform wrappers taken off:
    ``transpose(jvp(mla))`` -> ``mla`` (a scope inside a differentiated or
    vmapped function is named so)."""
    m = _WRAPPED.match(component)
    while m:
        component = m.group(1)
        m = _WRAPPED.match(component)
    return component


def hlo_scopes(text: str, stages: Sequence[str]) -> Dict[str, Optional[str]]:
    """``{instruction: stage}`` for every instruction of an HLO module's
    text. An instruction's stage is the first component of its
    ``op_name`` path that is one of ``stages`` (transform wrappers such as
    ``transpose(jvp(...))`` taken off); one whose path names none
    (the scan's own counter and slicing) maps to None. One with no
    ``op_name`` at all (a layout copy, a tuple, a loop parameter) takes
    the stage of its operands, else of its users, when they agree on
    one."""
    stages = set(stages)
    out, operands, orphans = {}, {}, []
    for line in text.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        instr, rest = m.groups()
        meta = rest.find("metadata={")
        body = rest if meta < 0 else rest[:meta]
        operands[instr] = [o for o in _OPERAND.findall(body) if o != instr]
        op = _OP_NAME.search(rest, meta) if meta >= 0 else None
        if op is None:
            orphans.append(instr)
            out[instr] = None
        else:
            out[instr] = next((p for p in map(_unwrap,
                                              op.group(1).split("/"))
                               if p in stages), None)
    users: Dict[str, list] = {}
    for instr, ops in operands.items():
        for o in ops:
            users.setdefault(o, []).append(instr)
    for neighbours in (operands, users):
        changed = True
        while changed:
            changed = False
            for instr in orphans:
                if out[instr] is not None:
                    continue
                near = {out.get(n) for n in neighbours.get(instr, ())}
                near.discard(None)
                if len(near) == 1:
                    out[instr] = near.pop()
                    changed = True
    return out


def scope_map(name: str, stages: Optional[Sequence[str]] = None):
    """``{instruction: stage or None}`` of the program noted as ``name``
    (``stages`` defaults to the noted ones), or None if none was noted.
    Compiles the noted specs again: call it after the measured work."""
    if name not in _programs:
        return None
    fn, args, kwargs, known = _programs[name]
    text = fn.lower(*args, **kwargs).compile().as_text()
    return hlo_scopes(text, known if stages is None else stages)
