"""Logical-axis sharding: models annotate tensors with *logical* axis names;
a rules table maps logical names to mesh axes. Outside a mesh context the
annotations are no-ops, so the same model code runs in CPU smoke tests and
in the 512-chip dry-run.
"""
from __future__ import annotations

import contextlib
import threading
import warnings
from dataclasses import dataclass
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_state = threading.local()

# one warning per (logical name, mesh axis, dim) — resolve_spec runs on
# every constrain call inside traced code, so a repeated warning would
# drown the log while a silent demotion hides real placement bugs
_DEMOTION_WARNED: set = set()


def _ctx():
    if not hasattr(_state, "rules"):
        _state.rules = None
        _state.mesh = None
    return _state


@contextlib.contextmanager
def logical_rules(mesh: Optional[Mesh], rules: dict):
    """Install a mesh + logical->mesh-axis rules for ``constrain``/``spec``.

    ``rules`` maps logical axis name -> mesh axis name, tuple of mesh axis
    names, or None (replicated).
    """
    s = _ctx()
    prev = (s.rules, s.mesh)
    s.rules, s.mesh = rules, mesh
    try:
        yield
    finally:
        s.rules, s.mesh = prev


def _mesh_axis_size(mesh: Mesh, axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, (tuple, list)):
        n = 1
        for a in axis:
            n *= mesh.shape[a]
        return n
    return mesh.shape[axis]


def resolve_spec(logical_axes: Sequence[Optional[str]],
                 shape: Optional[Tuple[int, ...]] = None) -> Optional[P]:
    """Resolve logical axes -> PartitionSpec under the current rules.

    If ``shape`` is given, any dim not divisible by its mesh-axis product is
    demoted to replicated (GSPMD requires even sharding for our purposes and
    uneven shards would silently pad). The demotion WARNS once per
    (logical name, mesh axis, dim): a constraint that quietly stops
    sharding is how a model ends up replicated on 512 chips without anyone
    noticing — pad the dim (see ``WorkerShards``) or accept the warning.
    """
    s = _ctx()
    if s.rules is None or s.mesh is None:
        return None
    spec = []
    used = set()
    for i, name in enumerate(logical_axes):
        axis = s.rules.get(name) if name is not None else None
        if axis is not None:
            key = tuple(axis) if isinstance(axis, (tuple, list)) else (axis,)
            if used & set(key):
                axis = None  # a mesh axis may appear only once in a spec
            elif shape is not None and shape[i] % _mesh_axis_size(s.mesh, axis):
                wkey = (name, key, shape[i])
                if wkey not in _DEMOTION_WARNED:
                    _DEMOTION_WARNED.add(wkey)
                    warnings.warn(
                        f"sharding: logical axis {name!r} (dim {shape[i]}) "
                        f"is not divisible by mesh axis {axis!r} "
                        f"(size {_mesh_axis_size(s.mesh, axis)}) — demoting "
                        f"to replicated; pad the dim for an even shard",
                        RuntimeWarning, stacklevel=3)
                axis = None
            else:
                used |= set(key)
        spec.append(tuple(axis) if isinstance(axis, list) else axis)
    return P(*spec)


def constrain(x, *logical_axes):
    """with_sharding_constraint by logical names; no-op without rules."""
    spec = resolve_spec(logical_axes, x.shape)
    if spec is None:
        return x
    s = _ctx()
    return jax.lax.with_sharding_constraint(x, NamedSharding(s.mesh, spec))


def partitioned() -> bool:
    """Whether the code being traced is split over several devices by the
    compiler (GSPMD), as far as the context shows: a mesh of more than one
    device installed by ``logical_rules`` or ``jax.set_mesh``, unless a
    ``shard_map`` has made every axis of it manual (each body then runs on
    one device). A jit given multi-device shardings with no mesh in context
    is not seen. Pallas TPU (Mosaic) kernels lower only where this is
    False: the compiler cannot partition them."""
    am = jax.sharding.get_abstract_mesh()
    if not am.empty and set(am.manual_axes) == set(am.axis_names):
        return False
    mesh = _ctx().mesh
    return (mesh is not None and mesh.size > 1) or (not am.empty
                                                    and am.size > 1)


def named_sharding(logical_axes, shape=None) -> Optional[NamedSharding]:
    spec = resolve_spec(logical_axes, shape)
    if spec is None:
        return None
    return NamedSharding(_ctx().mesh, spec)


# ---------------------------------------------------------------------------
# Worker-axis sharding: the DeFTA round programs' W axis as a mesh dim
# ---------------------------------------------------------------------------

def worker_mesh(shards: Optional[int] = None, axis: str = "worker") -> Mesh:
    """A 1-D mesh over the first ``shards`` local devices (all of them by
    default) whose single axis carries the worker/enrolled dimension of
    the round programs. On CPU, force the device count BEFORE importing
    jax: ``XLA_FLAGS=--xla_force_host_platform_device_count=N``."""
    devs = jax.devices()
    n = len(devs) if shards is None else int(shards)
    if n < 1 or n > len(devs):
        raise ValueError(f"worker_mesh: asked for {shards} shards but only "
                         f"{len(devs)} devices are visible (set XLA_FLAGS="
                         f"--xla_force_host_platform_device_count)")
    return Mesh(np.asarray(devs[:n]), (axis,))


@dataclass(frozen=True)
class WorkerShards:
    """The worker-axis sharding contract of a round program run.

    One 1-D mesh axis (``axis``, default "worker") carries the leading W
    (or enrolled-N) dimension of every per-worker buffer: params, backup,
    confidence rows, EF residuals, sketch ring buffers, and the per-worker
    training data. Everything else (PRNG key, scalars, the cross-device
    k-block) stays replicated. Placement is GSPMD ``NamedSharding`` — an
    uneven W pads implicitly at the XLA level, so W need not divide the
    shard count; only the ``shard_map`` transport pads explicitly (see
    ``core.gossip.worker_shard_plan``).
    """
    mesh: Mesh
    axis: str = "worker"

    @property
    def shards(self) -> int:
        return int(self.mesh.shape[self.axis])

    def spec(self, *axes) -> NamedSharding:
        return NamedSharding(self.mesh, P(*axes))

    def row_sharding(self, ndim: int) -> NamedSharding:
        """Leading axis on the worker mesh axis, rest replicated."""
        return self.spec(self.axis, *([None] * (ndim - 1)))

    def replicated(self) -> NamedSharding:
        return self.spec()

    def shard_leading(self, tree, n: int):
        """device_put a pytree: every leaf whose leading dim is ``n``
        (the worker/enrolled count) is row-sharded on the worker axis,
        every other leaf replicated. This is the single placement rule
        the sharded drivers apply to carry state, data, and donated
        scan buffers.

        ``NamedSharding`` needs ``n`` divisible by the shard count; an
        uneven ``n`` keeps the buffers replicated (warned once — the
        shard_map TRANSPORT still pads internally and runs, but the
        per-device memory win needs a divisible worker count)."""
        even = n % self.shards == 0
        if not even:
            wkey = ("worker_rows", (self.axis,), n)
            if wkey not in _DEMOTION_WARNED:
                _DEMOTION_WARNED.add(wkey)
                warnings.warn(
                    f"sharding: worker count {n} is not divisible by "
                    f"{self.shards} shards — state buffers stay "
                    f"replicated (the sharded transport still pads and "
                    f"runs); pad W for the per-device memory win",
                    RuntimeWarning, stacklevel=3)

        def place(x):
            if even and hasattr(x, "ndim") and x.ndim >= 1 \
                    and x.shape[0] == n:
                return jax.device_put(x, self.row_sharding(x.ndim))
            return jax.device_put(x, self.replicated())
        return jax.tree.map(place, tree)
