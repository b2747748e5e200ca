"""Production meshes. Functions only — importing this module never touches
jax device state (the dry-run sets XLA_FLAGS before any jax init).

Single-pod: (data=16, model=16)            = 256 chips (one v5e pod)
Multi-pod:  (pod=2, data=16, model=16)     = 512 chips (2 pods)

The ``pod`` axis is DeFTA's worker axis: each pod is one federated worker
holding its own model replica; cross-pod traffic happens only in the gossip
step (sampled peers, outdegree-corrected weights), never inside train_step.

Every mesh here has ``Auto`` axis types: the models place tensors through
logical-axis ``with_sharding_constraint`` calls, which JAX accepts only on
Auto axes (``jax.make_mesh`` alone gives Explicit ones).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def auto_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    need = 512 if multi_pod else 256
    devices = jax.devices()[:need]
    if len(devices) < need:
        raise RuntimeError(
            f"production mesh needs {need} devices, have {len(devices)} — "
            "run via launch/dryrun.py (it sets "
            "--xla_force_host_platform_device_count=512 before jax init)")
    return auto_mesh(shape, axes, devices=devices)


def make_debug_mesh(data: int = 2, model: int = 2, pods: int = 0):
    """Tiny mesh for CPU tests (requires xla_force_host_platform_device_count
    set by the test session)."""
    if pods:
        return auto_mesh((pods, data, model), ("pod", "data", "model"))
    return auto_mesh((data, model), ("data", "model"))


def make_pod_mesh(pods: int):
    """One chip per pod: ``pod=pods, data=1, model=1`` over the first
    ``pods`` devices present (the ``--fl --transport ppermute`` mesh)."""
    devices = jax.devices()
    if len(devices) < pods:
        raise RuntimeError(f"--fl with {pods} pods over a mesh needs "
                           f"{pods} devices, have {len(devices)}")
    return auto_mesh((pods, 1, 1), ("pod", "data", "model"),
                     devices=devices[:pods])
