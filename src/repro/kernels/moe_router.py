"""moe_router — fused softmax + top-k routing (Pallas TPU).

For [T, E] router logits, computes normalized top-k gate values and expert
indices in one VMEM pass, instead of softmax -> top_k -> renormalize as
three HLO ops with [T, E] round-trips to HBM.

* grid tiles T in rows of BT=256; E (≤ 512 for all assigned archs) stays a
  single lane dimension — the whole tile is (BT, E) in VMEM.
* top-k is an unrolled k-step select-max-and-mask loop (k ≤ 8 for every
  assigned arch), which maps to VPU max-reductions; no sort.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import resolve_interpret


def _kernel(logits_ref, gates_ref, idx_ref, *, k: int):
    x = logits_ref[...].astype(jnp.float32)               # [BT, E]
    x = x - x.max(axis=-1, keepdims=True)
    ex = jnp.exp(x)
    probs = ex / ex.sum(axis=-1, keepdims=True)

    remaining = probs
    vals = []
    idxs = []
    e = probs.shape[-1]
    iota = jax.lax.broadcasted_iota(jnp.int32, probs.shape, 1)
    for _ in range(k):
        v = remaining.max(axis=-1)                        # [BT]
        i = jnp.argmax(remaining, axis=-1).astype(jnp.int32)
        vals.append(v)
        idxs.append(i)
        remaining = jnp.where(iota == i[:, None], -1.0, remaining)
    gates = jnp.stack(vals, axis=-1)                      # [BT, k]
    gates = gates / (gates.sum(axis=-1, keepdims=True) + 1e-9)
    gates_ref[...] = gates
    idx_ref[...] = jnp.stack(idxs, axis=-1)


@functools.partial(jax.jit, static_argnames=("k", "block_t", "interpret"))
def moe_router_pallas(logits, *, k: int, block_t: int = 256,
                      interpret=None):
    """logits: [T, E], T % block_t == 0 (ops.py pads)."""
    t, e = logits.shape
    grid = (t // block_t,)
    gates, idx = pl.pallas_call(
        functools.partial(_kernel, k=k),
        grid=grid,
        in_specs=[pl.BlockSpec((block_t, e), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((block_t, k), lambda i: (i, 0)),
            pl.BlockSpec((block_t, k), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((t, k), jnp.float32),
            jax.ShapeDtypeStruct((t, k), jnp.int32),
        ],
        interpret=resolve_interpret(interpret),
    )(logits)
    return gates, idx
