"""ssd_chunk — Mamba2 SSD intra-chunk kernel (Pallas TPU).

The quadratic intra-chunk term of the SSD dual form (models/ssm.py):

    y[q, p] = Σ_{k<=q} (C[q]·B[k]) · exp(Acum[q]-Acum[k]) · dt[k] · x[k, p]

per (batch·chunk, head) grid cell. This is mamba2's MXU hot spot: two
matmuls (C·Bᵀ over the state dim, attn-like weights · x over the chunk)
fused with the decay/causal masking in VMEM, instead of five HLO ops with
[T, T] round-trips.

Tiles: one grid cell holds C,B [T,N], x [T,P], Acum/dt [T] in VMEM —
T=chunk (≤256), N=d_state (≤128), P=head_dim (64): ≤ 256·(128·2+64)·4B
≈ 330 KiB, MXU-aligned on every contraction.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import resolve_interpret


def _kernel(c_ref, b_ref, acum_ref, dt_ref, x_ref, o_ref):
    c = c_ref[0].astype(jnp.float32)                  # [T, N]
    b = b_ref[0].astype(jnp.float32)                  # [T, N]
    acum = acum_ref[0, 0].astype(jnp.float32)         # [T]
    dt = dt_ref[0, 0].astype(jnp.float32)             # [T]
    x = x_ref[0, 0].astype(jnp.float32)               # [T, P]
    t = c.shape[0]

    scores = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    qpos = jax.lax.broadcasted_iota(jnp.int32, (t, t), 0)
    kpos = jax.lax.broadcasted_iota(jnp.int32, (t, t), 1)
    decay = jnp.exp(acum[:, None] - acum[None, :])    # [T, T]
    w = jnp.where(kpos <= qpos, scores * decay * dt[None, :], 0.0)
    o_ref[0, 0] = jax.lax.dot(
        w, x, preferred_element_type=jnp.float32).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_chunk_pallas(C, B, acum, dt, x, *, interpret=None):
    """C,B: [G, T, N]; acum,dt: [G, H, T]; x: [G, H, T, P] ->
    y: [G, H, T, P]   (G = batch·num_chunks)."""
    g, t, n = C.shape
    h = x.shape[1]
    p = x.shape[-1]
    return pl.pallas_call(
        _kernel,
        grid=(g, h),
        in_specs=[
            pl.BlockSpec((1, t, n), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, t, n), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, 1, t), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, 1, t), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, 1, t, p), lambda i, j: (i, j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, t, p), lambda i, j: (i, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((g, h, t, p), x.dtype),
        interpret=resolve_interpret(interpret),
    )(C, B, acum, dt, x)
