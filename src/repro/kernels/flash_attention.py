"""flash_attention — blocked causal/sliding-window attention (Pallas TPU).

Online-softmax flash attention over [B*H, S, D]:

* grid = (bh, num_q_blocks, num_kv_blocks); the kv axis is the innermost,
  sequentially-executed ("arbitrary") dimension, so fp32 accumulators live
  in VMEM scratch across kv iterations.
* BlockSpec tiles: q (BQ, D), k/v (BK, D) with BQ=BK=128 — MXU-aligned on
  both matmul dims; VMEM working set = q + k + v + acc ≈ 4·128·D·4B
  (≤ 256 KiB at D=128), far under the ~16 MiB budget, leaving room for
  double-buffered pipelining of the k/v streams.
* causal + sliding-window masking is done blockwise: fully-masked kv blocks
  are skipped via @pl.when (no wasted MXU work — this is what makes the
  long_500k window-4096 decode linear instead of quadratic).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import resolve_interpret

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            scale: float, block_q: int, block_k: int, causal: bool,
            window: int, num_kv: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = qi * block_q
    k_start = ki * block_k

    # block-level reachability: any (q, k) pair in range?
    reachable = True
    if causal:
        reachable = k_start <= q_start + block_q - 1
    if window > 0:
        reachable = jnp.logical_and(
            reachable, k_start + block_k - 1 > q_start - window)

    @pl.when(reachable)
    def compute():
        q = q_ref[0].astype(jnp.float32) * scale          # [BQ, D]
        k = k_ref[0].astype(jnp.float32)                  # [BK, D]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                  (block_q, block_k), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                  (block_q, block_k), 1)
        mask = jnp.ones((block_q, block_k), jnp.bool_)
        if causal:
            mask &= kpos <= qpos
        if window > 0:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1)
        acc_ref[...] = (acc_ref[...] * alpha[:, None] +
                        jax.lax.dot(p, v_ref[0].astype(jnp.float32),
                                    preferred_element_type=jnp.float32))
        m_ref[...] = m_new

    @pl.when(ki == num_kv - 1)
    def finalize():
        denom = jnp.maximum(l_ref[...], 1e-20)[:, None]
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_k", "interpret"))
def flash_attention_pallas(q, k, v, *, causal: bool = True, window: int = 0,
                           block_q: int = 128, block_k: int = 128,
                           interpret=None):
    """q,k,v: [BH, S, D]; S % block == 0 (ops.py pads). Returns [BH, S, D]."""
    bh, s, d = q.shape
    nq, nk = s // block_q, s // block_k
    scale = 1.0 / (d ** 0.5)
    kernel = functools.partial(
        _kernel, scale=scale, block_q=block_q, block_k=block_k,
        causal=causal, window=window, num_kv=nk)
    return pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),   # acc
            pltpu.VMEM((block_q,), jnp.float32),     # running max m
            pltpu.VMEM((block_q,), jnp.float32),     # running sum l
        ],
        interpret=resolve_interpret(interpret),
    )(q, k, v)
