"""jit'd public wrappers around the Pallas kernels: shape padding and
layout glue. Every kernel runs compiled on TPU and interpreted on the CPU
(``kernels.common.resolve_interpret`` — the one place that choice lives).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.ssd_chunk import ssd_chunk_pallas
# the gossip kernels pad to their own tile plans: exported as they are
from repro.kernels.gossip_mix import gossip_mix_pallas as gossip_mix  # noqa: F401
from repro.kernels.gossip_mix_quant import (  # noqa: F401
    gossip_mix_quant_pallas as gossip_mix_quant)
from repro.kernels.gossip_mix_sparse import (  # noqa: F401
    gossip_mix_sparse_pallas as gossip_mix_sparse)
from repro.kernels.moe_router import moe_router_pallas


def _pad_to(x, axis: int, mult: int):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x, 0
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), pad


def _pow2_block(n: int, block: int) -> int:
    """Block length for a length-``n`` axis: the smallest power of two >= n,
    clamped to [16, block] with ``block`` itself rounded DOWN to a power of
    two — every returned value is MXU/lane aligned, even for a non-pow2
    ``block`` request or n >= block (both previously skipped the clamp)."""
    cap = 1 << (block.bit_length() - 1)            # largest pow2 <= block
    want = 1 << max(n - 1, 1).bit_length()         # smallest pow2 >= n
    return max(16, min(cap, want))


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128):
    """q,k,v: [B, H, S, D]. Pads S to a block multiple; padded kv rows are
    masked out by the causal mask (they sit after every real query)."""
    b, h, s, d = q.shape
    bq = _pow2_block(s, block_q)
    bk = min(_pow2_block(s, block_k), bq)
    flat = lambda x: x.reshape(b * h, s, d)
    qf, kf, vf = flat(q), flat(k), flat(v)
    qf, pad = _pad_to(qf, 1, bq)
    kf, _ = _pad_to(kf, 1, bq)
    vf, _ = _pad_to(vf, 1, bq)
    out = flash_attention_pallas(qf, kf, vf, causal=causal, window=window,
                                 block_q=bq, block_k=bk)
    out = out[:, :s] if pad else out
    return out.reshape(b, h, s, d)


@functools.partial(jax.jit, static_argnames=("k", "block_t"))
def moe_router_topk(logits, k: int, *, block_t: int = 256):
    """logits: [T, E] -> (gates [T, k] fp32, idx [T, k] int32)."""
    lp, pad = _pad_to(logits, 0, block_t)
    gates, idx = moe_router_pallas(lp, k=k, block_t=block_t)
    if pad:
        gates, idx = gates[:logits.shape[0]], idx[:logits.shape[0]]
    return gates, idx


def ssd_chunk(C, B, acum, dt, x):
    """Fused SSD intra-chunk op. See ssd_chunk.py for shapes."""
    return ssd_chunk_pallas(C, B, acum, dt, x)
