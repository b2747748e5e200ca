"""Attention: GQA/MQA/MHA with RoPE, qk_norm, bias, causal and sliding-window
masks, KV-cache decode (ring buffer for sliding window), optional cross-attn.

Up to ``BLOCKED_ATTN_THRESHOLD`` positions, training and prefill take
``_sdpa`` (the whole [S, S] score matrix) on every platform. Above it they
take ``blocked_attention_sdpa``: on one TPU device the Pallas splash flash
kernel shipped with JAX (forward, ``dq`` and ``dkv`` kernels under one custom
VJP); on the CPU, and under a mesh that the compiler partitions, a pure-jnp
online-softmax schedule that is also the kernel's reference in the tests.
``repro.kernels.flash_attention`` is on no model path.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.models.layers import Builder, apply_rope, head_rms_norm
from repro.sharding import constrain, partitioned


def init_attention(b: Builder, cfg: ModelConfig, cross: bool = False):
    d, hd = cfg.d_model, cfg.head_dim
    nq, nkv = cfg.num_heads, cfg.num_kv_heads
    b.normal("wq", (d, nq, hd), ("embed", "heads", "head_dim"))
    b.normal("wk", (d, nkv, hd), ("embed", "kv_heads", "head_dim"))
    b.normal("wv", (d, nkv, hd), ("embed", "kv_heads", "head_dim"))
    b.normal("wo", (nq, hd, d), ("heads", "head_dim", "embed"))
    if cfg.qkv_bias:
        b.zeros("bq", (nq, hd), ("heads", "head_dim"))
        b.zeros("bk", (nkv, hd), ("kv_heads", "head_dim"))
        b.zeros("bv", (nkv, hd), ("kv_heads", "head_dim"))
    if cfg.qk_norm:
        b.ones("q_norm", (hd,), ("head_dim",))
        b.ones("k_norm", (hd,), ("head_dim",))


def _project_qkv(params, cfg: ModelConfig, x, kv_x, positions, kv_positions,
                 rope: bool):
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    k = jnp.einsum("bsd,dhk->bshk", kv_x, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", kv_x, params["wv"])
    if cfg.qkv_bias:
        q = q + params["bq"]
        k = k + params["bk"]
        v = v + params["bv"]
    if cfg.qk_norm:
        q = head_rms_norm(q, params["q_norm"], cfg.norm_eps)
        k = head_rms_norm(k, params["k_norm"], cfg.norm_eps)
    if rope and cfg.rope_theta > 0:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, kv_positions, cfg.rope_theta)
    return q, k, v


def _sdpa(q, k, v, mask, num_kv_heads):
    """q, k: [B,Sq|Sk,Hq|Hkv,hd]; v: [B,Sk,Hkv,dv] (dv may differ from hd,
    as in MLA); mask: [B,1,Sq,Sk] or None. Returns [B,Sq,Hq,dv]."""
    b_, sq, hq, hd = q.shape
    hkv = k.shape[2]
    group = hq // hkv
    q = q.reshape(b_, sq, hkv, group, hd)
    scores = jnp.einsum("bqhgk,bshk->bhgqs", q, k).astype(jnp.float32)
    scores = scores / jnp.sqrt(hd).astype(jnp.float32)
    if mask is not None:
        scores = jnp.where(mask[:, :, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhgqs,bshk->bqhgk", probs, v)
    return out.reshape(b_, sq, hq, v.shape[-1])


BLOCKED_ATTN_THRESHOLD = 2048   # use the memory-linear path above this S

# The flash kernel's tiles: query rows a block, keys a block held in VMEM,
# and keys a matmul within it. Chosen on a v5e from one microbenchmark of a
# layer's forward and backward at 2 pods x 16 heads x 8,192 positions, q.k
# 192 and v 128 (benchmarks/flash_tiles.py; the table is in PERF.md). The
# backward is splash's separate dq and dkv kernels: its fused one sums a
# bfloat16 dq partial per key block.
FLASH_BLOCK_Q = 512
FLASH_BLOCK_KV = 1024
FLASH_BLOCK_KV_COMPUTE = 512


def flash_tiling(s: int, block_q: int = FLASH_BLOCK_Q,
                 block_kv: int = FLASH_BLOCK_KV,
                 block_kv_compute: int = FLASH_BLOCK_KV_COMPUTE):
    """splash ``BlockSizes`` for causal self-attention over ``s`` positions
    (each block capped at ``s``; the dq and dkv kernels take the forward's
    tiles), or None where ``s`` is not a multiple of every block, or a key
    block is not a multiple of the 128 lanes: such shapes stay on the jnp
    path."""
    from jax.experimental.pallas.ops.tpu.splash_attention import BlockSizes

    bq, bkv = min(block_q, s), min(block_kv, s)
    bkc = min(block_kv_compute, bkv)
    if s % bq or s % bkv or bkv % bkc or bkc % 128:
        return None
    return BlockSizes(block_q=bq, block_kv=bkv, block_kv_compute=bkc,
                      block_q_dkv=bq, block_kv_dkv=bkv,
                      block_kv_dkv_compute=bkc, block_q_dq=bq,
                      block_kv_dq=bkv)


@functools.lru_cache(maxsize=None)
def _splash_kernel(s: int, heads: int, tiles, interpret: bool):
    """The causal splash kernel for ``heads`` query heads over ``s``
    positions, built once per shape: its mask's block tables take about a
    second of host time at 8,192 positions. They are made eagerly, as
    constants, even when the first call is being traced."""
    from jax.experimental.pallas.ops.tpu import splash_attention as sa

    mask = sa.MultiHeadMask([sa.CausalMask((s, s))] * heads)
    with jax.ensure_compile_time_eval():
        return sa.make_splash_mha_single_device(mask, block_sizes=tiles,
                                                interpret=interpret)


def flash_attention_sdpa(q, k, v, *, tiles=None, interpret: bool = False):
    """Causal self-attention as the Pallas splash flash kernel: scores,
    softmax statistics and accumulators stay in VMEM; only q, k, v, the
    output and a per-row log-sum-exp touch HBM. Its backward is the
    kernel's own ``dq`` and ``dkv`` kernels, which compute the scores
    again from q, k and the log-sum-exp.

    q, k: [B,S,Hq|Hkv,hd]; v: [B,S,Hkv,dv] (dv may differ from hd; Hkv
    divides Hq). q is scaled by 1/sqrt(hd) in f32, then cast back to its
    dtype. Every matmul operand has the inputs' dtype: the scaled q, and
    the probabilities p and score gradients ds that the backward kernels
    round to it before the dv, dk and dq matmuls (``blocked_attention_jnp``
    keeps p in f32). Scores, statistics and accumulators are f32.
    ``tiles`` defaults to ``flash_tiling(S)``, which must not be None.
    Returns [B,S,Hq,dv].
    """
    s, hq, hd = q.shape[1:]
    tiles = flash_tiling(s) if tiles is None else tiles
    kernel = _splash_kernel(s, hq, tiles, interpret)
    q = (q.astype(jnp.float32) * (1.0 / hd ** 0.5)).astype(q.dtype)
    heads_first = lambda x: x.swapaxes(1, 2)               # [B,H,S,d]
    out = jax.vmap(kernel)(heads_first(q), heads_first(k), heads_first(v))
    return out.swapaxes(1, 2)


def blocked_attention_sdpa(q, k, v, *, causal: bool = True, window: int = 0,
                           block_q: int = 512, block_k: int = 1024):
    """Attention that never materializes the [S, S] score matrix — what
    makes 4k-train/32k-prefill lowerable. On the TPU, causal
    self-attention (Sq = Sk) with no window, over a length that
    ``flash_tiling`` tiles, outside a mesh that the compiler partitions
    (``sharding.partitioned``), is the Pallas flash kernel
    (``flash_attention_sdpa``). Everything else, and every shape on the
    CPU, is ``blocked_attention_jnp`` with ``block_q`` / ``block_k``:
    sliding windows (the kernel's local mask is not wired), cross-length
    or non-causal calls, lengths that are not a multiple of the kernel's
    tiles, and calls split over several devices by GSPMD (which cannot
    partition a Mosaic kernel, and shards the jnp path by batch and
    heads).

    q, k: [B,Sq|Sk,Hq|Hkv,hd]; v: [B,Sk,Hkv,dv]. Returns [B,Sq,Hq,dv].
    """
    def jnp_path(q, k, v):
        return blocked_attention_jnp(q, k, v, causal=causal, window=window,
                                     block_q=block_q, block_k=block_k)

    if (causal and window == 0 and q.shape[1] == k.shape[1]
            and flash_tiling(q.shape[1]) is not None and not partitioned()):
        return jax.lax.platform_dependent(q, k, v, tpu=flash_attention_sdpa,
                                          default=jnp_path)
    return jnp_path(q, k, v)


def blocked_attention_jnp(q, k, v, *, causal: bool = True, window: int = 0,
                          block_q: int = 512, block_k: int = 1024):
    """Flash-style online-softmax attention in pure jnp (lax.scan over query
    and kv tiles + checkpointed inner body): ``blocked_attention_sdpa``'s
    path off the TPU and for the shapes the kernel does not take.

    q, k: [B,Sq|Sk,Hq|Hkv,hd]; v: [B,Sk,Hkv,dv]. Returns [B,Sq,Hq,dv].
    """
    b_, sq, hq, hd = q.shape
    dv = v.shape[-1]
    sk, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    bq = min(block_q, sq)
    bk = min(block_k, sk)
    pad_q = (-sq) % bq
    pad_k = (-sk) % bk
    qp = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0))) if pad_q else q
    kp = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0))) if pad_k else k
    vp = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0))) if pad_k else v
    nq, nk = qp.shape[1] // bq, kp.shape[1] // bk
    qp = qp.reshape(b_, nq, bq, hkv, g, hd)
    kp = kp.reshape(b_, nk, bk, hkv, hd)
    vp = vp.reshape(b_, nk, bk, hkv, dv)
    scale = 1.0 / (hd ** 0.5)

    def q_step(_, inp):
        q_blk, qi = inp
        q0 = qi * bq

        def kv_step(carry, inp):
            k_blk, v_blk, k0 = inp

            def update(carry):
                acc, m, l = carry
                s = jnp.einsum("bqhgd,bkhd->bhgqk", q_blk, k_blk,
                               preferred_element_type=jnp.float32) * scale
                qpos = q0 + jnp.arange(bq)[:, None]
                kpos = k0 + jnp.arange(bk)[None, :]
                msk = kpos < sk                             # kv padding
                if causal:
                    msk &= kpos <= qpos
                if window > 0:
                    msk &= kpos > qpos - window
                s = jnp.where(msk[None, None, None], s, -1e30)
                m_new = jnp.maximum(m, s.max(axis=-1))
                p = jnp.exp(s - m_new[..., None])
                alpha = jnp.exp(m - m_new)
                l_new = l * alpha + p.sum(axis=-1)
                acc_new = acc * alpha[..., None] + jnp.einsum(
                    "bhgqk,bkhd->bhgqd", p, v_blk.astype(jnp.float32),
                    preferred_element_type=jnp.float32)
                return acc_new, m_new, l_new

            # a tile wholly above the diagonal (or before the window) adds
            # nothing: skip its work
            live = jnp.bool_(True)
            if causal:
                live &= k0 <= q0 + bq - 1
            if window > 0:
                live &= k0 + bk - 1 > q0 - window
            return jax.lax.cond(live, update, lambda c: c, carry), None

        kv_step = jax.checkpoint(kv_step, prevent_cse=False)
        acc0 = jnp.zeros((b_, hkv, g, bq, dv), jnp.float32)
        m0 = jnp.full((b_, hkv, g, bq), -1e30, jnp.float32)
        l0 = jnp.zeros((b_, hkv, g, bq), jnp.float32)
        (acc, m, l), _ = jax.lax.scan(
            kv_step, (acc0, m0, l0),
            (kp.swapaxes(0, 1), vp.swapaxes(0, 1),
             jnp.arange(nk) * bk))
        out = (acc / jnp.maximum(l, 1e-20)[..., None]).astype(q.dtype)
        return None, out                                     # [b,hkv,g,bq,dv]

    # only each query tile's inputs are kept for the backward pass; its
    # kv sweep is recomputed there
    q_step = jax.checkpoint(q_step, prevent_cse=False)

    _, outs = jax.lax.scan(q_step, None,
                           (qp.swapaxes(0, 1), jnp.arange(nq)))
    out = jnp.moveaxis(outs, 0, 3)                 # [b,hkv,g,nq,bq,hd]
    out = out.reshape(b_, hkv, g, nq * bq, dv)[:, :, :, :sq]
    out = jnp.moveaxis(out, 3, 1).reshape(b_, sq, hq, dv)
    return out


def causal_mask(sq: int, sk: int, window: int = 0, offset: int = 0):
    """[1, 1, Sq, Sk] boolean; query i (absolute pos offset+i) sees keys
    j<=pos and, if window>0, j > pos - window."""
    qpos = jnp.arange(sq)[:, None] + offset
    kpos = jnp.arange(sk)[None, :]
    m = kpos <= qpos
    if window > 0:
        m &= kpos > qpos - window
    return m[None, None]


def attention(params, cfg: ModelConfig, x, positions, *, window: int = 0):
    """Training/prefill self-attention. x: [B,S,D], positions: [B,S]."""
    q, k, v = _project_qkv(params, cfg, x, x, positions, positions, rope=True)
    q = constrain(q, "batch", "seq", "heads", None)
    k = constrain(k, "batch", "seq", "kv_heads", None)
    v = constrain(v, "batch", "seq", "kv_heads", None)
    if x.shape[1] > BLOCKED_ATTN_THRESHOLD:
        out = blocked_attention_sdpa(q, k, v, causal=True, window=window)
    else:
        mask = causal_mask(x.shape[1], x.shape[1], window)
        out = _sdpa(q, k, v, mask, cfg.num_kv_heads)
    out = constrain(out, "batch", "seq", "heads", None)
    return jnp.einsum("bshk,hkd->bsd", out, params["wo"])


def cross_attention(params, cfg: ModelConfig, x, enc_out):
    """Decoder cross-attn over encoder states (no mask, no rope)."""
    q, k, v = _project_qkv(params, cfg, x, enc_out, None, None, rope=False)
    out = _sdpa(q, k, v, None, cfg.num_kv_heads)
    return jnp.einsum("bshk,hkd->bsd", out, params["wo"])


def bidirectional_attention(params, cfg: ModelConfig, x):
    """Encoder self-attention (whisper encoder)."""
    q, k, v = _project_qkv(params, cfg, x, x, None, None, rope=False)
    out = _sdpa(q, k, v, None, cfg.num_kv_heads)
    return jnp.einsum("bshk,hkd->bsd", out, params["wo"])


# ---------------------------------------------------------------------------
# Decode path with KV cache
# ---------------------------------------------------------------------------

def init_kv_cache(cfg: ModelConfig, batch: int, seq_len: int, window: int = 0):
    """One layer's cache. Sliding-window layers use a ring buffer of size
    ``window`` (memory win: long_500k dense decode holds 4k, not 512k)."""
    cache_len = min(seq_len, window) if window > 0 else seq_len
    shape = (batch, cache_len, cfg.num_kv_heads, cfg.head_dim)
    dt = jnp.dtype(cfg.dtype)
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}


def kv_cache_axes():
    # kv_heads -> model when divisible, else head_dim picks up the model
    # axis (resolve_spec fallback chain) — critical for decode cache memory.
    ax = ("batch", "seq", "kv_heads", "head_dim")
    return {"k": ax, "v": ax}


def decode_attention(params, cfg: ModelConfig, x, cache, pos, *,
                     window: int = 0):
    """One-token decode. x: [B,1,D]; cache k/v: [B,C,Hkv,hd]; pos: scalar
    int32 (current absolute position). Returns (out [B,1,D], new_cache).
    """
    b_ = x.shape[0]
    positions = jnp.full((b_, 1), pos, jnp.int32)
    q, k_new, v_new = _project_qkv(params, cfg, x, x, positions, positions,
                                   rope=True)
    cache_len = cache["k"].shape[1]
    slot = (pos % cache_len) if window > 0 else pos
    k = jax.lax.dynamic_update_slice(cache["k"], k_new, (0, slot, 0, 0))
    v = jax.lax.dynamic_update_slice(cache["v"], v_new, (0, slot, 0, 0))
    k = constrain(k, "batch", None, "kv_heads", "head_dim")
    v = constrain(v, "batch", None, "kv_heads", "head_dim")
    # valid mask: ring buffer entries written so far & inside the window
    idx = jnp.arange(cache_len)
    if window > 0:
        valid = (idx <= pos % cache_len) | (pos >= cache_len)
    else:
        valid = idx <= pos
    mask = valid[None, None, None, :]                 # [1,1,1,C]
    out = _sdpa(q, k, v, mask, cfg.num_kv_heads)
    out = jnp.einsum("bshk,hkd->bsd", out, params["wo"])
    return out, {"k": k, "v": v}
