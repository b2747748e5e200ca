"""Multi-head latent attention (DeepSeek-V2/V3 MLA), training/prefill form.

Per token ``x`` (H heads; widths from ``cfg.mla``):

* ``q = x W_q`` → [H, nope + rope], split ``q_nope | q_rope``;
* ``[c_kv | k_rope] = x W_kva``, then ``c_kv ← RMSNorm(c_kv)``;
* ``[k_nope | v]`` per head ``= c_kv W_kvb``;
* rotary embedding on adjacent pairs of ``q_rope`` and ``k_rope``
  (DeepSeek-V3's layout); ``k_rope`` is one per token, shared by all heads;
* ``score = (q_nope·k_nope + q_rope·k_rope) / √(nope + rope)``, causal
  softmax, ``Σ p·v`` → [H, v], then ``W_o``.

Training computes this expanded form; the absorbed decode form (scores
against the cached latent) is not implemented. Up to
``BLOCKED_ATTN_THRESHOLD`` positions the scores are ``_sdpa``'s whole
[S, S] matrix. Above it they go through ``blocked_attention_sdpa``, as for
GQA: on the TPU the Pallas splash flash kernel (q·k and v widths of their
own, its backward the kernel's ``dq`` and ``dkv`` kernels), in scope
``mla`` like the rest of this function; on the CPU the jnp online-softmax
path.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.models.attention import (BLOCKED_ATTN_THRESHOLD, _sdpa,
                                    blocked_attention_sdpa, causal_mask)
from repro.models.layers import Builder, rms_norm, rope_frequencies
from repro.sharding import constrain


def init_mla(b: Builder, cfg: ModelConfig):
    m, d, h = cfg.mla, cfg.d_model, cfg.num_heads
    b.normal("wq", (d, h, m.qk_head_dim), ("embed", "heads", "head_dim"))
    b.normal("wkv_a", (d, m.kv_lora_rank + m.qk_rope_head_dim),
             ("embed", "kv_lora"))
    b.ones("kv_norm", (m.kv_lora_rank,), ("kv_lora",))
    b.normal("wkv_b", (m.kv_lora_rank, h, m.qk_nope_head_dim + m.v_head_dim),
             ("kv_lora", "heads", "head_dim"))
    b.normal("wo", (h, m.v_head_dim, d), ("heads", "head_dim", "embed"))


def rope_pairs(x, positions, theta):
    """Rotary embedding on adjacent pairs (x[2i], x[2i+1]) at frequency
    theta^(-2i/d). x: [B, S, H, d]; positions: [B, S]."""
    d = x.shape[-1]
    freqs = jnp.asarray(rope_frequencies(d, theta))              # [d/2]
    ang = positions[..., None].astype(jnp.float32) * freqs       # [B,S,d/2]
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    xf = x.astype(jnp.float32).reshape(x.shape[:-1] + (d // 2, 2))
    x0, x1 = xf[..., 0], xf[..., 1]
    out = jnp.stack([x0 * cos - x1 * sin, x0 * sin + x1 * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def mla(params, cfg: ModelConfig, x, positions):
    """Causal self-attention. x: [B,S,D], positions: [B,S] -> [B,S,D]."""
    m, h = cfg.mla, cfg.num_heads
    nope, rope = m.qk_nope_head_dim, m.qk_rope_head_dim
    with jax.named_scope("mla"):
        q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
        kva = jnp.einsum("bsd,dk->bsk", x, params["wkv_a"])
        c_kv = rms_norm(kva[..., :m.kv_lora_rank], params["kv_norm"],
                        cfg.norm_eps)
        k_rope = kva[..., m.kv_lora_rank:][:, :, None]           # [B,S,1,r]
        kv = jnp.einsum("bsc,chk->bshk", c_kv, params["wkv_b"])
        q_rope = rope_pairs(q[..., nope:], positions, cfg.rope_theta)
        k_rope = rope_pairs(k_rope, positions, cfg.rope_theta)
        q = jnp.concatenate([q[..., :nope], q_rope], axis=-1)
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_rope, kv.shape[:3] + (rope,))],
            axis=-1)
        v = kv[..., nope:]
        q = constrain(q, "batch", "seq", "heads", None)
        k = constrain(k, "batch", "seq", "heads", None)
        v = constrain(v, "batch", "seq", "heads", None)
        if x.shape[1] > BLOCKED_ATTN_THRESHOLD:
            out = blocked_attention_sdpa(q, k, v, causal=True)
        else:
            out = _sdpa(q, k, v, causal_mask(x.shape[1], x.shape[1]), h)
        out = constrain(out, "batch", "seq", "heads", None)
        return jnp.einsum("bshk,hkd->bsd", out, params["wo"])
