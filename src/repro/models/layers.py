"""Core layers: param builder, norms, RoPE, MLPs, embeddings.

Parameters are plain nested dicts of jnp arrays. Each init function is
mirrored by an ``*_axes`` twin returning the same-structure tree of logical
axis tuples (consumed by launch/sharding.py to build PartitionSpecs). A
property test asserts the two trees always match structurally.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ModelConfig
from repro.sharding import constrain


# ---------------------------------------------------------------------------
# Param builder
# ---------------------------------------------------------------------------

class Builder:
    """Splits one PRNG key into named params; records logical axes.

    ``abstract=True`` records ShapeDtypeStructs instead of allocating —
    used for the dry-run's 1T-param models and for ``param_axes`` (the axes
    tree must be derivable without touching device memory).
    """

    def __init__(self, key, dtype, abstract: bool = False):
        self.key = key
        self.dtype = dtype
        self.abstract = abstract
        self.params = {}
        self.axes = {}

    def _next(self):
        if self.abstract:
            return self.key
        self.key, sub = jax.random.split(self.key)
        return sub

    def _put(self, name, shape, axes, make, dtype=None):
        assert len(shape) == len(axes), (name, shape, axes)
        if self.abstract:
            self.params[name] = jax.ShapeDtypeStruct(tuple(shape),
                                                     dtype or self.dtype)
        else:
            self.params[name] = make()
        self.axes[name] = tuple(axes)
        return self.params[name]

    def normal(self, name, shape, axes, scale=0.02):
        return self._put(name, shape, axes, lambda: (
            scale * jax.random.normal(self._next(), shape, jnp.float32)
        ).astype(self.dtype))

    def zeros(self, name, shape, axes, dtype=None):
        dtype = dtype or self.dtype
        return self._put(name, shape, axes,
                         lambda: jnp.zeros(shape, dtype), dtype)

    def ones(self, name, shape, axes):
        return self._put(name, shape, axes,
                         lambda: jnp.ones(shape, self.dtype))

    def const(self, name, value, axes):
        shape = np.shape(value)
        return self._put(name, shape, [axes[i] for i in range(len(shape))]
                         if len(axes) == len(shape) else axes,
                         lambda: jnp.asarray(value, self.dtype))

    def sub(self, name):
        b = Builder(self._next(), self.dtype, self.abstract)
        self.params[name] = b.params
        self.axes[name] = b.axes
        return b


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rms_norm(x, weight, eps):
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * weight.astype(jnp.float32)).astype(dt)


def head_rms_norm(x, weight, eps):
    """Per-head RMSNorm over head_dim (Qwen3 qk_norm). x: [..., H, hd]."""
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * weight.astype(jnp.float32)).astype(dt)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float):
    half = head_dim // 2
    return 1.0 / (theta ** (np.arange(0, half, dtype=np.float32) / half))


def apply_rope(x, positions, theta):
    """x: [B, S, H, hd]; positions: [B, S] (absolute). Pairs are split-half."""
    if theta <= 0:
        return x
    hd = x.shape[-1]
    freqs = jnp.asarray(rope_frequencies(hd, theta))            # [hd/2]
    angles = positions[..., None].astype(jnp.float32) * freqs   # [B, S, hd/2]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(seq_len: int, d_model: int):
    """Whisper-style fixed sinusoidal embedding table [S, D]."""
    half = d_model // 2
    freqs = np.exp(-np.log(10_000.0) * np.arange(half) / max(half - 1, 1))
    pos = np.arange(seq_len)[:, None] * freqs[None, :]
    return jnp.asarray(
        np.concatenate([np.sin(pos), np.cos(pos)], axis=1), jnp.float32)


def sinusoidal_at(pos, d_model: int):
    """Sinusoidal embedding [D] for a (possibly traced) scalar position."""
    half = d_model // 2
    freqs = jnp.exp(-jnp.log(10_000.0) * jnp.arange(half) / max(half - 1, 1))
    angles = pos.astype(jnp.float32) * freqs
    return jnp.concatenate([jnp.sin(angles), jnp.cos(angles)])


# ---------------------------------------------------------------------------
# Dense FFNs
# ---------------------------------------------------------------------------

def init_mlp(b: Builder, d_model: int, d_ff: int):
    b.normal("wi", (d_model, d_ff), ("embed", "mlp"))
    b.normal("wg", (d_model, d_ff), ("embed", "mlp"))
    b.normal("wo", (d_ff, d_model), ("mlp", "embed"))


def mlp(params, x):
    """SwiGLU MLP. x: [..., D]."""
    h = jnp.einsum("...d,df->...f", x, params["wi"])
    g = jnp.einsum("...d,df->...f", x, params["wg"])
    h = jax.nn.silu(g) * h
    h = constrain(h, "batch", "seq", "mlp")
    return jnp.einsum("...f,fd->...d", h, params["wo"])


def init_gelu_mlp(b: Builder, d_model: int, d_ff: int):
    b.normal("wi", (d_model, d_ff), ("embed", "mlp"))
    b.zeros("bi", (d_ff,), ("mlp",))
    b.normal("wo", (d_ff, d_model), ("mlp", "embed"))
    b.zeros("bo", (d_model,), ("embed",))


def gelu_mlp(params, x):
    h = jax.nn.gelu(jnp.einsum("...d,df->...f", x, params["wi"]) + params["bi"])
    return jnp.einsum("...f,fd->...d", h, params["wo"]) + params["bo"]


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------

def init_embed(b: Builder, cfg: ModelConfig):
    b.normal("embedding", (cfg.vocab_size, cfg.d_model), ("vocab", "embed"),
             scale=0.01)
    if not cfg.tie_embeddings:
        b.normal("lm_head", (cfg.d_model, cfg.vocab_size), ("embed", "vocab"))


def embed(params, tokens):
    return jnp.take(params["embedding"], tokens, axis=0)


def unembed(params, x, tie: bool):
    if tie:
        return jnp.einsum("...d,vd->...v", x, params["embedding"])
    return jnp.einsum("...d,dv->...v", x, params["lm_head"])
