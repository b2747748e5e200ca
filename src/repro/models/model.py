"""Top-level models: CausalLM (dense/moe/ssm/hybrid/vlm) and the Whisper-style
encoder-decoder. Pure-functional API:

    params = init_params(key, cfg)
    axes   = param_axes(cfg)            # logical axes tree, same structure
    logits, aux = forward(params, cfg, batch)
    loss, metrics = loss_fn(params, cfg, batch)
    cache  = init_cache(cfg, batch, seq_len)
    logits, cache = decode_step(params, cfg, tokens, cache, pos)

``batch`` is a dict: tokens [B,S] (+ labels for training; + vision_embeds
[B,V,D] for vlm; + frame_embeds [B,F,D] for audio — the stubbed frontends).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.models import blocks
from repro.models.attention import (BLOCKED_ATTN_THRESHOLD,
                                    bidirectional_attention)
from repro.models.layers import (Builder, embed, gelu_mlp, init_embed,
                                 init_gelu_mlp, rms_norm, sinusoidal_at,
                                 sinusoidal_positions, unembed)
from repro.sharding import constrain


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _init_encoder(b: Builder, cfg: ModelConfig):
    """Whisper-style encoder: bidirectional attn + GELU MLP blocks over the
    (stubbed) conv frame embeddings."""
    for i in range(cfg.num_encoder_layers):
        lb = b.sub(str(i))
        lb.ones("ln1", (cfg.d_model,), ("embed",))
        from repro.models.attention import init_attention
        init_attention(lb.sub("attn"), cfg)
        lb.ones("ln2", (cfg.d_model,), ("embed",))
        init_gelu_mlp(lb.sub("mlp"), cfg.d_model, cfg.d_ff)
    b.ones("ln_post", (cfg.d_model,), ("embed",))


def _init_vlm_projector(b: Builder, cfg: ModelConfig):
    """MLP projector from (stub) vision embeddings to LM space. The ViT
    itself is stubbed per the assignment carve-out: inputs arrive already
    patch-embedded at d_model width."""
    b.normal("w1", (cfg.d_model, cfg.d_model), ("embed", "mlp"))
    b.normal("w2", (cfg.d_model, cfg.d_model), ("mlp", "embed"))
    b.ones("ln", (cfg.d_model,), ("embed",))


def _build(key, cfg: ModelConfig, abstract: bool = False):
    b = Builder(key, jnp.dtype(cfg.dtype), abstract)
    init_embed(b, cfg)
    if cfg.is_encoder_decoder:
        _init_encoder(b.sub("encoder"), cfg)
    if cfg.family == "vlm":
        _init_vlm_projector(b.sub("projector"), cfg)
    blocks.init_stack(b, cfg, cross=cfg.is_encoder_decoder)
    b.ones("ln_f", (cfg.d_model,), ("embed",))
    return b


def init_params(key, cfg: ModelConfig):
    return _build(key, cfg).params


def param_axes(cfg: ModelConfig):
    """Logical-axes tree (no allocation)."""
    return _build(jax.random.PRNGKey(0), cfg, abstract=True).axes


def abstract_params(cfg: ModelConfig):
    """Param ShapeDtypeStructs without allocating (for the dry-run)."""
    return _build(jax.random.PRNGKey(0), cfg, abstract=True).params


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _encoder_forward(params, cfg: ModelConfig, frames):
    """frames: [B, F, D] stub conv outputs -> encoder states [B, F, D]."""
    x = frames + sinusoidal_positions(frames.shape[1],
                                      cfg.d_model).astype(frames.dtype)
    for i in range(cfg.num_encoder_layers):
        p = params["encoder"][str(i)]
        h = rms_norm(x, p["ln1"], cfg.norm_eps)
        x = x + bidirectional_attention(p["attn"], cfg, h)
        h = rms_norm(x, p["ln2"], cfg.norm_eps)
        x = x + gelu_mlp(p["mlp"], h)
    return rms_norm(x, params["encoder"]["ln_post"], cfg.norm_eps)


def _vlm_prefix(params, cfg: ModelConfig, vision_embeds):
    p = params["projector"]
    h = rms_norm(vision_embeds, p["ln"], cfg.norm_eps)
    return jnp.einsum("bvd,de->bve", jax.nn.gelu(
        jnp.einsum("bvd,de->bve", h, p["w1"])), p["w2"])


def _embed_inputs(params, cfg: ModelConfig, batch):
    """Returns (x [B,S',D], positions [B,S'], text_offset, enc_out)."""
    tokens = batch["tokens"]
    x = embed(params, tokens)
    enc_out = None
    offset = 0
    if cfg.family == "vlm" and "vision_embeds" in batch:
        vis = _vlm_prefix(params, cfg, batch["vision_embeds"].astype(x.dtype))
        x = jnp.concatenate([vis, x], axis=1)
        offset = vis.shape[1]
    if cfg.is_encoder_decoder:
        enc_out = _encoder_forward(params, cfg,
                                   batch["frame_embeds"].astype(x.dtype))
        x = x + sinusoidal_positions(x.shape[1],
                                     cfg.d_model).astype(x.dtype)[None]
    b_, s = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b_, s))
    return x, positions, offset, enc_out


def _hidden(params, cfg: ModelConfig, batch, moe_strategy):
    """Final-norm hidden states [B,S',D], text offset, aux loss, MoE stats."""
    x, positions, offset, enc_out = _embed_inputs(params, cfg, batch)
    x = constrain(x, "batch", "act_seq", "embed")
    x, aux, stats = blocks.stack_apply(params, cfg, x, positions,
                                       window=cfg.sliding_window,
                                       enc_out=enc_out,
                                       moe_strategy=moe_strategy)
    return rms_norm(x, params["ln_f"], cfg.norm_eps), offset, aux, stats


def forward(params, cfg: ModelConfig, batch, *, moe_strategy="grouped"):
    """Training/prefill forward. Returns (logits [B,S',V], aux_loss)."""
    x, offset, aux, _ = _hidden(params, cfg, batch, moe_strategy)
    logits = unembed(params, x, cfg.tie_embeddings)
    logits = constrain(logits, "batch", "act_seq", "vocab")
    if offset:
        logits = logits[:, offset:]
    return logits, aux


LOSS_CHUNK = 1024   # above BLOCKED_ATTN_THRESHOLD positions the loss runs
                    # over chunks of this many, never holding [B, S, V]


def _chunked_ce(params, cfg: ModelConfig, x, labels):
    """Next-token cross-entropy over chunks of LOSS_CHUNK positions, each
    chunk's logits recomputed in the backward pass. x: [B,S,D]."""
    b_, s, d = x.shape
    n = s // LOSS_CHUNK
    targets = jnp.concatenate(
        [labels[:, 1:], jnp.full((b_, 1), -1, labels.dtype)], axis=1)
    xs = x.reshape(b_, n, LOSS_CHUNK, d).swapaxes(0, 1)
    ts = targets.reshape(b_, n, LOSS_CHUNK).swapaxes(0, 1)

    @jax.checkpoint
    def chunk(carry, inp):
        xc, tc = inp
        logits = unembed(params, xc, cfg.tie_embeddings).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        ll = jnp.take_along_axis(logp, jnp.maximum(tc, 0)[..., None],
                                 axis=-1)[..., 0]
        mask = (tc >= 0).astype(jnp.float32)
        return (carry[0] + (ll * mask).sum(), carry[1] + mask.sum()), None

    zero = jnp.zeros((), jnp.float32)
    (ll, count), _ = jax.lax.scan(chunk, (zero, zero), (xs, ts))
    return -ll / jnp.maximum(count, 1.0)


def loss_fn(params, cfg: ModelConfig, batch, *, moe_strategy="grouped"):
    """Next-token cross-entropy (+ MoE aux). Returns (loss, metrics);
    ``metrics["moe"]`` holds the MoE layers' stats where they have any."""
    x, offset, aux, stats = _hidden(params, cfg, batch, moe_strategy)
    labels = batch["labels"]
    if (not offset and x.shape[1] > BLOCKED_ATTN_THRESHOLD
            and x.shape[1] % LOSS_CHUNK == 0):
        ce = _chunked_ce(params, cfg, x, labels)
    else:
        logits = unembed(params, x, cfg.tie_embeddings)
        logits = constrain(logits, "batch", "act_seq", "vocab")
        if offset:
            logits = logits[:, offset:]
        logits = logits[:, :-1].astype(jnp.float32)
        targets = labels[:, 1:]
        logp = jax.nn.log_softmax(logits, axis=-1)
        ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        mask = (targets >= 0).astype(jnp.float32)
        ce = -(ll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    aux_w = cfg.moe.router_aux_weight if cfg.moe is not None else 0.0
    loss = ce + aux_w * aux
    metrics = {"ce": ce, "aux": aux}
    if stats:
        metrics["moe"] = stats
    return loss, metrics


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, seq_len: int):
    return blocks.init_stack_cache(cfg, batch, seq_len,
                                   window=cfg.sliding_window)


def cache_axes(cfg: ModelConfig):
    return blocks.stack_cache_axes(cfg)


def decode_step(params, cfg: ModelConfig, tokens, cache, pos, *,
                enc_out=None, batch=None, moe_strategy="dense"):
    """One-token decode. tokens: [B,1]; pos: int32 scalar (absolute).
    For enc-dec pass ``batch`` with frame_embeds (or a precomputed enc_out).
    Returns (logits [B,1,V], new_cache)."""
    x = embed(params, tokens)
    if cfg.is_encoder_decoder:
        if enc_out is None:
            enc_out = _encoder_forward(params, cfg,
                                       batch["frame_embeds"].astype(x.dtype))
        x = x + sinusoidal_at(jnp.asarray(pos), cfg.d_model)[None, None].astype(
            x.dtype)
    x = constrain(x, "batch", None, "embed")
    x, cache = blocks.stack_decode(params, cfg, x, cache, pos,
                                   window=cfg.sliding_window, enc_out=enc_out,
                                   moe_strategy=moe_strategy)
    x = rms_norm(x, params["ln_f"], cfg.norm_eps)
    logits = unembed(params, x, cfg.tie_embeddings)
    return logits, cache
