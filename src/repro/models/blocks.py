"""Block assembly: pre-norm residual blocks of four kinds (attention+dense,
attention+MoE, mamba, mamba+MoE), plus the scan-over-layers machinery.

Heterogeneous stacks (jamba's 1:7 attn:mamba interleave, deepseek/kimi's
dense-first-layer) are handled by factoring the layer schedule into
``prefix + pattern * repeats``: prefix layers run unscanned; the repeated
pattern becomes one ``lax.scan`` whose body applies the pattern positions in
order, with per-position parameter stacks. This keeps the lowered HLO small
(one pattern body, not num_layers copies) — essential for the 61-layer/1T
dry-run compile.
"""
from __future__ import annotations

from typing import List, Tuple

import jax
import jax.numpy as jnp

from repro.config import (ATTN_DENSE, ATTN_MOE, MAMBA, MAMBA_MOE, ModelConfig)
from repro.models import attention as attn_mod
from repro.models import mla as mla_mod
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import (Builder, gelu_mlp, init_gelu_mlp, init_mlp,
                                 mlp, rms_norm)
from repro.sharding import constrain


# ---------------------------------------------------------------------------
# Schedule factoring
# ---------------------------------------------------------------------------

def factor_schedule(schedule: Tuple[str, ...]):
    """Return (prefix_len, pattern, repeats) with schedule ==
    schedule[:prefix] + pattern * repeats, minimizing prefix then pattern."""
    n = len(schedule)
    best = (n, tuple(schedule), 1)          # fallback: all prefix... repeats 1
    for prefix in range(0, min(n, 4)):
        rem = schedule[prefix:]
        m = len(rem)
        if m == 0:
            continue
        for p in range(1, m + 1):
            if m % p:
                continue
            if rem == rem[:p] * (m // p):
                cand = (prefix, rem[:p], m // p)
                # prefer more repeats (smaller pattern), then smaller prefix
                if (len(cand[1]), cand[0]) < (len(best[1]), best[0]):
                    best = cand
                break
    return best


# ---------------------------------------------------------------------------
# Single block init / apply
# ---------------------------------------------------------------------------

def init_block(b: Builder, cfg: ModelConfig, kind: str, cross: bool = False):
    b.ones("ln1", (cfg.d_model,), ("embed",))
    if kind in (ATTN_DENSE, ATTN_MOE) and cfg.mla is not None:
        mla_mod.init_mla(b.sub("attn"), cfg)
    elif kind in (ATTN_DENSE, ATTN_MOE):
        attn_mod.init_attention(b.sub("attn"), cfg)
    else:
        ssm_mod.init_ssm(b.sub("ssm"), cfg)
    if cross:
        b.ones("ln_x", (cfg.d_model,), ("embed",))
        attn_mod.init_attention(b.sub("xattn"), cfg, cross=True)
    if kind in (ATTN_MOE, MAMBA_MOE):
        b.ones("ln2", (cfg.d_model,), ("embed",))
        moe_mod.init_moe(b.sub("moe"), cfg)
    elif cfg.d_ff > 0:
        b.ones("ln2", (cfg.d_model,), ("embed",))
        if cfg.mlp_gelu:
            init_gelu_mlp(b.sub("mlp"), cfg.d_model, cfg.d_ff)
        else:
            init_mlp(b.sub("mlp"), cfg.d_model, cfg.d_ff)


def block_apply(params, cfg: ModelConfig, kind: str, x, positions, aux,
                *, window: int = 0, enc_out=None, moe_strategy="grouped"):
    """Training/prefill. x: [B,S,D] -> (x, aux, stats); ``stats`` is the
    MoE layer's (``moe.moe_ffn_stats``), None for every other layer."""
    stats = None
    h = rms_norm(x, params["ln1"], cfg.norm_eps)
    if kind in (ATTN_DENSE, ATTN_MOE) and cfg.mla is not None:
        h = mla_mod.mla(params["attn"], cfg, h, positions)
    elif kind in (ATTN_DENSE, ATTN_MOE):
        h = attn_mod.attention(params["attn"], cfg, h, positions,
                               window=window)
    else:
        h = ssm_mod.ssm_block(params["ssm"], cfg, h)
    x = x + h
    x = constrain(x, "batch", "act_seq", "embed")
    if enc_out is not None:
        h = rms_norm(x, params["ln_x"], cfg.norm_eps)
        x = x + attn_mod.cross_attention(params["xattn"], cfg, h, enc_out)
    if kind in (ATTN_MOE, MAMBA_MOE):
        h = rms_norm(x, params["ln2"], cfg.norm_eps)
        h, moe_aux, stats = moe_mod.moe_ffn_stats(params["moe"], cfg, h,
                                                  strategy=moe_strategy)
        aux = aux + moe_aux
        x = x + h
    elif cfg.d_ff > 0:
        h = rms_norm(x, params["ln2"], cfg.norm_eps)
        ffn = gelu_mlp if cfg.mlp_gelu else mlp
        x = x + ffn(params["mlp"], h)
    x = constrain(x, "batch", "act_seq", "embed")
    return x, aux, stats


def init_block_cache(cfg: ModelConfig, kind: str, batch: int, seq_len: int,
                     window: int = 0):
    if kind in (ATTN_DENSE, ATTN_MOE):
        return attn_mod.init_kv_cache(cfg, batch, seq_len, window)
    return ssm_mod.init_ssm_cache(cfg, batch)


def block_cache_axes(kind: str):
    if kind in (ATTN_DENSE, ATTN_MOE):
        return attn_mod.kv_cache_axes()
    return ssm_mod.ssm_cache_axes()


def block_decode(params, cfg: ModelConfig, kind: str, x, cache, pos,
                 *, window: int = 0, enc_out=None, moe_strategy="dense"):
    """One-token decode. x: [B,1,D] -> (x, new_cache)."""
    h = rms_norm(x, params["ln1"], cfg.norm_eps)
    if kind in (ATTN_DENSE, ATTN_MOE):
        h, cache = attn_mod.decode_attention(params["attn"], cfg, h, cache,
                                             pos, window=window)
    else:
        h, cache = ssm_mod.ssm_decode_step(params["ssm"], cfg, h, cache)
    x = x + h
    if enc_out is not None:
        h = rms_norm(x, params["ln_x"], cfg.norm_eps)
        x = x + attn_mod.cross_attention(params["xattn"], cfg, h, enc_out)
    if kind in (ATTN_MOE, MAMBA_MOE):
        h = rms_norm(x, params["ln2"], cfg.norm_eps)
        h, _ = moe_mod.moe_ffn(params["moe"], cfg, h, strategy=moe_strategy)
        x = x + h
    elif cfg.d_ff > 0:
        h = rms_norm(x, params["ln2"], cfg.norm_eps)
        ffn = gelu_mlp if cfg.mlp_gelu else mlp
        x = x + ffn(params["mlp"], h)
    return x, cache


# ---------------------------------------------------------------------------
# Stack init: prefix blocks + per-position stacked pattern params
# ---------------------------------------------------------------------------

def init_stack(b: Builder, cfg: ModelConfig, cross: bool = False):
    schedule = cfg.block_schedule()
    prefix_len, pattern, repeats = factor_schedule(schedule)
    pb = b.sub("prefix")
    for i in range(prefix_len):
        init_block(pb.sub(str(i)), cfg, schedule[i], cross=cross)
    if cfg.scan_layers and repeats > 1:
        # init one params tree per repeat, then stack leaves: leading axis
        # becomes the scan axis.
        sb = b.sub("scan")
        for pos, kind in enumerate(pattern):
            reps = []
            ax = None
            for r in range(repeats):
                tmp = Builder(jax.random.fold_in(sb._next(), r), b.dtype,
                              b.abstract)
                init_block(tmp, cfg, kind, cross=cross)
                reps.append(tmp.params)
                ax = tmp.axes
            def _stack(*xs):
                if isinstance(xs[0], jax.ShapeDtypeStruct):
                    return jax.ShapeDtypeStruct((len(xs),) + xs[0].shape,
                                                xs[0].dtype)
                return jnp.stack(xs)
            stacked = jax.tree.map(_stack, *reps)
            sb.params[str(pos)] = stacked
            sb.axes[str(pos)] = jax.tree.map(
                lambda a: ("layers",) + a, ax,
                is_leaf=lambda v: isinstance(v, tuple))
    else:
        lb = b.sub("layers")
        for i in range(prefix_len, len(schedule)):
            init_block(lb.sub(str(i)), cfg, schedule[i], cross=cross)
    return prefix_len, pattern, repeats


def stack_apply(params, cfg: ModelConfig, x, positions, *, window: int = 0,
                enc_out=None, moe_strategy="grouped"):
    """Apply the whole layer stack. Returns (x, aux_loss, stats): the MoE
    layers' stats laid out as their parameters are ({"prefix" | "layers":
    {i: stats}, "scan": {pos: stats stacked over repeats}}), empty where
    no layer has any."""
    schedule = cfg.block_schedule()
    prefix_len, pattern, repeats = factor_schedule(schedule)
    aux = jnp.zeros((), jnp.float32)
    stats = {}

    def keep(group, key, st):
        if st is not None:
            stats.setdefault(group, {})[key] = st

    for i in range(prefix_len):
        def one(p, x, aux, kind=schedule[i]):
            return block_apply(p, cfg, kind, x, positions, aux,
                               window=window, enc_out=enc_out,
                               moe_strategy=moe_strategy)
        if cfg.remat:
            # outside a loop XLA may merge the backward's recomputed
            # forward with the first one (CSE) and keep the layer's
            # activations through the whole backward: keep them apart
            one = jax.checkpoint(one)
        x, aux, st = one(params["prefix"][str(i)], x, aux)
        keep("prefix", str(i), st)
    if cfg.scan_layers and repeats > 1:
        def body(carry, layer_params):
            xc, auxc = carry
            ys = {}
            for pos, kind in enumerate(pattern):
                xc, auxc, st = block_apply(layer_params[str(pos)], cfg, kind,
                                           xc, positions, auxc,
                                           window=window, enc_out=enc_out,
                                           moe_strategy=moe_strategy)
                if st is not None:
                    ys[str(pos)] = st
            return (xc, auxc), (ys or None)
        if cfg.remat:
            body = jax.checkpoint(body, prevent_cse=False)
        (x, aux), ys = jax.lax.scan(body, (x, aux), params["scan"])
        for pos, st in (ys or {}).items():
            keep("scan", pos, st)
    else:
        for i in range(prefix_len, len(schedule)):
            x, aux, st = block_apply(params["layers"][str(i)], cfg,
                                     schedule[i], x, positions, aux,
                                     window=window, enc_out=enc_out,
                                     moe_strategy=moe_strategy)
            keep("layers", str(i), st)
    return x, aux, stats


def update_router_biases(params, cfg: ModelConfig, stats):
    """DeepSeek-V3's aux-loss-free balancing after a step: each sigmoid
    router's bias moves by ``bias_update_rate · sign(mean load − load)``
    over its experts (``stats`` as ``stack_apply`` returns them)."""
    rate = cfg.moe.bias_update_rate
    new = dict(params)
    for group, layers in stats.items():
        new[group] = dict(params[group])
        for key, st in layers.items():
            load = st["load"].astype(jnp.float32)
            blk = dict(params[group][key])
            moe_p = dict(blk["moe"])
            moe_p["router_bias"] = moe_p["router_bias"] + rate * jnp.sign(
                load.mean(-1, keepdims=True) - load)
            blk["moe"] = moe_p
            new[group][key] = blk
    return new


def init_stack_cache(cfg: ModelConfig, batch: int, seq_len: int,
                     window: int = 0):
    schedule = cfg.block_schedule()
    prefix_len, pattern, repeats = factor_schedule(schedule)
    cache = {"prefix": {str(i): init_block_cache(cfg, schedule[i], batch,
                                                 seq_len, window)
                        for i in range(prefix_len)}}
    if cfg.scan_layers and repeats > 1:
        cache["scan"] = {
            str(pos): jax.tree.map(
                lambda x: jnp.stack([x] * repeats),
                init_block_cache(cfg, kind, batch, seq_len, window))
            for pos, kind in enumerate(pattern)}
    else:
        cache["layers"] = {
            str(i): init_block_cache(cfg, schedule[i], batch, seq_len, window)
            for i in range(prefix_len, len(schedule))}
    return cache


def stack_cache_axes(cfg: ModelConfig):
    schedule = cfg.block_schedule()
    prefix_len, pattern, repeats = factor_schedule(schedule)
    axes = {"prefix": {str(i): block_cache_axes(schedule[i])
                       for i in range(prefix_len)}}
    if cfg.scan_layers and repeats > 1:
        axes["scan"] = {
            str(pos): jax.tree.map(
                lambda a: ("layers",) + a, block_cache_axes(kind),
                is_leaf=lambda v: isinstance(v, tuple))
            for pos, kind in enumerate(pattern)}
    else:
        axes["layers"] = {str(i): block_cache_axes(schedule[i])
                          for i in range(prefix_len, len(schedule))}
    return axes


def stack_decode(params, cfg: ModelConfig, x, cache, pos, *, window: int = 0,
                 enc_out=None, moe_strategy="dense"):
    schedule = cfg.block_schedule()
    prefix_len, pattern, repeats = factor_schedule(schedule)
    new_cache = {"prefix": {}}
    for i in range(prefix_len):
        x, c = block_decode(params["prefix"][str(i)], cfg, schedule[i], x,
                            cache["prefix"][str(i)], pos, window=window,
                            enc_out=enc_out, moe_strategy=moe_strategy)
        new_cache["prefix"][str(i)] = c
    if cfg.scan_layers and repeats > 1:
        def body(xc, scanned):
            layer_params, layer_cache = scanned
            new_lc = {}
            for p, kind in enumerate(pattern):
                xc, new_lc[str(p)] = block_decode(
                    layer_params[str(p)], cfg, kind, xc,
                    layer_cache[str(p)], pos, window=window, enc_out=enc_out,
                    moe_strategy=moe_strategy)
            return xc, new_lc
        x, new_cache["scan"] = jax.lax.scan(
            body, x, (params["scan"], cache["scan"]))
    else:
        new_cache["layers"] = {}
        for i in range(prefix_len, len(schedule)):
            x, c = block_decode(params["layers"][str(i)], cfg, schedule[i], x,
                                cache["layers"][str(i)], pos, window=window,
                                enc_out=enc_out, moe_strategy=moe_strategy)
            new_cache["layers"][str(i)] = c
    return x, new_cache
