"""Step builders for the production launcher and the dry-run:

* ``build_train_step``  — fwd+bwd+optimizer (single model).
* ``build_fl_train_step`` / ``build_gossip_step`` — the multi-pod DeFTA
  variant: params carry a leading ``worker`` (pod) axis; each pod trains on
  its own batch shard with NO cross-pod traffic, and the gossip step mixes
  pod params with the outdegree-corrected matrix P (the paper's Algorithm 1
  mapped onto the pod axis).
* ``build_prefill_step`` / ``build_decode_step`` — serving.
* ``input_specs`` — ShapeDtypeStruct stand-ins for every model input
  (weak-type-correct, shardable, no device allocation).
* ``abstract_state`` — params/optimizer SDS trees + their shardings.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.config import MeshConfig, ModelConfig, ShapeConfig
from repro.core.gossip import mix_pytree
from repro.models import blocks
from repro.models import model as model_mod
from repro.optim import make_optimizer
from repro.launch.sharding_rules import base_rules, sharding_tree, with_sharding


# ---------------------------------------------------------------------------
# Steps
# ---------------------------------------------------------------------------

def build_train_step(cfg: ModelConfig, optimizer, *, moe_strategy="grouped",
                     microbatches: int = 1, accum_dtype=jnp.float32,
                     with_stats: bool = False):
    """fwd+bwd+update. ``microbatches>1`` scans grad accumulation over the
    leading batch dim (fp32 accumulators by default; ``accum_dtype=bf16``
    is the §Perf memory lever for the 1T-param archs).

    Sigmoid-routed MoE layers (DeepSeek-V3) move their router biases after
    the update by the step's expert loads. ``with_stats`` appends those
    MoE stats (``blocks.stack_apply``'s tree: per layer ``load`` [E] and
    ``dropped``) to the step's outputs."""
    def grads_of(params, batch):
        def lf(p):
            return model_mod.loss_fn(p, cfg, batch,
                                     moe_strategy=moe_strategy)
        (loss, metrics), grads = jax.value_and_grad(lf, has_aux=True)(params)
        return loss, grads, metrics.get("moe", {})

    def train_step(params, opt_state, step, batch):
        if microbatches == 1:
            loss, grads, stats = grads_of(params, batch)
        else:
            mb_batch = jax.tree.map(
                lambda x: x.reshape((microbatches,
                                     x.shape[0] // microbatches) +
                                    x.shape[1:]), batch)

            def mb_step(acc, one_batch):
                loss, g, st = grads_of(params, one_batch)
                acc = jax.tree.map(
                    lambda a, gg: a + gg.astype(accum_dtype), acc, g)
                return acc, (loss, st)

            acc0 = jax.tree.map(
                lambda p: jnp.zeros(p.shape, accum_dtype), params)
            grads, (losses, stats) = jax.lax.scan(mb_step, acc0, mb_batch)
            grads = jax.tree.map(
                lambda g, p: (g / microbatches).astype(p.dtype), grads,
                params)
            loss = losses.mean()
            stats = jax.tree.map(lambda a: a.sum(0), stats)
        params, opt_state = optimizer.update(params, grads, opt_state, step)
        if stats:
            params = blocks.update_router_biases(params, cfg, stats)
        if with_stats:
            return params, opt_state, step + 1, loss, stats
        return params, opt_state, step + 1, loss
    return train_step


def build_fl_train_step(cfg: ModelConfig, optimizer, *,
                        moe_strategy="grouped", microbatches: int = 1,
                        spmd_axis_name=None, accum_dtype=jnp.float32,
                        with_stats: bool = False):
    """vmapped-over-pods train step. params/opt_state have leading axis
    [npods, ...] sharded over the ``pod`` mesh axis; batch is
    [npods, per_pod_batch, ...]. ``spmd_axis_name='pod'`` tells vmap the
    batched dim lives on the pod mesh axis (required when the body contains
    shard_map, e.g. expert-parallel MoE). ``with_stats`` appends each
    pod's MoE stats (``build_train_step``), stacked [npods, ...]."""
    inner = build_train_step(cfg, optimizer, moe_strategy=moe_strategy,
                             microbatches=microbatches,
                             accum_dtype=accum_dtype, with_stats=with_stats)

    def fl_step(stacked_params, stacked_opt, step, batch):
        def one(p, o, b):
            p2, o2, _, *rest = inner(p, o, step, b)
            return (p2, o2, *rest)
        p2, o2, *rest = jax.vmap(
            one, spmd_axis_name=spmd_axis_name)(stacked_params, stacked_opt,
                                                batch)
        return (p2, o2, step + 1, *rest)
    return fl_step


def build_gossip_step(cfg: ModelConfig, *, wire=None, backend: str = "einsum",
                      adjacency=None, error_feedback: bool = False,
                      wire_round: str = "nearest"):
    """One DeFTA aggregation across pods: params <- P @ params, where P is
    the (sampled, outdegree-corrected) mixing matrix [npods, npods].

    ``wire``: None | "bf16" | "int8" — the gossip wire format (see
    core/gossip.py). NOTE the scope of the byte claim: the in-jit
    backends here (einsum/pallas/sparse) reproduce the wire's NUMERICS —
    the payload precision every peer receives — but XLA fuses
    encode→mix inside one program, so GSPMD's collectives still move
    fp32; the realized ~2×/~4× cross-pod byte cut comes from the
    multi-host ``mix_pytree_ppermute`` path, which explicitly permutes
    the int8 payload + scales (``launch.costing.gossip_cost`` prices the
    algorithmic wire contract either way). With ``error_feedback`` the
    step becomes ``gossip_step(stacked_params, mix, wire_err) ->
    (mixed, wire_err')`` carrying the EF21 residual buffers (zeros at step
    0); without it (default) the signature is unchanged from PR 1.

    ``wire_round="stochastic"`` (int8 wire only) appends a PRNG key to the
    step's signature — ``gossip_step(..., wire_key)`` — and makes the
    per-round quantization unbiased (core/gossip.quantize_rows_int8)."""
    stochastic = wire_round == "stochastic"
    if error_feedback:
        def gossip_step(stacked_params, mix, wire_err, wire_key=None):
            return mix_pytree(mix, stacked_params, backend=backend,
                              adjacency=adjacency, wire=wire,
                              residual=wire_err, wire_round=wire_round,
                              wire_key=wire_key if stochastic else None)
    else:
        def gossip_step(stacked_params, mix, wire_key=None):
            return mix_pytree(mix, stacked_params, backend=backend,
                              adjacency=adjacency, wire=wire,
                              wire_round=wire_round,
                              wire_key=wire_key if stochastic else None)
    return gossip_step


def build_pod_gossip_step(cfg: ModelConfig, defta_cfg, npods: int, sizes, *,
                          adjacency, transport: str = "in_jit",
                          backend: str = "einsum", mesh=None,
                          axis: str = "pod", scenario=None,
                          self_eval=None):
    """The multi-pod DeFTA gossip round as the unified engine's stage
    pipeline (``repro.core.engine.build_pod_round``): scenario_view →
    peer_sample (DTS) → transport → attack_inject → trust_update over the
    pod axis — the full feature set of the simulation engines (compiled
    scenarios, robust aggregation, the complete wire stack) on the
    production launcher.

    ``transport="ppermute"`` ships the encoded payload on the
    offset-skipping + nnz-row-selected ``collective_permute`` ring
    (requires ``mesh`` with the pod axis); ``"in_jit"`` uses the
    einsum/pallas/sparse/quant ``mix_pytree`` backends. The scenario's
    epoch axis is the GOSSIP ROUND index. ``self_eval(stacked_params) ->
    [npods] losses`` enables the pod time machine (held-out self-eval
    damage check) when ``defta_cfg.time_machine`` is set; the trust
    signal follows ``defta_cfg.dts_signal`` (loss / geom / both / corr /
    all — "corr"/"all" need the pod state built with
    ``init_pod_state(..., sketch=sketch_shape(defta_cfg))``).

    Returns ``(gossip_round, pod_transport)`` where
    ``gossip_round(pstate, stacked_params, losses, start_params=None) ->
    (pstate', stacked_params')`` (see ``engine.PodState`` /
    ``engine.init_pod_state``). Pass ``start_params`` — the stacked
    params the pods departed from this gossip interval — so the
    geometry/correlation signals score TRUE local-train deltas
    (``sent − start``), matching the simulation engines; omitted, they
    fall back to the legacy round-displacement approximation."""
    del cfg                                    # model config not needed —
                                               # kept for signature parity
                                               # with build_gossip_step
    import numpy as np

    from repro.core.engine import build_pod_round, make_transport
    from repro.scenarios.robust_agg import ROBUST_RULES

    support = np.asarray(adjacency, bool)
    if scenario is not None and scenario.adj_union is not None:
        # time-varying topology: the padded-CSR / ring support must cover
        # every segment's regenerated adjacency
        support = scenario.adj_union
    tr = make_transport(
        defta_cfg, backend=backend, adjacency=support,
        mesh=mesh if transport == "ppermute" else None, axis=axis,
        robust=defta_cfg.aggregation in ROBUST_RULES)
    rnd = build_pod_round(defta_cfg, npods, sizes, transport=tr,
                          adj=np.asarray(adjacency, bool),
                          scenario=scenario, self_eval=self_eval)
    return rnd, tr


def build_prefill_step(cfg: ModelConfig, *, moe_strategy="grouped"):
    def prefill_step(params, batch):
        logits, _ = model_mod.forward(params, cfg, batch,
                                      moe_strategy=moe_strategy)
        return logits
    return prefill_step


def build_decode_step(cfg: ModelConfig, *, moe_strategy="dense"):
    def decode_step(params, tokens, cache, pos, enc_out=None):
        return model_mod.decode_step(params, cfg, tokens, cache, pos,
                                     enc_out=enc_out,
                                     moe_strategy=moe_strategy)
    return decode_step


# ---------------------------------------------------------------------------
# Abstract inputs (ShapeDtypeStructs, shardable, no allocation)
# ---------------------------------------------------------------------------

def _sds(shape, dtype, sharding=None):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def input_specs(cfg: ModelConfig, shape: ShapeConfig, mesh=None,
                rules: Optional[dict] = None, *, fl_pods: int = 0):
    """Returns a dict of SDS for the given mode. With ``mesh``+``rules``,
    shardings are attached. ``fl_pods``>0 prepends the worker axis to the
    batch (multi-pod FL training)."""
    B, S = shape.global_batch, shape.seq_len
    dt = jnp.dtype(cfg.dtype)

    def shard(axes, shp):
        if mesh is None:
            return None
        from repro.sharding import logical_rules, resolve_spec
        with logical_rules(mesh, rules):
            spec = resolve_spec(axes, shp)
        return NamedSharding(mesh, spec if spec is not None else P())

    def tok(shp, axes):
        return _sds(shp, jnp.int32, shard(axes, shp))

    specs = {}
    if shape.mode == "train":
        if fl_pods:
            bp = B // fl_pods
            specs["tokens"] = tok((fl_pods, bp, S), ("worker", "batch", None))
            specs["labels"] = tok((fl_pods, bp, S), ("worker", "batch", None))
            if cfg.family == "vlm":
                v = (fl_pods, bp, cfg.num_vision_tokens, cfg.d_model)
                specs["vision_embeds"] = _sds(
                    v, dt, shard(("worker", "batch", None, None), v))
            if cfg.is_encoder_decoder:
                f = (fl_pods, bp, cfg.encoder_seq_len, cfg.d_model)
                specs["frame_embeds"] = _sds(
                    f, dt, shard(("worker", "batch", None, None), f))
        else:
            specs["tokens"] = tok((B, S), ("batch", None))
            specs["labels"] = tok((B, S), ("batch", None))
            if cfg.family == "vlm":
                v = (B, cfg.num_vision_tokens, cfg.d_model)
                specs["vision_embeds"] = _sds(v, dt,
                                              shard(("batch", None, None), v))
            if cfg.is_encoder_decoder:
                f = (B, cfg.encoder_seq_len, cfg.d_model)
                specs["frame_embeds"] = _sds(f, dt,
                                             shard(("batch", None, None), f))
    elif shape.mode == "prefill":
        specs["tokens"] = tok((B, S), ("batch", None))
        if cfg.family == "vlm":
            v = (B, cfg.num_vision_tokens, cfg.d_model)
            specs["vision_embeds"] = _sds(v, dt,
                                          shard(("batch", None, None), v))
        if cfg.is_encoder_decoder:
            f = (B, cfg.encoder_seq_len, cfg.d_model)
            specs["frame_embeds"] = _sds(f, dt,
                                         shard(("batch", None, None), f))
    else:  # decode
        specs["tokens"] = tok((B, 1), ("batch", None))
        specs["pos"] = _sds((), jnp.int32, shard((), ()))
        cache_sds = jax.eval_shape(
            lambda: model_mod.init_cache(cfg, B, S))
        axes_tree = model_mod.cache_axes(cfg)
        if mesh is not None:
            shards = sharding_tree(mesh, rules, axes_tree, cache_sds)
            cache_sds = with_sharding(cache_sds, shards)
        specs["cache"] = cache_sds
        if cfg.is_encoder_decoder:
            e = (B, cfg.encoder_seq_len, cfg.d_model)
            specs["enc_out"] = _sds(e, dt, shard(("batch", None, None), e))
    return specs


def abstract_state(cfg: ModelConfig, optimizer_name: str, lr: float = 1e-3,
                   mesh=None, rules: Optional[dict] = None, *,
                   fl_pods: int = 0):
    """(params_sds, opt_sds, optimizer) with shardings resolved."""
    opt = make_optimizer(optimizer_name, lr)
    params_sds = model_mod.abstract_params(cfg)
    opt_sds = jax.eval_shape(opt.init, params_sds)
    axes = model_mod.param_axes(cfg)
    opt_axes = _opt_state_axes(optimizer_name, axes, params_sds)
    if rules and rules.get("zero"):
        from repro.launch.sharding_rules import zero1_axes
        opt_axes = zero1_axes(opt_axes, opt_sds, rules)
    if fl_pods:
        params_sds = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((fl_pods,) + s.shape, s.dtype),
            params_sds)
        opt_sds = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((fl_pods,) + s.shape, s.dtype),
            opt_sds)
        addw = lambda a: ("worker",) + a
        axes = jax.tree.map(addw, axes,
                            is_leaf=lambda v: isinstance(v, tuple))
        opt_axes = jax.tree.map(addw, opt_axes,
                                is_leaf=lambda v: isinstance(v, tuple))
    if mesh is not None:
        pshard = sharding_tree(mesh, rules, axes, params_sds)
        oshard = sharding_tree(mesh, rules, opt_axes, opt_sds)
        params_sds = with_sharding(params_sds, pshard)
        opt_sds = with_sharding(opt_sds, oshard)
    return params_sds, opt_sds, opt


def _opt_state_axes(name: str, axes, params_sds):
    if name == "adam":
        return {"m": axes, "v": axes}
    if name == "sgd":
        return {}
    if name == "adafactor":
        def one(a, s):
            if len(s.shape) >= 2:
                return {"vr": a[:-1], "vc": a[:-2] + a[-1:]}
            return {"v": a}
        return {"f": jax.tree.map(one, axes, params_sds,
                                  is_leaf=lambda v: isinstance(v, tuple))}
    raise ValueError(name)


def param_shardings(cfg: ModelConfig, mesh, rules, *, fl_pods: int = 0):
    params_sds = model_mod.abstract_params(cfg)
    axes = model_mod.param_axes(cfg)
    if fl_pods:
        params_sds = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((fl_pods,) + s.shape, s.dtype),
            params_sds)
        axes = jax.tree.map(lambda a: ("worker",) + a, axes,
                            is_leaf=lambda v: isinstance(v, tuple))
    return sharding_tree(mesh, rules, axes, params_sds)
