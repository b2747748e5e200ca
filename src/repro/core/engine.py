"""The round-program engine: one stage pipeline behind sync and async
DeFTA, the cross-device cohort round, the multi-pod gossip round and
FedAvg.

A decentralized-FL round is a pipeline of interchangeable stages
(Gabrielli et al. 2023; Hallaji et al. 2024). A *round program* is an
ordered tuple of named stages over a mutable round context, each run
under its own ``jax.named_scope``. The stages from ``split_keys`` to
``trust_update`` exist once, in ``block_stages``, and run on an n-row
``DeFTAState`` block::

    split_keys -> peer_sample -> transport -> damage_check -> local_train
               -> [dp_noise] -> attack_inject -> trust_update

Each execution mode is a selection around that block:

* sync DeFTA (``build_defta_round``, driven by ``core.defta``): the block
  over the [W] state, with ``scenario_view`` after ``split_keys`` and
  ``finalize`` (static topology) or ``fire_merge`` (a scenario) last.
* async DeFTA (``core.async_defta``): the sync round inside a fire-gated
  tick (``build_fire_gated_tick``): speed-sampled workers merge the new
  state, the rest freeze.
* cross-device (``build_cross_device_round``, ``core.cross_device``):
  ``participation`` gathers the round's k-member cohort out of the
  enrolled population into a k-row block, the block runs on it, and
  ``scatter_merge`` writes the firing rows back.
* multi-pod (``build_pod_round``, ``launch.train --fl``): its own key
  split, time machine, attack injection and finalize around the same
  ``scenario_view``, ``peer_sample``, ``mix`` and ``confidence_update``;
  local training happens outside, in ``build_fl_train_step``.
* FedAvg (``build_fedavg_round``, ``core.fedavg``): a star topology —
  server broadcast, local training, size-weighted mean, server optimizer;
  no peer sampling, no DTS, no time machine.

Every variant is selected at build time from what the builder knows (the
scenario, the wire, ``cfg.dts_signal``, the world) and traces no op and
no PRNG split into a round that does not need it: that is what keeps
``tests/golden_engine.json`` bit-identical. The confidence update
(``confidence_update`` over ``dts.geom_confidence_update``) is itself a
selection: the paper's loss delta (``"loss"``), the update geometry
(``"geom"``), the cross-round collusion correlation over the sign-sketch
ring buffer the state carries (``"corr"``), or their fusions (``"both"``,
``"all"``). docs/ARCHITECTURE.md gives every stage's reads and writes.

Transports are a pluggable stage (``make_transport``): ``in_jit`` wraps
the einsum/pallas/sparse/quant backends of ``core.gossip.mix_pytree``,
``ppermute`` the cross-pod ring and ``sharded`` the worker-sharded mix;
each carries the full wire stack (fp32/bf16/int8 payloads, EF21
residuals, secure-aggregation pads).

The drivers: ``drive_epochs`` runs rounds in chunked ``lax.scan``
supersteps with donated buffers, one XLA dispatch per eval chunk
(``run_defta``, ``run_fedavg``, ``run_cross_device``); ``drive_ticks``
runs async ticks with a device-side ``lax.while_loop`` early exit
(``run_async_defta``).
"""
from __future__ import annotations

import functools
import time
from dataclasses import dataclass
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import DeFTAConfig, TrainConfig
from repro.core import dts as dts_mod
from repro.core.gossip import (dynamic_mixing_matrix, mix_pytree,
                               mix_pytree_ppermute, mix_pytree_sharded,
                               normalize_wire, uses_error_feedback)
from repro.core.tasks import Task
from repro.scenarios.attacks import tree_select


# ---------------------------------------------------------------------------
# Shared state + local-training stage
# ---------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclass
class DeFTAState:
    params: Any                  # stacked [W, ...]
    backup: Any                  # stacked [W, ...]
    conf: jnp.ndarray            # [W, W]
    best_loss: jnp.ndarray       # [W]
    last_loss: jnp.ndarray       # [W]
    key: jnp.ndarray
    epoch: jnp.ndarray           # [W] per-worker epoch counters
    wire_err: Any = None         # EF21 quantization residuals (stacked
                                 # like params; None when wire is lossless
                                 # or error feedback is off)
    sketch: Any = None           # [W, R, S] sign-sketch ring buffer for
                                 # the DTS v3 correlation trust signal
                                 # (None unless dts_signal needs it — the
                                 # "loss" golden state is unchanged)


def init_state(key, task: Task, num_workers: int, *,
               wire_error: bool = False, sketch=None) -> DeFTAState:
    """``sketch``: the (R, S) ring-buffer dims from ``sketch_shape(cfg)``
    when the correlation trust channel is on (zeros-initialized — empty
    history self-calibrates to zero suspicion), else None."""
    keys = jax.random.split(key, num_workers + 1)
    params = jax.vmap(task.init)(keys[:num_workers])
    return DeFTAState(
        params=params,
        # distinct buffers: superstep drivers donate the whole state, and
        # XLA rejects donating one buffer through two arguments
        backup=jax.tree.map(jnp.copy, params),
        conf=jnp.zeros((num_workers, num_workers)),
        best_loss=jnp.full((num_workers,), jnp.inf),
        last_loss=jnp.zeros((num_workers,)),
        key=keys[-1],
        epoch=jnp.zeros((num_workers,), jnp.int32),
        wire_err=jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        if wire_error else None,
        sketch=jnp.zeros((num_workers,) + tuple(sketch), jnp.float32)
        if sketch else None,
    )


def local_train_fn(task: Task, train: TrainConfig, local_epochs: int,
                   dp_clip: float = 0.0, dp_sigma: float = 0.0):
    """Returns f(key, params, x, y, mask) -> (params, mean_loss) running
    ``local_epochs`` epochs of minibatch SGD. With ``dp_clip>0`` runs
    DP-SGD (clip the minibatch gradient, add N(0, σ·clip/bs) noise) — the
    paper's compatibility claim: DP composes with DeFTA untouched."""
    bs = train.batch_size

    def one_step(params, batch):
        x, y, m, skey = batch
        loss, g = jax.value_and_grad(task.loss)(params, x, y, m)
        if dp_clip > 0:
            gnorm = jnp.sqrt(sum(jnp.vdot(v, v).real
                                 for v in jax.tree.leaves(g)) + 1e-12)
            scale = jnp.minimum(1.0, dp_clip / gnorm)
            leaves, tdef = jax.tree.flatten(g)
            nkeys = jax.random.split(skey, len(leaves))
            g = jax.tree.unflatten(tdef, [
                v * scale + dp_sigma * dp_clip *
                jax.random.normal(k, v.shape, v.dtype) / bs
                for k, v in zip(nkeys, leaves)])
        params = jax.tree.map(lambda p, gg: p - train.learning_rate * gg,
                              params, g)
        return params, loss

    def run(key, params, x, y, mask):
        n = x.shape[0]
        steps_per_epoch = max(n // bs, 1)

        def epoch(carry, ekey):
            params = carry
            pkey, nkey = jax.random.split(ekey)
            perm = jax.random.permutation(pkey, n)
            xs = x[perm][:steps_per_epoch * bs].reshape(
                steps_per_epoch, bs, *x.shape[1:])
            ys = y[perm][:steps_per_epoch * bs].reshape(steps_per_epoch, bs)
            ms = mask[perm][:steps_per_epoch * bs].reshape(
                steps_per_epoch, bs)
            skeys = jax.random.split(nkey, steps_per_epoch)
            params, losses = jax.lax.scan(
                lambda p, b: one_step(p, b), params, (xs, ys, ms, skeys))
            return params, losses.mean()

        params, losses = jax.lax.scan(epoch, params,
                                      jax.random.split(key, local_epochs))
        return params, losses.mean()

    return run


# ---------------------------------------------------------------------------
# Transports: the pluggable mixing stage
# ---------------------------------------------------------------------------

@dataclass
class Transport:
    """How a round's mixing actually moves bytes.

    ``mix(P, stacked, residual=None, key=None, round_=None)`` follows the
    ``core.gossip.mix_pytree`` contract: returns the mixed pytree, or
    ``(mixed, new_residual)`` when an EF21 residual pytree is passed.
    ``round_`` is the round counter the secagg pads are keyed on (inert
    without ``cfg.secagg``).
    """
    kind: str                    # "in_jit" | "ppermute" | "sharded"
    wire: Optional[str]          # None | "bf16" | "int8"
    use_ef: bool
    stochastic: bool             # int8 stochastic rounding (in_jit only)
    mix: Callable


def make_transport(cfg: DeFTAConfig, *, backend: str = "einsum",
                   adjacency=None, mesh=None, axis: str = "pod",
                   robust: bool = False, shard=None) -> Transport:
    """Build the transport stage from a ``DeFTAConfig``.

    ``mesh=None`` selects the ``in_jit`` transport (the einsum / pallas /
    sparse / quant backends of ``mix_pytree``); with a mesh the transport
    is the cross-pod ``ppermute`` ring (offset-skipping + per-edge nnz row
    selection, int8/bf16 payloads, EF residuals). Stochastic int8 rounding
    is an in_jit-only option — the ppermute encode rounds to nearest.

    ``shard`` (a ``repro.sharding.WorkerShards``) selects the
    worker-axis-sharded transport: intra-shard edges run the padded-CSR
    sparse/quant kernels on the local block, cross-shard edges ride the
    block-granular ppermute ring (``mix_pytree_sharded``). Like the
    cross-pod ring it encodes row-local to nearest.

    ``cfg.secagg="pairwise"`` arms the secure-aggregation wire on EVERY
    transport kind: payloads cross the wire one-time-padded per directed
    edge in the wire format's integer ring (``core.secagg``), the
    receiver unmasks before the weighted sum — exact by construction, so
    it composes with int8/bf16 + EF21 untouched. The pad-PRG base key
    derives from ``cfg.seed`` alone (never the engine PRNG stream), and
    every mix closure takes ``round_`` so pads are fresh each round.
    ``secagg=None`` (default) passes None through — the traced program
    is bit-identical to the plaintext wire.
    """
    wire = normalize_wire(cfg.gossip_dtype)
    use_ef = uses_error_feedback(cfg)
    stochastic = wire == "int8" and cfg.gossip_wire_round == "stochastic"
    # stochastic rounding only exists on the int8 wire; on any other wire
    # the knob is inert (same downgrade the --fl launch path applies)
    wire_round = cfg.gossip_wire_round if stochastic else "nearest"
    if robust and wire is not None:
        raise ValueError(
            f"robust aggregation ({cfg.aggregation!r}) simulates lossless "
            f"model exchange — it never runs the quantized wire, so "
            f"comparing it against a lossy-wire DeFTA run would be "
            f"apples-to-oranges; set gossip_dtype='float32'")
    if cfg.secagg not in (None, "pairwise"):
        raise ValueError(f"unknown secagg scheme {cfg.secagg!r} "
                         f"(None | 'pairwise')")
    if cfg.secagg_mode not in ("edge", "masked_geom"):
        raise ValueError(f"unknown secagg_mode {cfg.secagg_mode!r} "
                         f"('edge' | 'masked_geom')")
    sec_base = None
    if cfg.secagg is not None:
        if robust:
            raise ValueError(
                f"secagg composes with the weighted gossip mix only — "
                f"robust rules ({cfg.aggregation!r}) inspect individual "
                f"plaintext models, which is exactly what the masked "
                f"wire denies them")
        from repro.core import secagg as secagg_mod
        sec_base = secagg_mod.secagg_base_key(cfg.seed)

    if shard is not None:
        if stochastic:
            raise ValueError("wire_round='stochastic' is not supported on "
                             "the sharded transport (row-local nearest "
                             "encode only)")

        def mix(P, stacked, residual=None, key=None, round_=None):
            del key
            return mix_pytree_sharded(P, stacked, shard.mesh,
                                      axis=shard.axis, adjacency=adjacency,
                                      wire=wire, residual=residual,
                                      secagg=sec_base,
                                      secagg_round=round_)
        kind = "sharded"
    elif mesh is None:
        def mix(P, stacked, residual=None, key=None, round_=None):
            return mix_pytree(P, stacked, backend=backend,
                              adjacency=adjacency, wire=wire,
                              residual=residual, wire_round=wire_round,
                              wire_key=key, secagg=sec_base,
                              secagg_round=round_)
        kind = "in_jit"
    else:
        if stochastic:
            raise ValueError("wire_round='stochastic' is not supported on "
                             "the ppermute transport (row-local nearest "
                             "encode only)")

        def mix(P, stacked, residual=None, key=None, round_=None):
            del key
            return mix_pytree_ppermute(P, stacked, mesh, axis=axis,
                                       adjacency=adjacency, wire=wire,
                                       residual=residual, secagg=sec_base,
                                       secagg_round=round_)
        kind = "ppermute"
    return Transport(kind=kind, wire=wire, use_ef=use_ef,
                     stochastic=stochastic, mix=mix)


# ---------------------------------------------------------------------------
# Round programs: stage pipelines over a round context
# ---------------------------------------------------------------------------

_DTS_CHANNELS = {"loss": (), "geom": ("geom",), "both": ("geom",),
                 "corr": ("corr",), "all": ("geom", "corr")}


def resolve_dts_signal(cfg: DeFTAConfig) -> frozenset:
    """Validate ``cfg.dts_signal`` at build time and return the frozenset
    of EXTRA trust channels traced into the round body: ``{"geom"}``
    (geometry), ``{"corr"}`` (cross-round correlation), both for
    ``"all"``. Falsy (empty) exactly when the legacy loss-only
    trust_update compiles — ``"loss"`` (the default) traces no geometry
    or sketch ops and no extra PRNG splits, which is what the
    golden-parity tests pin."""
    if cfg.dts_signal not in _DTS_CHANNELS:
        raise ValueError(f"unknown dts_signal {cfg.dts_signal!r} "
                         f"(one of: {', '.join(_DTS_CHANNELS)})")
    if not cfg.use_dts:
        return frozenset()
    return frozenset(_DTS_CHANNELS[cfg.dts_signal])


def sketch_shape(cfg: DeFTAConfig):
    """The (R, S) sketch ring-buffer dims the state needs under this
    config, or None when the correlation channel is off — pass straight
    to ``init_state(..., sketch=sketch_shape(cfg))`` (and the pod
    analog) so state sizing and round building can never disagree."""
    if "corr" in resolve_dts_signal(cfg):
        return (cfg.dts_sketch_rounds, cfg.dts_sketch_dim)
    return None


def constrain_worker_rows(tree, shard, n: int):
    """with_sharding_constraint every leaf whose leading dim is ``n``
    (the worker/enrolled count) to the worker-axis row sharding; leave
    everything else (key, scalars) unconstrained. Applied to a round's
    output state so GSPMD keeps the donated scan carry row-sharded
    instead of collapsing it onto one device between rounds. An ``n``
    not divisible by the shard count is left unconstrained (NamedSharding
    needs even shards; the shard_map transport pads internally)."""
    if shard is None or n % shard.shards:
        return tree

    def c(x):
        if hasattr(x, "ndim") and x.ndim >= 1 and x.shape[0] == n:
            return jax.lax.with_sharding_constraint(
                x, shard.row_sharding(x.ndim))
        return x
    return jax.tree.map(c, tree)


def run_pipeline(stages, ctx: dict) -> dict:
    """Execute the ordered (name, fn) stage tuple over the context. Each
    stage runs under a ``jax.named_scope`` so profiler traces (and XLA
    metadata) attribute every op to its pipeline stage — name-only, so
    the traced computation (and the golden parity gate) is untouched."""
    for _name, fn in stages:
        with jax.named_scope(_name):
            fn(ctx)
    return ctx


def stage_names(round_fn) -> Tuple[str, ...]:
    """The pipeline a built round runs (for docs/tests/introspection)."""
    return tuple(n for n, _ in getattr(round_fn, "stages", ()))


def round_program(stages, *, rows: int, telemetry=None, shard=None,
                  **context):
    """The scannable round(state, data, epoch=None) -> next state body
    over ``stages``: the context starts from the state, data, epoch and
    ``context``; the last stage writes ``next``, whose [rows, ...] leaves
    are constrained to ``shard``'s worker rows. With ``telemetry`` the
    round also returns its probe frame — the probes declared so far (a
    wrapper such as the async tick declares its own later)."""
    specs = telemetry.specs if telemetry is not None else None

    def round(state, data, epoch=None):
        c = {"state": state, "data": data, "epoch": epoch, **context}
        run_pipeline(stages, c)
        nxt = constrain_worker_rows(c["next"], shard, rows)
        if telemetry is None:
            return nxt
        return nxt, telemetry.collect(c, specs)

    round.stages = stages
    round.telemetry = telemetry
    return round


def split_round_keys(key, stochastic: bool, dp_update: bool) -> dict:
    """The frozen per-round PRNG split layout, in one place: key,
    k_sample, k_train, k_noise — plus k_wire on the stochastic int8 wire
    and k_dp on the update-DP stage, both APPENDED and build-time gated
    (jax.random.split(key, n) redraws everything when n changes, so an
    ungated extra split would shift every downstream draw and break the
    golden parity the tests pin). Absent keys come back None."""
    names = ["key", "k_sample", "k_train", "k_noise"]
    if stochastic:
        names.append("k_wire")
    if dp_update:
        names.append("k_dp")
    out = dict(zip(names, jax.random.split(key, len(names))))
    out.setdefault("k_wire", None)
    out.setdefault("k_dp", None)
    return out


def uses_update_dp(cfg: DeFTAConfig) -> bool:
    """The per-round update-DP stage compiles iff ``dp_sigma > 0`` with
    ``dp_clip == 0`` (with dp_clip > 0 the sigma belongs to in-training
    DP-SGD — ``local_train_fn`` — and the stage must not double-noise)."""
    return cfg.dp_sigma > 0 and cfg.dp_clip == 0


def apply_update_dp(cfg: DeFTAConfig, key, start, trained):
    """Clip the local-update delta ``trained − start`` to
    ``cfg.dp_update_clip`` per worker (L2, whole-model) and add one
    N(0, (dp_sigma·clip)²) draw — per-round update-level DP on what
    actually crosses the wire. Returns the noised ``trained``."""
    delta = jax.tree.map(jnp.subtract, trained, start)
    flat = dts_mod.flatten_stacked(delta)
    nrm = jnp.linalg.norm(flat, axis=1)
    clip = jnp.float32(cfg.dp_update_clip)
    scale = jnp.minimum(1.0, clip / jnp.maximum(nrm, 1e-12))
    sigma = jnp.float32(cfg.dp_sigma) * clip
    leaves, tdef = jax.tree.flatten(delta)
    keys = jax.random.split(key, len(leaves))
    noised = [
        v * scale.reshape((-1,) + (1,) * (v.ndim - 1))
        + sigma * jax.random.normal(kk, v.shape, v.dtype)
        for kk, v in zip(keys, leaves)]
    return jax.tree.map(jnp.add, start, jax.tree.unflatten(tdef, noised))


def scenario_view(c: dict, scenario, adj, t):
    """The round's effective topology at scenario round ``t``: writes
    eff_adj = (per-segment or static) ``adj`` ∧ link_ok ∧ alive on both
    endpoints, and with a scenario alive, fire, att_on, att_kind and
    att_scale — one device-side lookup into the compiled arrays."""
    if scenario is None:
        c["eff_adj"] = adj
        return
    from repro.scenarios.compile import epoch_view
    view = epoch_view(scenario, t)
    c["alive"], c["fire"], c["att_on"] = \
        view["alive"], view["fire"], view["attack_on"]
    c["att_kind"], c["att_scale"] = scenario.attack_kind, scenario.attack_scale
    base = view["adj"] if scenario.adj_seg is not None else adj
    c["eff_adj"] = base & view["link_ok"] \
        & c["alive"][None, :] & c["alive"][:, None]


def peer_sample(cfg: DeFTAConfig, c: dict, conf, *,
                listen_all: bool = False):
    """The DTS peer sample every round runs: reads eff_adj, k_sample;
    writes theta [n, n] (DTS sampling weights from ``conf``, observed by
    theta-aware attacks and reused as the geometric reference weights)
    and sampled [n, n] (Gumbel top-k, ≤ num_sampled per row). Without DTS
    theta is the uniform row-normalized adjacency; ``listen_all`` (the pod
    round) then listens to every live peer instead of sampling."""
    if cfg.use_dts:
        theta = dts_mod.sample_weights(conf, c["eff_adj"], cfg.crelu_slope)
    else:
        theta = c["eff_adj"] / jnp.maximum(
            c["eff_adj"].sum(1, keepdims=True), 1)
        if listen_all:
            c["theta"], c["sampled"] = theta, c["eff_adj"]
            return
    c["theta"] = theta
    skeys = jax.random.split(c["k_sample"], theta.shape[0])
    c["sampled"] = jax.vmap(
        lambda k, t: dts_mod.sample_peers(k, t, cfg.num_sampled)
    )(skeys, theta)


def mix(cfg: DeFTAConfig, transport: Transport, c: dict, params, wire_err,
        round_, *, robust: bool = False, col_w=None, k_min: int = 1):
    """The mixing stage every round runs: reads sampled, eff_adj, sizes
    (on a dynamic matrix), k_wire; writes P (the mixing matrix), agg (the
    mixed ``params``) and wire_err (the advanced EF21 residuals). A robust
    rule (trimmed_mean/median/krum) replaces the weighted mix, and P
    degrades to the uniform bookkeeping weights the confidence update
    needs. Otherwise P is the static matrix of the build-time column
    weights ``col_w``, or the round's ``dynamic_mixing_matrix`` over the
    live adjacency; with ``k_min > 1`` rows with fewer surviving sampled
    peers degrade to the identity (the worker self-trains). ``round_``
    keys the secagg pads."""
    n = c["sampled"].shape[0]
    mask = (c["sampled"] & c["eff_adj"]) | jnp.eye(n, dtype=bool)
    if robust:
        from repro.scenarios.robust_agg import robust_mix
        c["agg"] = robust_mix(cfg.aggregation, mask, params,
                              trim=cfg.robust_trim)
        c["P"] = mask / mask.sum(axis=1, keepdims=True)
        c["wire_err"] = wire_err
        return
    if col_w is not None:
        P = mask * col_w[None, :]
        P = P / P.sum(axis=1, keepdims=True)
    else:
        # per-round outdegree renormalization under the live adjacency
        # (churn, link failures and dropouts change |D_j|/d_j)
        P = dynamic_mixing_matrix(c["sampled"], c["eff_adj"], c["sizes"],
                                  cfg.aggregation)
        if k_min > 1:
            eye = jnp.eye(n, dtype=bool)
            npeers = (c["sampled"] & c["eff_adj"] & ~eye).sum(axis=1)
            P = jnp.where((npeers >= k_min)[:, None], P, eye.astype(P.dtype))
    c["P"] = P
    if transport.use_ef:
        if wire_err is None:
            raise ValueError(
                "cfg enables gossip error feedback on a lossy wire but the "
                "state carries no residual buffers — build it with "
                "wire_error=True")
        c["agg"], c["wire_err"] = transport.mix(
            P, params, residual=wire_err, key=c["k_wire"], round_=round_)
    else:
        c["agg"] = transport.mix(P, params, key=c["k_wire"], round_=round_)
        c["wire_err"] = wire_err


def confidence_update(cfg: DeFTAConfig, c: dict, conf, sketch, loss_trust,
                      damaged, deltas, *, masked_geom: bool = False,
                      stamp=None):
    """The confidence update every round runs, ``c ← c − m ∘ p · signal``
    (Algorithm 3 line 12): reads sampled, P, theta, eff_adj (+ fire, and
    epoch with ``stamp``); writes conf and sketch (+ stamp).

    The signal is the loss delta (dts_signal="loss", bit-exact), or with
    the extra channels of ``resolve_dts_signal`` the centered
    update-geometry scores, the cross-round collusion-correlation scores,
    or their fusions — both observe ``deltas``, each peer's local-update
    delta, over the live neighborhood (non-firing peers excluded: their
    delta is never consumed). The sketch ring buffer rotates with this
    round's sign-sketch. ``stamp`` (the cross-device round's [n, R]
    global-round stamps) rotates with it, and the correlation is then the
    stamp-aligned ``dts.stamped_correlation`` instead of the dense
    ``dts.colluder_scores``. ``masked_geom`` (aggregate-only secagg): the
    receiver never sees a per-peer delta, so the channels degrade to the
    pooled aggregate-minus-own-contribution signal broadcast over its
    sampled row, and the sketch (and stamps) pass through unrotated."""
    channels = resolve_dts_signal(cfg)
    c["sketch"] = sketch
    if stamp is not None:
        c["stamp"] = stamp
    signal, gmask, corr = "loss", None, None
    if channels:
        gmask = c["eff_adj"] & c["fire"][None, :] if "fire" in c \
            else c["eff_adj"]
    if channels and masked_geom:
        loss_trust = loss_trust + cfg.dts_geom_weight \
            * dts_mod.masked_geom_trust(deltas, c["P"], gmask)
    elif channels:
        signal = cfg.dts_signal
        if "corr" in channels:
            if sketch is None:
                raise ValueError(
                    f"dts_signal={cfg.dts_signal!r} needs the sketch ring "
                    f"buffer — build the state with "
                    f"sketch=sketch_shape(cfg)")
            c["sketch"] = dts_mod.update_sketch(sketch, deltas,
                                                seed=cfg.seed)
            if stamp is not None:
                c["stamp"] = jnp.concatenate(
                    [stamp[:, 1:], jnp.full((stamp.shape[0], 1), c["epoch"],
                                            jnp.int32)], axis=1)
                cmat, valid = dts_mod.stamped_correlation(
                    c["sketch"], c["stamp"], min_obs=cfg.dts_min_obs)
                corr = dts_mod.correlation_suspicion(
                    cmat, gmask, weights=c["theta"], valid=valid)
    c["conf"] = dts_mod.geom_confidence_update(
        signal, cfg.dts_geom_weight, conf, c["sampled"], c["P"], loss_trust,
        damaged, deltas, gmask, c["theta"], sketch=c["sketch"],
        lam_corr=cfg.dts_corr_weight, corr=corr)


def block_stages(task: Task, cfg: DeFTAConfig, train: TrainConfig, n: int,
                 transport: Transport, *, telemetry=None,
                 robust: bool = False, col_w=None, k_min: int = 1,
                 kinds_present=(), num_classes: int = 0, malicious=None,
                 noise_scale: float = 200.0):
    """The stages from ``split_keys`` to ``trust_update`` over an n-row
    block: the dense round runs them on its [W] state, the cross-device
    round on the [k] cohort its ``participation`` stage gathers. They read
    ``state`` (a ``DeFTAState``), ``data``, ``sizes``, ``epoch``,
    ``eff_adj``, and with attacks ``att_kind``/``att_scale``/``att_on``
    (+ ``fire`` where rows may not fire, ``g_stamp`` on the cross-device
    correlation channel) from the round context.

    ``malicious`` (an [n] mask) selects the paper's aggregate + noise
    attack of a round without a scenario; otherwise ``kinds_present``
    compiles the scenario's attack kinds. A stage emits only the probes
    its round's telemetry declared. Returns the ordered (name, fn)
    tuple."""
    from repro.scenarios import attacks as attacks_mod
    from repro.scenarios.compile import ATTACK_CODE

    ltrain = local_train_fn(task, train, cfg.local_epochs,
                            dp_clip=cfg.dp_clip, dp_sigma=cfg.dp_sigma)
    channels = resolve_dts_signal(cfg)
    dp_update = uses_update_dp(cfg)
    # masked_geom: the receiver of an aggregate-only secagg sees no
    # per-peer update (see ``confidence_update``)
    masked_geom = cfg.secagg is not None \
        and cfg.secagg_mode == "masked_geom"
    if "label_flip" in kinds_present and num_classes <= 0:
        raise ValueError("label_flip attacks need num_classes > 0")

    def on(name):
        return telemetry is not None and name in telemetry

    def stage_split_keys(c):
        """reads state.key; writes key (next round), k_sample, k_train,
        k_noise and — build-time gated — k_wire (stochastic int8 wire)
        and k_dp (update-DP stage). The split layout is frozen
        (``split_round_keys``): adding a split changes every downstream
        draw."""
        c.update(split_round_keys(c["state"].key, transport.stochastic,
                                  dp_update))

    def stage_peer_sample(c):
        """reads eff_adj, state.conf, k_sample; writes theta and sampled
        (``peer_sample``)."""
        peer_sample(cfg, c, c["state"].conf)
        if on("theta_in"):
            telemetry.emit(c, "theta_in", c["theta"].mean(axis=0))

    def stage_transport(c):
        """reads sampled, eff_adj, sizes, state.params, state.wire_err,
        k_wire, epoch; writes P, agg and wire_err (``mix``). This is the
        pluggable stage: in_jit mix_pytree backends, the worker-sharded
        ring, or a robust rule replacing the weighted mix."""
        state = c["state"]
        if telemetry is not None:
            from repro.telemetry.spec import stacked_payload_bytes
            live = (c["sampled"] & c["eff_adj"]
                    & ~jnp.eye(n, dtype=bool)).sum()
            telemetry.emit(c, "edges", live)
            telemetry.emit(c, "wire_bytes", live.astype(jnp.float32) *
                           stacked_payload_bytes(state.params,
                                                 transport.wire))
        mix(cfg, transport, c, state.params, state.wire_err,
            0 if c["epoch"] is None else c["epoch"], robust=robust,
            col_w=col_w, k_min=k_min)
        if transport.use_ef and telemetry is not None:
            telemetry.emit(c, "ef_norm", jnp.linalg.norm(
                dts_mod.flatten_stacked(c["wire_err"]), axis=1))

    def stage_damage_check(c):
        """reads agg, data, att_kind, att_on, state.{backup,best_loss};
        writes y_data (label-flip poisoned labels where active), loss_agg
        (each row's self-evaluation of the aggregate), damaged and start
        (the params local training departs from — the backup on damaged
        rounds: the §3.3 time machine)."""
        state, data = c["state"], c["data"]
        y_data = data["y"]
        if "label_flip" in kinds_present:
            # data poisoning: label-flippers train (and self-evaluate) on
            # y -> C-1-y; their protocol behaviour stays honest
            lf = (c["att_kind"] == ATTACK_CODE["label_flip"]) & c["att_on"]
            y_data = attacks_mod.flip_labels(y_data, lf, num_classes)
        c["y_data"] = y_data
        c["loss_agg"] = jax.vmap(task.loss)(c["agg"], data["x"], y_data,
                                            data["mask"])
        if cfg.time_machine:
            c["damaged"] = dts_mod.is_damaged(c["loss_agg"],
                                              state.best_loss)
            c["start"] = tree_select(c["damaged"], state.backup, c["agg"])
        else:
            c["damaged"] = jnp.zeros_like(c["loss_agg"], bool)
            c["start"] = c["agg"]
        if on("loss_agg"):
            telemetry.emit(c, "loss_agg", c["loss_agg"])
        if on("damaged"):
            telemetry.emit(c, "damaged", c["damaged"])

    def stage_local_train(c):
        """reads start, y_data, data, k_train; writes trained (post-SGD
        stacked params) and train_loss — ``local_epochs`` minibatch epochs
        per row, vmapped over the block."""
        data = c["data"]
        tkeys = jax.random.split(c["k_train"], n)
        c["trained"], c["train_loss"] = jax.vmap(
            lambda k, p, x, y, m: ltrain(k, p, x, y, m)
        )(tkeys, c["start"], data["x"], c["y_data"], data["mask"])
        if on("train_loss"):
            telemetry.emit(c, "train_loss", c["train_loss"])

    def stage_dp_noise(c):
        """reads trained, start, k_dp; writes trained — per-round
        update-DP (``apply_update_dp``): every row clips its local-update
        delta and noises it BEFORE it becomes next round's send, so both
        peers and the trust channels only ever observe the privatized
        update. Build-time gated on ``uses_update_dp(cfg)`` (the default
        σ=0 compiles this stage away entirely)."""
        c["trained"] = apply_update_dp(cfg, c["k_dp"], c["start"],
                                       c["trained"])

    def stage_attack_inject(c):
        """reads trained, agg, att_kind, att_scale, att_on, theta,
        k_noise; writes trained (attacker rows replaced by their poisoned
        sends — what peers consume NEXT round). theta feeds the adaptive
        theta_aware gate."""
        if malicious is None:
            c["trained"] = attacks_mod.poison_sends(
                c["k_noise"], kinds_present, c["att_kind"],
                c["att_scale"], c["att_on"], c["agg"], c["trained"],
                theta=c["theta"] if cfg.use_dts else None)
        else:
            # no scenario: the paper's aggregate+noise on ``malicious``
            poisoned = attacks_mod.noise(
                c["k_noise"], c["agg"], c["trained"],
                jnp.full((n,), noise_scale, jnp.float32))
            c["trained"] = tree_select(malicious, poisoned, c["trained"])

    def stage_trust_update(c):
        """reads loss_agg, damaged, trained, start, state.{conf, sketch,
        best_loss, last_loss, backup} + what ``confidence_update`` reads;
        writes conf, sketch (+ stamp), backup, best_loss, last_loss. The
        scored delta is each row's LOCAL-UPDATE ``trained − start`` (the
        step it applied on top of its adopted aggregate; post attack
        injection, so the poison is exactly what gets scored)."""
        state = c["state"]
        loss_trust = jnp.where(c["damaged"], dts_mod.DAMAGE_PENALTY,
                               c["loss_agg"] - state.last_loss)
        deltas = None
        if channels:
            deltas = dts_mod.flatten_stacked(c["trained"]) \
                - dts_mod.flatten_stacked(c["start"])
        confidence_update(cfg, c, state.conf, state.sketch, loss_trust,
                          c["damaged"], deltas, masked_geom=masked_geom,
                          stamp=c.get("g_stamp"))
        if telemetry is not None:
            telemetry.emit(c, "loss_trust", loss_trust)
            telemetry.emit(c, "conf_in", c["conf"].mean(axis=0))
            # the scored observable: ‖trained − start‖ per row (on the
            # channels path XLA CSEs this with the deltas above)
            telemetry.emit(c, "update_norm", jnp.linalg.norm(
                dts_mod.flatten_stacked(c["trained"])
                - dts_mod.flatten_stacked(c["start"]), axis=1))

        improved = (c["loss_agg"] < state.best_loss) & ~c["damaged"]
        # the time machine's compensation step RATCHETS: a damaged round
        # starts from the backup, so its trained result is train(backup) —
        # clean by induction — and becomes the new backup. Without this a
        # worker whose whole peer set is malicious (66%-regime reality)
        # re-trains the same frozen backup forever and never progresses.
        c["backup"] = tree_select(improved | c["damaged"], c["trained"],
                                  state.backup)
        c["best_loss"] = jnp.where(improved, c["loss_agg"],
                                   state.best_loss)
        c["last_loss"] = jnp.where(c["damaged"], state.last_loss,
                                   c["loss_agg"])

    return (
        ("split_keys", stage_split_keys),
        ("peer_sample", stage_peer_sample),
        ("transport", stage_transport),
        ("damage_check", stage_damage_check),
        ("local_train", stage_local_train),
    ) + ((("dp_noise", stage_dp_noise),) if dp_update else ()) + (
        ("attack_inject", stage_attack_inject),
        ("trust_update", stage_trust_update),
    )


def build_defta_round(task: Task, cfg: DeFTAConfig, train: TrainConfig,
                      adj: np.ndarray, sizes: np.ndarray,
                      malicious: np.ndarray, *,
                      gossip_backend: str = "einsum",
                      noise_scale: float = 200.0,
                      scenario=None, num_classes: int = 0,
                      transport: Optional[Transport] = None,
                      telemetry=None, shard=None):
    """The DeFTA round program: returns an UN-jitted
    round(state, data, epoch=None) -> state body — scannable, so drivers
    fuse many rounds into one XLA dispatch (and jittable as-is for
    single-round use). Its stages are ``block_stages`` over the [W]
    state, with ``scenario_view`` after ``split_keys`` and ``finalize`` (or
    ``fire_merge`` with a scenario) at the end.

    ``scenario``: a ``repro.scenarios.CompiledScenario``. When given, the
    traced ``epoch`` index looks up that epoch's alive/link/fire/attack
    state (and, for time-varying topologies, the segment's regenerated
    adjacency) from the compiled device arrays — churn, partitions,
    stragglers and the whole attack zoo run INSIDE the scan body, no host
    round-trips. Without it the topology is static and the ``malicious``
    workers send the paper's aggregate + noise.

    ``transport``: a ``Transport`` (default: ``make_transport`` over the
    in_jit ``gossip_backend``). ``num_classes`` is required when the
    scenario contains a ``label_flip`` attack (the flip is ``y -> C-1-y``).

    ``telemetry``: a ``repro.telemetry.Telemetry`` registry. When given,
    the stages emit the ``defta_specs`` probes (read-only observations of
    values already materialized) and the round returns ``(next_state,
    frame)`` so the scan driver stacks per-round frames as ys — zero
    extra dispatches. ``telemetry=None`` (default) traces NOTHING: the
    round body is bit-identical to the golden path.

    ``shard``: a ``repro.sharding.WorkerShards``. When given, the default
    transport becomes the worker-axis-sharded local-block-CSR +
    cross-shard-ring mix, and the round constrains every [W, ...] leaf
    of its output state to the worker row sharding so GSPMD keeps the
    whole scanned carry distributed. The per-worker stages (train,
    damage check, trust) are embarrassingly parallel over W and
    partition from those constraints; the handful of cross-worker
    reductions (outdegrees, geometry scores, telemetry means) lower to
    collectives automatically. ``shard=None`` (default) changes nothing.
    """
    from repro.scenarios.robust_agg import ROBUST_RULES

    w = adj.shape[0]
    adj_j = jnp.asarray(adj)
    sizes_j = jnp.asarray(np.asarray(sizes, np.float32))
    max_staleness = int(cfg.max_staleness)
    robust = cfg.aggregation in ROBUST_RULES
    col_w = None
    if scenario is None and not robust:
        # static topology: the column weights are fixed at build time
        outdeg = jnp.asarray((adj | np.eye(w, dtype=bool)).sum(axis=0)
                             .astype(np.float32))
        if cfg.aggregation == "defta":
            col_w = sizes_j / outdeg
        elif cfg.aggregation == "defl":
            col_w = sizes_j
        else:  # uniform gossip
            col_w = jnp.ones_like(sizes_j)

    if scenario is not None and scenario.num_workers != w:
        raise ValueError(f"scenario compiled for W={scenario.num_workers}, "
                         f"topology has {w}")

    if transport is None:
        # time-varying topologies: the sparse/padded-CSR support must cover
        # every segment's regenerated adjacency (support union), so the
        # ``sparse_support`` memo stays a single static entry
        support = adj
        if scenario is not None and scenario.adj_union is not None:
            support = scenario.adj_union
        transport = make_transport(cfg, backend=gossip_backend,
                                   adjacency=support, robust=robust,
                                   shard=shard)
    use_ef = transport.use_ef
    corr = "corr" in resolve_dts_signal(cfg)

    if telemetry is not None:
        from repro.telemetry.spec import defta_specs
        telemetry.declare(*defta_specs(w, scenario=scenario is not None,
                                       use_ef=use_ef))

    block = block_stages(
        task, cfg, train, w, transport, telemetry=telemetry, robust=robust,
        col_w=col_w,
        kinds_present=scenario.kinds_present if scenario is not None else (),
        num_classes=num_classes,
        malicious=jnp.asarray(malicious) if scenario is None else None,
        noise_scale=noise_scale)

    def stage_scenario_view(c):
        """reads epoch; writes eff_adj (and alive/fire/att_on/att_kind/
        att_scale with a scenario): the round's effective topology
        (``scenario_view``). With ``cfg.max_staleness > 0`` (build-time gated: the
        default 0 traces no extra ops) edges from peers whose epoch
        counter lags the receiver's by more than S rounds are additionally
        dropped — a straggler's S-rounds-old model is excluded from the
        merge instead of silently mixed (async ticks and straggler
        scenarios open exactly these gaps)."""
        scenario_view(c, scenario, adj_j, c["epoch"])
        if max_staleness:
            ep = c["state"].epoch
            fresh = (ep[:, None] - ep[None, :]) <= max_staleness
            c["eff_adj"] = c["eff_adj"] & fresh
        if telemetry is not None:
            telemetry.emit(c, "round", jnp.int32(-1)
                           if c["epoch"] is None else c["epoch"])
            if scenario is not None:
                telemetry.emit(c, "alive", c["alive"])
                telemetry.emit(c, "fire", c["fire"])

    def stage_finalize(c):
        """reads trained, backup, conf, best_loss, last_loss, key,
        wire_err, sketch; writes next (the static-topology DeFTAState:
        every worker advanced one epoch)."""
        state = c["state"]
        c["next"] = DeFTAState(
            params=c["trained"], backup=c["backup"], conf=c["conf"],
            best_loss=c["best_loss"], last_loss=c["last_loss"],
            key=c["key"], epoch=state.epoch + 1, wire_err=c["wire_err"],
            sketch=c["sketch"])

    def stage_fire_merge(c):
        """reads fire + everything finalize reads; writes next. The
        churn/straggler merge: non-firing workers freeze (dead workers
        are absent from eff_adj so nobody consumed them; stragglers
        expose their stale params and skip their own round — including
        their sketch-history row, which must not rotate on a round whose
        delta peers never consumed)."""
        state, fire = c["state"], c["fire"]
        params = tree_select(fire, c["trained"], state.params)
        backup = tree_select(fire, c["backup"], state.backup)
        wire_err = tree_select(fire, c["wire_err"], state.wire_err) \
            if use_ef else state.wire_err
        sketch = jnp.where(fire[:, None, None], c["sketch"],
                           state.sketch) if corr else state.sketch
        c["next"] = DeFTAState(
            params=params, backup=backup,
            conf=jnp.where(fire[:, None], c["conf"], state.conf),
            best_loss=jnp.where(fire, c["best_loss"], state.best_loss),
            last_loss=jnp.where(fire, c["last_loss"], state.last_loss),
            key=c["key"], epoch=state.epoch + fire.astype(jnp.int32),
            wire_err=wire_err, sketch=sketch)

    stages = block[:1] + (("scenario_view", stage_scenario_view),) \
        + block[1:] + (("finalize", stage_finalize) if scenario is None
                       else ("fire_merge", stage_fire_merge),)
    return round_program(stages, rows=w, telemetry=telemetry, shard=shard,
                         sizes=sizes_j)


def build_fedavg_round(task: Task, cfg: DeFTAConfig, train: TrainConfig,
                       sizes: np.ndarray, malicious: np.ndarray, *,
                       sample_workers: int = 0, server_opt: str = "none",
                       server_lr: float = 1.0, noise_scale: float = 200.0,
                       telemetry=None):
    """FedAvg as a stage selection over the same pipeline: the transport is
    a STAR topology (server broadcast down, size-weighted mean up), there
    is no peer sampling / DTS / time machine, and the server optimizer is
    the finalize stage. ``sample_workers=0`` -> CFL-F; >0 -> CFL-S.

    Returns an UN-jitted round(state, data, epoch=None) body — scannable by
    ``drive_epochs`` exactly like the DeFTA round.
    """
    from repro.scenarios.attacks import noise as noise_attack

    w = len(sizes)
    sizes_j = jnp.asarray(sizes, jnp.float32)
    malicious_j = jnp.asarray(malicious)
    ltrain = local_train_fn(task, train, cfg.local_epochs)

    if telemetry is not None:
        from repro.telemetry.spec import fedavg_specs
        telemetry.declare(*fedavg_specs(w))

    def stage_split_keys(c):
        """reads state.key; writes key, k_sel, k_train, k_noise."""
        c["key"], c["k_sel"], c["k_train"], c["k_noise"] = \
            jax.random.split(c["state"].key, 4)

    def stage_star_broadcast(c):
        """reads state.server; writes bcast — the star topology going
        down: every worker starts from the server model."""
        c["bcast"] = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (w,) + x.shape),
            c["state"].server)
        if telemetry is not None:
            telemetry.emit(c, "round", jnp.int32(-1)
                           if c["epoch"] is None else c["epoch"])

    def stage_local_train(c):
        """reads bcast, data, k_train; writes trained (per-worker losses
        feed the telemetry probe; without it they are dead outputs XLA
        eliminates — the golden trace is unchanged)."""
        data = c["data"]
        tkeys = jax.random.split(c["k_train"], w)
        c["trained"], train_loss = jax.vmap(
            lambda k, p, x, y, m: ltrain(k, p, x, y, m)
        )(tkeys, c["bcast"], data["x"], data["y"], data["mask"])
        if telemetry is not None:
            telemetry.emit(c, "train_loss", train_loss)

    def stage_attack_inject(c):
        """reads trained, bcast, k_noise; writes trained — malicious
        workers send server + noise (the paper's one attack model; the
        undefended baseline)."""
        poisoned = noise_attack(c["k_noise"], c["bcast"], c["trained"],
                                jnp.full((w,), noise_scale, jnp.float32))
        c["trained"] = tree_select(malicious_j, poisoned, c["trained"])

    def stage_star_aggregate(c):
        """reads trained, k_sel; writes new_server — the size-weighted
        mean over the (optionally sampled: CFL-S) worker cohort."""
        if sample_workers:
            sel = jax.random.choice(c["k_sel"], w, (sample_workers,),
                                    replace=False)
            wmask = jnp.zeros((w,)).at[sel].set(1.0)
        else:
            wmask = jnp.ones((w,))
        aw = wmask * sizes_j
        aw = aw / aw.sum()
        c["new_server"] = jax.tree.map(
            lambda x: jnp.einsum("i,i...->...", aw.astype(x.dtype), x),
            c["trained"])
        if telemetry is not None:
            # star wire: W broadcasts down + the (sampled) cohort up —
            # static at the fp32 payload, priced once at trace time
            from repro.telemetry.spec import tree_payload_bytes
            up = sample_workers if sample_workers else w
            telemetry.emit(c, "wire_bytes", jnp.float32(
                (w + up) * tree_payload_bytes(c["state"].server, None)))

    def stage_server_update(c):
        """reads new_server, state.{server,opt}; writes next — the server
        optimizer (plain replacement, or FedAdam on the server delta)."""
        from repro.core.fedavg import FedAvgState
        state = c["state"]
        if server_opt == "fedadam":
            b1, b2, eps = 0.9, 0.99, 1e-3
            delta = jax.tree.map(lambda n, s: n - s, c["new_server"],
                                 state.server)
            m = jax.tree.map(lambda mm, d: b1 * mm + (1 - b1) * d,
                             state.opt["m"], delta)
            v = jax.tree.map(lambda vv, d: b2 * vv + (1 - b2) * d * d,
                             state.opt["v"], delta)
            new_server = jax.tree.map(
                lambda s, mm, vv: s + server_lr * mm / (jnp.sqrt(vv) + eps),
                state.server, m, v)
            c["next"] = FedAvgState(server=new_server,
                                    opt={"m": m, "v": v}, key=c["key"])
        else:
            c["next"] = FedAvgState(server=c["new_server"], opt=state.opt,
                                    key=c["key"])

    stages = (
        ("split_keys", stage_split_keys),
        ("star_broadcast", stage_star_broadcast),
        ("local_train", stage_local_train),
        ("attack_inject", stage_attack_inject),
        ("star_aggregate", stage_star_aggregate),
        ("server_update", stage_server_update),
    )
    # FedAvg's round is epoch-invariant; the traced index only feeds the
    # telemetry round stamp (dead when telemetry is None)
    return round_program(stages, rows=w, telemetry=telemetry)


# ---------------------------------------------------------------------------
# Async: fire-gated tick wrapper
# ---------------------------------------------------------------------------

def build_fire_gated_tick(rnd_fn, jdata, speeds, w: int):
    """Wrap a round program in the AsyncDeFTA tick merge: on each tick,
    worker i completes a round with probability speeds[i]; fired workers
    take the new state, the rest freeze (heterogeneous hardware, modeled by
    its only algorithmically observable effect — which epoch's peer models
    a worker reads). Dead (chunk-padding) ticks skip ENTIRELY: no round
    compute and no key advance, so the device-exit path returns a state
    bit-identical to the host-exit reference.

    When the wrapped round carries a Telemetry registry the tick adds the
    ``fired`` probe and yields ``(state, frame)`` — dead ticks yield the
    structurally-identical zero frame (``lax.cond`` pytree parity), which
    the driver trims off host-side."""
    telemetry = getattr(rnd_fn, "telemetry", None)
    if telemetry is not None:
        from repro.telemetry.spec import tick_specs
        telemetry.declare(*tick_specs(w))

    def tick(state: DeFTAState, inp):
        tkey, live, t = inp

        def run(state):
            fired = jax.random.uniform(tkey, (w,)) < speeds
            if telemetry is None:
                nxt = rnd_fn(state, jdata, t)
            else:
                nxt, frame = rnd_fn(state, jdata, t)
            # merge: fired workers take the new state, others keep the
            # old. wire_err rides along — a worker that did not fire did
            # not send, so its EF residual must not advance either.
            # (with a scenario, nxt already froze non-firing/dead workers,
            # so taking nxt.* for fired workers composes both gates)
            params = tree_select(fired, nxt.params, state.params)
            backup = tree_select(fired, nxt.backup, state.backup)
            wire_err = tree_select(fired, nxt.wire_err, state.wire_err)
            conf = jnp.where(fired[:, None], nxt.conf, state.conf)
            sketch = jnp.where(fired[:, None, None], nxt.sketch,
                               state.sketch) \
                if state.sketch is not None else state.sketch
            merged = DeFTAState(
                params=params, backup=backup, conf=conf,
                best_loss=jnp.where(fired, nxt.best_loss, state.best_loss),
                last_loss=jnp.where(fired, nxt.last_loss, state.last_loss),
                key=nxt.key,
                epoch=jnp.where(fired, nxt.epoch, state.epoch),
                wire_err=wire_err, sketch=sketch)
            if telemetry is None:
                return merged
            return merged, dict(frame, fired=fired)

        if telemetry is None:
            return jax.lax.cond(live, run, lambda s: s, state), None
        return jax.lax.cond(live, run,
                            lambda s: (s, telemetry.zero_frame()), state)

    tick.telemetry = telemetry
    return tick


# ---------------------------------------------------------------------------
# Drivers: chunked-scan superstep + device-side while_loop early exit
# ---------------------------------------------------------------------------

def drive_epochs(rnd_fn, state, jdata, epochs: int, *, eval_every: int = 0,
                 eval_fn=None, superstep: bool = True,
                 stats: Optional[dict] = None, ledger=None,
                 shard=None, shard_rows: Optional[int] = None):
    """The chunked-scan superstep driver (shared by run_defta and
    run_fedavg): epochs advance inside ``jax.lax.scan`` chunks bounded by
    eval points, with the state buffers DONATED across chunks — a run is
    ceil(epochs / eval_every) XLA dispatches (one, if eval_every=0).
    ``superstep=False`` keeps the per-epoch dispatch loop (the reference
    the fused path is tested against). ``eval_fn(state, done_epochs)`` is
    called at eval boundaries; its results are collected into the returned
    history.

    Accounting goes through one ``repro.telemetry.RunLedger`` (pass
    ``ledger=`` to keep it — dispatches, per-superstep wall clock, and,
    when the round was built with a Telemetry registry, the per-round
    probe frames flushed at each chunk/eval boundary). ``stats={}`` is
    the deprecated dict view: it gets ``ledger.as_stats()`` — the exact
    legacy ``{"dispatches": n, "epochs": e}`` keys.

    With ``shard`` (a ``repro.sharding.WorkerShards``) the driver becomes
    the SHARDED superstep: the state and the per-worker data are placed
    row-sharded on the worker mesh axis before the first chunk
    (``shard_rows`` = the worker/enrolled count, default
    ``state.conf.shape[0]``), so every donated scan carry stays
    distributed — same dispatch count, per-device worker blocks.

    Returns ``(state, history)``.
    """
    from repro.telemetry.ledger import RunLedger
    from repro.telemetry.trace import note_program, span
    led = ledger if ledger is not None else RunLedger()
    telemetry = getattr(rnd_fn, "telemetry", None)
    history = []
    if shard is not None:
        n = shard_rows if shard_rows is not None else state.conf.shape[0]
        state = shard.shard_leading(state, n)
        jdata = shard.shard_leading(jdata, n)

    def flush(frames, start, n_rounds, wall):
        led.record_dispatch(n_rounds, wall)
        if telemetry is not None:
            from repro.telemetry.spec import gather_frames
            # host-gather: sharded probe buffers reassemble to the global
            # layout so ledger rows are identical at any shard count
            led.record_frames(gather_frames(frames), start)

    if not superstep:                       # per-epoch reference driver
        rnd = jax.jit(rnd_fn)
        for e in range(epochs):
            t0 = time.perf_counter()
            out = rnd(state, jdata, jnp.int32(e))
            if telemetry is None:
                state, frames = out, None
            else:
                state, frame = out
                frames = {kk: np.asarray(v)[None]
                          for kk, v in frame.items()}
            jax.block_until_ready(state)
            flush(frames, e, 1, time.perf_counter() - t0)
            if eval_every and (e + 1) % eval_every == 0 \
                    and eval_fn is not None:
                with span("eval", round=e + 1):
                    history.append(eval_fn(state, e + 1))
    else:
        @functools.partial(jax.jit, static_argnames=("length",),
                           donate_argnums=(0,))
        def run_chunk(st, jd, e0, *, length):
            def body(s, e):
                if telemetry is None:
                    return rnd_fn(s, jd, e), None
                return rnd_fn(s, jd, e)
            # the scan ys ARE the [chunk, ...] telemetry buffers — XLA
            # stacks frames in-place, zero extra dispatches (None if off)
            return jax.lax.scan(body, st, e0 + jnp.arange(length))

        done = 0
        # eval boundaries only matter when there is something to eval —
        # otherwise the whole run is a single dispatch
        chunk = eval_every if (eval_every and eval_fn is not None) \
            else epochs
        while done < epochs:
            n = min(chunk, epochs - done)
            with span("chunk", round=done, length=n) as sp:
                args = (state, jdata, jnp.int32(done))
                programs = run_chunk._cache_size()
                with span("dispatch"):
                    state, frames = run_chunk(*args, length=n)
                if run_chunk._cache_size() != programs:
                    # a new program: keep its specs for the scope map
                    note_program("run_chunk", run_chunk, args,
                                 {"length": n}, stage_names(rnd_fn))
                with span("wait"):
                    jax.block_until_ready(state)
            flush(frames, done, n, sp.seconds)
            done += n
            if eval_every and done % eval_every == 0 \
                    and eval_fn is not None:
                with span("eval", round=done):
                    history.append(eval_fn(state, done))

    led.finish("epochs", epochs)
    if stats is not None:
        stats.update(led.as_stats())
    return state, history


def drive_ticks(tick_fn, state, tkeys, ticks: int, *, check_every: int,
                required: np.ndarray, target_epochs: int = 0,
                host_exit: bool = False, stats: Optional[dict] = None,
                ledger=None, shard=None,
                shard_rows: Optional[int] = None):
    """The tick driver (AsyncDeFTA): ticks advance inside ``lax.scan``
    chunks with donated state buffers. The target_epochs early-exit
    predicate is evaluated DEVICE-SIDE by default: a ``lax.while_loop``
    over scan chunks of ``check_every`` ticks checks
    ``all(epoch >= target_epochs)`` on ``required`` workers between chunks,
    so the whole targeted run is ONE dispatch with zero host round-trips.
    ``host_exit=True`` keeps the reference path: host syncs at every
    ``check_every`` boundary. Untargeted runs are a single scan either way.

    Accounting goes through the same ``RunLedger`` as ``drive_epochs``
    (pass ``ledger=``); ``stats={}`` is the deprecated view and gets the
    legacy ``{"dispatches": n, "ticks": ticks}`` keys. With a
    telemetry-built tick, the device-exit path carries preallocated
    ``[padded_ticks, ...]`` probe buffers through the while-loop carry
    (chunk frames written via ``dynamic_update_slice`` — still one
    dispatch) and the ledger keeps the ticks that actually ran.

    ``tkeys``: [ticks, 2] per-tick PRNG keys. ``shard`` (a
    ``repro.sharding.WorkerShards``) places the state row-sharded on the
    worker mesh axis before the first chunk, same contract as
    ``drive_epochs``. Returns the final state.
    """
    from repro.telemetry.ledger import RunLedger
    led = ledger if ledger is not None else RunLedger()
    telemetry = getattr(tick_fn, "telemetry", None)
    ts_all = jnp.arange(ticks, dtype=jnp.int32)
    if shard is not None:
        n = shard_rows if shard_rows is not None else state.conf.shape[0]
        state = shard.shard_leading(state, n)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def run_ticks(st, tk, ts):
        live = jnp.ones((tk.shape[0],), bool)
        return jax.lax.scan(tick_fn, st, (tk, live, ts))

    def flush(frames, start, n_ticks, wall):
        led.record_dispatch(n_ticks, wall)
        if telemetry is not None:
            from repro.telemetry.spec import gather_frames
            led.record_frames(gather_frames(frames), start)

    def finish(state):
        led.finish("ticks", ticks)
        if stats is not None:
            stats.update(led.as_stats())
        return state

    if not target_epochs or not ticks:     # no predicate: one plain scan
        if ticks:
            t0 = time.perf_counter()
            state, frames = run_ticks(state, tkeys, ts_all)
            jax.block_until_ready(state)
            flush(frames, 0, ticks, time.perf_counter() - t0)
        return finish(state)

    if host_exit:                          # reference path (PR 1)
        for t0 in range(0, ticks, check_every):
            w0 = time.perf_counter()
            state, frames = run_ticks(state, tkeys[t0:t0 + check_every],
                                      ts_all[t0:t0 + check_every])
            jax.block_until_ready(state)
            flush(frames, t0, min(check_every, ticks - t0),
                  time.perf_counter() - w0)
            if bool((np.asarray(state.epoch)[required]
                     >= target_epochs).all()):
                break
        return finish(state)

    # device-side early exit: while_loop over scan chunks, zero round-trips.
    # Ticks are padded up to a whole number of chunks; padded slots carry
    # live=False so they never fire (parity with the host path, which
    # simply stops at ``ticks``).
    nchunks = -(-ticks // check_every)
    padded = nchunks * check_every
    if padded > ticks:
        tkeys = jnp.concatenate(
            [tkeys, jnp.zeros((padded - ticks,) + tkeys.shape[1:],
                              tkeys.dtype)])
    tkeys = tkeys.reshape(nchunks, check_every, *tkeys.shape[1:])
    live = (jnp.arange(padded) < ticks).reshape(nchunks, check_every)
    ts = jnp.arange(padded, dtype=jnp.int32).reshape(nchunks, check_every)
    vanilla = jnp.asarray(required)
    bufs0 = telemetry.zero_buffers(padded) if telemetry is not None else {}

    @functools.partial(jax.jit, donate_argnums=(0,))
    def run_until(st, bufs, tkeys, live, ts):
        def not_done(carry):
            st, c, _ = carry
            reached = jnp.all(jnp.where(vanilla,
                                        st.epoch >= target_epochs, True))
            return (c < nchunks) & ~reached

        def chunk(carry):
            st, c, bufs = carry
            st, frames = jax.lax.scan(tick_fn, st,
                                      (tkeys[c], live[c], ts[c]))
            if telemetry is not None:
                bufs = {kk: jax.lax.dynamic_update_slice(
                    bufs[kk], frames[kk],
                    (c * check_every,) + (0,) * (bufs[kk].ndim - 1))
                    for kk in bufs}
            return st, c + 1, bufs

        return jax.lax.while_loop(not_done, chunk,
                                  (st, jnp.zeros((), jnp.int32), bufs))

    t0 = time.perf_counter()
    state, chunks_run, bufs = run_until(state, bufs0, tkeys, live, ts)
    jax.block_until_ready(state)
    wall = time.perf_counter() - t0
    # only the chunks the while_loop actually ran carry real frames —
    # trim the early-exit tail (and the chunk padding) host-side
    valid = min(int(chunks_run) * check_every, ticks)
    led.record_dispatch(valid, wall)
    if telemetry is not None and valid:
        from repro.telemetry.spec import gather_frames
        led.record_frames(
            {kk: v[:valid] for kk, v in gather_frames(bufs).items()}, 0)
    return finish(state)


# ---------------------------------------------------------------------------
# Multi-pod round program (launch/train.py --fl)
# ---------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclass
class PodState:
    """Gossip-round state for the multi-pod path: DTS confidence, EF
    residuals and the round counter (local train state — params/opt —
    lives outside, in the launcher's train loop). ``backup``/``best_loss``
    are the pod time machine (held-out self-eval between gossip rounds,
    the analog of the simulation engines' §3.3 damage check) — None when
    the time machine is off."""
    conf: jnp.ndarray            # [npods, npods]
    last_loss: jnp.ndarray       # [npods]
    key: jnp.ndarray
    round: jnp.ndarray           # scalar int32 gossip-round counter
    wire_err: Any = None
    backup: Any = None           # stacked [npods, ...] best-eval params
    best_loss: Any = None        # [npods] best held-out self-eval loss
    sketch: Any = None           # [npods, R, S] sign-sketch ring buffer
                                 # (DTS v3 correlation trust)


def init_pod_state(key, npods: int, params=None, *,
                   wire_error: bool = False,
                   time_machine: bool = False, sketch=None) -> PodState:
    """``sketch``: the (R, S) dims from ``sketch_shape(cfg)`` when the
    correlation trust channel is on, else None."""
    if (wire_error or time_machine) and params is None:
        raise ValueError("wire_error/time_machine pod state needs the "
                         "stacked params to size its buffers")
    return PodState(
        conf=jnp.zeros((npods, npods)),
        last_loss=jnp.zeros((npods,)),
        key=key,
        round=jnp.zeros((), jnp.int32),
        wire_err=jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        if wire_error else None,
        backup=jax.tree.map(jnp.copy, params) if time_machine else None,
        best_loss=jnp.full((npods,), jnp.inf) if time_machine else None,
        sketch=jnp.zeros((npods,) + tuple(sketch), jnp.float32)
        if sketch else None,
    )


def build_pod_round(cfg: DeFTAConfig, npods: int, sizes, *,
                    transport: Transport, adj: np.ndarray,
                    scenario=None, self_eval=None):
    """The multi-pod gossip round over the pod axis: split_keys ->
    scenario_view -> peer_sample -> transport -> [damage_check] ->
    attack_inject -> trust_update -> finalize. Its peer sample, mixing and
    confidence update are the code the dense and cross-device rounds run
    (``peer_sample``, ``mix``, ``confidence_update``); its key split,
    time machine, attack injection and finalize are its own. Without DTS
    a pod listens to every live peer. Local training happens between
    gossip rounds in ``build_fl_train_step``.

    ``self_eval(stacked_params) -> [npods] losses`` is the pod TIME
    MACHINE's held-out self-evaluation: with ``cfg.time_machine`` it is
    run on the candidate aggregate between gossip rounds, damaged pods
    (``dts.is_damaged`` vs their best eval loss) restore their backup
    instead of adopting the mix, and the damage penalty feeds the trust
    update — the simulation engines' §3.3 damage check mapped onto pods.
    Without it (the default) ``damage_check`` stays the skipped stage of
    this selection.

    Returns gossip_round(pstate, params, losses, start_params=None) ->
    (pstate, new_params): ``params`` is the stacked [npods, ...] pod
    pytree, ``losses`` [npods] the pods' current train losses (the
    loss-trust signal; ``cfg.dts_signal`` adds/substitutes the
    geometric/correlation signals). ``start_params`` — the stacked params
    the pods DEPARTED from this round (last round's adopted
    ``new_params``) — makes the geometry/correlation observables the true
    local-train deltas ``sent − start``, as in the simulation engines
    (the launcher threads it); when omitted the signals fall back to the
    round displacement ``out − params``. The scenario epoch axis is the
    GOSSIP ROUND index (pstate.round).
    """
    from repro.scenarios import attacks as attacks_mod
    from repro.scenarios.compile import ATTACK_CODE
    from repro.scenarios.robust_agg import ROBUST_RULES

    w = npods
    adj_j = jnp.asarray(adj)
    sizes_j = jnp.asarray(np.asarray(sizes, np.float32))
    robust = cfg.aggregation in ROBUST_RULES
    if robust and transport.wire is not None:
        raise ValueError("robust aggregation on the pod path needs a "
                         "lossless wire (gossip_dtype='float32')")
    if scenario is not None and scenario.num_workers != w:
        raise ValueError(f"scenario compiled for W={scenario.num_workers} "
                         f"pods, mesh has {w}")
    use_ef = transport.use_ef
    channels = resolve_dts_signal(cfg)
    corr = "corr" in channels
    if cfg.secagg is not None and cfg.secagg_mode == "masked_geom":
        raise ValueError(
            "secagg_mode='masked_geom' has no pod selection: pod trust "
            "already runs at pod granularity (each pod IS an aggregate) "
            "— use the simulation/cross-device engines to measure the "
            "aggregate-only trust degradation")
    # the pod time machine needs BOTH the flag and a held-out evaluator;
    # without self_eval the selection quietly stays TM-less (sim configs
    # default time_machine=True and are reused here)
    time_machine = cfg.time_machine and self_eval is not None

    def stage_split_keys(c):
        """reads pstate.key; writes key, k_sample, k_noise (+ k_wire on
        the stochastic int8 wire)."""
        if transport.stochastic:
            c["key"], c["k_sample"], c["k_noise"], c["k_wire"] = \
                jax.random.split(c["pstate"].key, 4)
        else:
            c["key"], c["k_sample"], c["k_noise"] = \
                jax.random.split(c["pstate"].key, 3)
            c["k_wire"] = None

    def stage_scenario_view(c):
        """reads pstate.round; writes eff_adj (+ alive/fire/att_on/
        att_kind/att_scale with a scenario: ``scenario_view``) — the
        gossip-round axis is the scenario's epoch axis."""
        scenario_view(c, scenario, adj_j, c["pstate"].round)

    def stage_peer_sample(c):
        """reads eff_adj, pstate.conf, k_sample; writes theta and sampled
        (``peer_sample``; without DTS every live peer is listened to)."""
        peer_sample(cfg, c, c["pstate"].conf, listen_all=True)

    def stage_transport(c):
        """reads sampled, eff_adj, sizes, params, pstate.{wire_err,round},
        k_wire; writes P, agg, wire_err (``mix``) — the wire stack
        (fp32/bf16/int8 + EF21) over the in_jit backends or the cross-pod
        ppermute ring, or a robust rule."""
        pstate = c["pstate"]
        mix(cfg, transport, c, c["params"], pstate.wire_err, pstate.round,
            robust=robust)

    def stage_damage_check(c):
        """reads agg, pstate.{backup,best_loss}; writes eval_loss (the
        held-out self-eval of the candidate aggregate), damaged, and agg
        (damaged pods restore their backup instead of adopting the mix —
        the pod time machine)."""
        pstate = c["pstate"]
        c["eval_loss"] = self_eval(c["agg"])
        c["damaged"] = dts_mod.is_damaged(c["eval_loss"], pstate.best_loss)
        c["agg"] = tree_select(c["damaged"], pstate.backup, c["agg"])

    def stage_attack_inject(c):
        """reads agg, params, att_on, theta, k_noise; writes out (actively
        attacking slots ship their poisoned send, everyone else adopts the
        aggregate) and att_active (the [W] mask of slots that actually
        poisoned — what trust_update needs to reconstruct the true
        sends)."""
        if scenario is None:
            c["out"] = c["agg"]
            c["att_active"] = jnp.zeros((w,), bool)
            return
        # attackers replace their post-mix state with the poisoned send
        # (based on the aggregate + their own pre-mix params, same
        # transforms as the simulation engines); peers consume it at the
        # NEXT gossip round. poison_sends' honest base is the pre-mix
        # params, but honest pods must ADOPT the aggregate — so re-select:
        # actively attacking slots ship the poison, everyone else the mix
        poisoned = attacks_mod.poison_sends(
            c["k_noise"], scenario.kinds_present, scenario.attack_kind,
            scenario.attack_scale, c["att_on"], c["agg"], c["params"],
            theta=c["theta"] if cfg.use_dts else None)
        att = jnp.zeros_like(c["att_on"])
        for kind in scenario.kinds_present:
            if kind in attacks_mod.MODEL_ATTACKS:
                att = att | (scenario.attack_kind == ATTACK_CODE[kind])
        c["att_active"] = att & c["att_on"]
        c["out"] = tree_select(c["att_active"], poisoned, c["agg"])

    def stage_trust_update(c):
        """reads losses, out, params, att_active, start_params,
        pstate.{conf, last_loss, sketch} (+ damaged) + what
        ``confidence_update`` reads; writes conf (+ sketch). The scored
        delta: with ``start_params`` each pod's TRUE local-train delta —
        the post-attack send (poison for active attackers, the trained
        params peers actually consume otherwise) minus the params the pod
        departed from, as the simulation engines' ``trained − start``;
        without it the round displacement ``out − params``."""
        pstate = c["pstate"]
        damaged = c.get("damaged")
        if damaged is None:
            damaged = jnp.zeros((w,), bool)
        loss_trust = jnp.where(damaged, dts_mod.DAMAGE_PENALTY,
                               c["losses"] - pstate.last_loss)
        deltas = None
        if channels and c["start_params"] is not None:
            sent = tree_select(c["att_active"], c["out"], c["params"])
            deltas = dts_mod.flatten_stacked(sent) \
                - dts_mod.flatten_stacked(c["start_params"])
        elif channels:
            deltas = dts_mod.flatten_stacked(c["out"]) \
                - dts_mod.flatten_stacked(c["params"])
        confidence_update(cfg, c, pstate.conf, pstate.sketch, loss_trust,
                          damaged, deltas)

    def stage_finalize(c):
        """reads out, conf, losses, wire_err, sketch (+ fire/damaged/
        eval_loss); writes next (PodState) and new_params. With a
        scenario, non-firing pods freeze (sketch rows included); with the
        time machine, improving rounds refresh the backup (the ratchet: a
        damaged pod adopted its backup, trains on, and re-backs-up once
        its held-out eval improves)."""
        pstate = c["pstate"]
        if time_machine:
            improved = (c["eval_loss"] < pstate.best_loss) & ~c["damaged"]
            backup = tree_select(improved, c["out"], pstate.backup)
            best_loss = jnp.where(improved, c["eval_loss"],
                                  pstate.best_loss)
        else:
            backup, best_loss = pstate.backup, pstate.best_loss
        sketch = c["sketch"]
        if scenario is not None:
            fire = c["fire"]
            out = tree_select(fire, c["out"], c["params"])
            wire_err = tree_select(fire, c["wire_err"], pstate.wire_err) \
                if use_ef else pstate.wire_err
            conf = jnp.where(fire[:, None], c["conf"], pstate.conf)
            last_loss = jnp.where(fire, c["losses"], pstate.last_loss)
            if time_machine:
                backup = tree_select(fire, backup, pstate.backup)
                best_loss = jnp.where(fire, best_loss, pstate.best_loss)
            if corr:
                sketch = jnp.where(fire[:, None, None], c["sketch"],
                                   pstate.sketch)
        else:
            out, wire_err = c["out"], c["wire_err"]
            conf, last_loss = c["conf"], c["losses"]
        c["next"] = PodState(conf=conf, last_loss=last_loss, key=c["key"],
                             round=pstate.round + 1, wire_err=wire_err,
                             backup=backup, best_loss=best_loss,
                             sketch=sketch)
        c["new_params"] = out

    stages = (
        ("split_keys", stage_split_keys),
        ("scenario_view", stage_scenario_view),
        ("peer_sample", stage_peer_sample),
        ("transport", stage_transport),
    ) + ((("damage_check", stage_damage_check),) if time_machine
         else ()) + (
        ("attack_inject", stage_attack_inject),
        ("trust_update", stage_trust_update),
        ("finalize", stage_finalize),
    )

    def gossip_round(pstate: PodState, params, losses, start_params=None):
        c = {"pstate": pstate, "params": params, "losses": losses,
             "start_params": start_params, "sizes": sizes_j}
        run_pipeline(stages, c)
        return c["next"], c["new_params"]

    gossip_round.stages = stages
    return gossip_round


# ---------------------------------------------------------------------------
# Cross-device participation: enrolled population, sampled cohorts
# ---------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclass
class CrossDeviceState:
    """Population state for the cross-device path: every per-worker buffer
    the dense engines carry, sized to the ENROLLED population [N] instead
    of the round cohort [k], plus the participation bookkeeping the
    gather/scatter drivers need (when a user last fired, how often it has
    been observed, which global round each sketch slot came from)."""
    params: Any                  # stacked [N, ...] per-user models
    backup: Any                  # stacked [N, ...] time-machine backups
    conf: jnp.ndarray            # [N, N] trust confidences
    best_loss: jnp.ndarray       # [N]
    last_loss: jnp.ndarray       # [N]
    key: jnp.ndarray
    epoch: jnp.ndarray           # [N] per-user completed-round counters
    last_part: jnp.ndarray       # [N] int32 global round of the user's
                                 # last COMPLETED participation (anchor for
                                 # lazy confidence decay + staleness)
    obs: jnp.ndarray             # [N] int32 completed-participation count
    wire_err: Any = None         # EF21 residuals [N, ...]
    sketch: Any = None           # [N, R, S] sign-sketch ring buffer
    sketch_round: Any = None     # [N, R] int32 global-round stamps per
                                 # ring slot (−1 = never filled) — the
                                 # alignment evidence sparse correlation
                                 # trust needs (dts.stamped_correlation)


def init_cross_device_state(key, task: Task, enrolled: int, *,
                            wire_error: bool = False,
                            sketch=None) -> CrossDeviceState:
    """``init_state`` over the enrolled population, plus the participation
    bookkeeping. ``sketch``: the (R, S) dims from ``sketch_shape(cfg)``
    when the correlation channel is on, else None. Stamps start at −1: an
    empty ring slot can never stamp-match, so fresh users carry zero
    correlation evidence by construction."""
    st = init_state(key, task, enrolled, wire_error=wire_error,
                    sketch=sketch)
    return CrossDeviceState(
        **vars(st),
        last_part=jnp.zeros((enrolled,), jnp.int32),
        obs=jnp.zeros((enrolled,), jnp.int32),
        sketch_round=jnp.full((enrolled, sketch[0]), -1, jnp.int32)
        if sketch else None,
    )


def build_cross_device_round(task: Task, cfg: DeFTAConfig,
                             train: TrainConfig, world, sizes, *,
                             gossip_backend: str = "einsum",
                             num_classes: int = 0,
                             transport: Optional[Transport] = None,
                             telemetry=None, shard=None):
    """The cross-device round program: ``participation`` gathers the
    round's k-member cohort out of the enrolled population into a k-row
    ``DeFTAState`` block, ``block_stages`` — the dense round's stages —
    run on it, and ``scatter_merge`` writes the survivors' state back: one
    scannable body, so ``drive_epochs`` fuses a whole eval window of
    gather → cohort block → scatter into a single XLA dispatch exactly
    like the dense path.

    ``world`` is a ``repro.scenarios.cross_device.CompiledWorld``: the
    per-round cohort indices, mid-round dropout / straggler-timeout draws,
    cohort topology, and the enrolled-population attack assignment, all
    compiled host-side once. Graceful-degradation semantics:

    * mid-round dropout (``world.survive`` False): the slot's partial
      contribution is masked out of ``eff_adj`` BEFORE the mixing-matrix
      row normalization — survivors renormalize over who actually shipped
      (DeceFL-style) — and the dropper's own state does not fire;
    * straggler timeout (``world.complete`` False): the slot trains and
      is consumed by peers, but its own update misses the round's merge
      (it does not fire), the async tick semantics mapped to cohorts;
    * fewer than ``world.k_min`` surviving sampled peers: the row's
      mixing degrades to the identity — the worker self-trains for the
      round, no NaN weights, no error;
    * vacancy (fewer available users than k): pad slots carry
      ``filled=False``, never fire, and are masked out of everything.

    Attackers attack whenever they participate: ``att_on`` is the
    participation mask, gathered from the enrolled-population assignment
    (29% of ENROLLED means ~29% of every cohort in expectation).

    Trust stays calibrated under sparse observation: gathered confidence
    rows decay toward the uninformative prior (0) by
    ``cfg.dts_conf_decay ** (rounds since the row's user last fired)`` —
    applied lazily at gather, written back only on fire, so absent users'
    rows stay bit-unchanged — and the correlation channel scores
    stamp-ALIGNED sketch slots gated on ≥ ``cfg.dts_min_obs`` common
    observations (``dts.stamped_correlation``). ``cfg.max_staleness``
    additionally drops peers whose model is > S rounds old (including
    never-participated users once t > S, whose "model" is still the
    round-0 init).

    ``shard`` (a ``repro.sharding.WorkerShards``): shard the ENROLLED-N
    population buffers across the worker mesh axis — the gather lowers
    to collectives, the dense k-block stays replicated (k ≪ N), and the
    scatter_merge writes back to the owning shard; the round constrains
    its output state so the donated scan carry stays row-sharded.
    """
    from repro.scenarios.robust_agg import ROBUST_RULES

    n = int(world.enrolled)
    k = int(world.sample_k)
    corr = "corr" in resolve_dts_signal(cfg)
    decay = float(cfg.dts_conf_decay)
    max_staleness = int(cfg.max_staleness)
    sizes_j = jnp.asarray(np.asarray(sizes, np.float32))

    if world.epochs <= 0:
        raise ValueError("cross-device world compiled for 0 rounds")
    if cfg.aggregation in ROBUST_RULES:
        raise ValueError(
            f"robust aggregation ({cfg.aggregation!r}) has no "
            f"cross-device selection yet — use defta/defl/uniform")
    if transport is None:
        # the cohort block is dense [k, k]: no sparse adjacency support —
        # except under secagg, whose per-edge pads need the support
        # explicitly (every cohort-slot pair is a potential wire edge;
        # pads are keyed on (round, slot, slot), so two different users
        # occupying the same slot in different rounds never share one)
        support = np.ones((k, k), bool) if cfg.secagg is not None else None
        transport = make_transport(cfg, backend=gossip_backend,
                                   adjacency=support)
    use_ef = transport.use_ef

    if telemetry is not None:
        from repro.telemetry.spec import cross_device_specs
        telemetry.declare(*cross_device_specs(k, use_ef=use_ef))

    part_ix = jnp.asarray(world.part_ix)        # [T, k] int32, per-round
    filled_t = jnp.asarray(world.filled)        # [T, k] bool
    survive_t = jnp.asarray(world.survive)      # [T, k] bool
    complete_t = jnp.asarray(world.complete)    # [T, k] bool
    adj_t = jnp.asarray(world.adj)              # [T, k, k] bool
    att_kind_u = jnp.asarray(world.attack_kind)     # [N] int32
    att_scale_u = jnp.asarray(world.attack_scale)   # [N] float32

    block = block_stages(task, cfg, train, k, transport, telemetry=telemetry,
                         k_min=int(world.k_min),
                         kinds_present=world.kinds_present,
                         num_classes=num_classes)

    def stage_participation(c):
        """reads epoch (the global round t), state (the population), data,
        sizes; writes population (the state it read), state (the cohort's
        k-row DeFTAState block: params, backup, wire_err, best_loss,
        last_loss, the decayed [k, k] confidence block, sketch, and the
        population key), data and sizes (the cohort's shards), ix (the
        cohort), active/fire (dropout ∧ straggler ∧ filled), eff_adj (the
        survivor-masked cohort topology), att_kind/att_scale/att_on,
        g_conf_raw/g_conf_rows (the undecayed/decayed [k, N] confidence
        rows), g_last_part, g_obs (+ g_stamp). The gather: one x[ix] per
        buffer — XLA fuses it into the scan body, no extra dispatch."""
        pop, t = c["state"], c["epoch"]
        ix = part_ix[t]
        c["ix"] = ix
        active = filled_t[t] & survive_t[t]
        c["active"] = active
        c["fire"] = active & complete_t[t]
        params = jax.tree.map(lambda x: x[ix], pop.params)
        backup = jax.tree.map(lambda x: x[ix], pop.backup)
        wire_err = jax.tree.map(lambda x: x[ix], pop.wire_err) \
            if use_ef else None
        c["g_last_part"] = pop.last_part[ix]
        c["g_conf_raw"] = pop.conf[ix]                   # [k, N]
        rows = c["g_conf_raw"]
        if decay < 1.0:
            gap = jnp.maximum(t - c["g_last_part"], 0).astype(jnp.float32)
            rows = rows * jnp.power(jnp.float32(decay), gap)[:, None]
        c["g_conf_rows"] = rows
        c["population"] = pop
        c["state"] = DeFTAState(
            params=params, backup=backup, conf=rows[:, ix],
            best_loss=pop.best_loss[ix], last_loss=pop.last_loss[ix],
            key=pop.key, epoch=None, wire_err=wire_err,
            sketch=pop.sketch[ix] if corr else None)
        c["g_obs"] = pop.obs[ix]
        if corr:
            c["g_stamp"] = pop.sketch_round[ix]
        data = c["data"]
        c["data"] = {"x": data["x"][ix], "y": data["y"][ix],
                     "mask": data["mask"][ix]}
        c["sizes"] = sizes_j[ix]
        c["att_kind"] = att_kind_u[ix]
        c["att_scale"] = att_scale_u[ix]
        c["att_on"] = active & (c["att_kind"] > 0)
        eff = adj_t[t] & active[None, :] & active[:, None]
        if max_staleness:
            fresh = (t - c["g_last_part"]) <= max_staleness
            eff = eff & fresh[None, :]
        c["eff_adj"] = eff
        if telemetry is not None:
            telemetry.emit(c, "round", t)
            telemetry.emit(c, "cohort", ix)
            telemetry.emit(c, "occupancy", active.sum())
            telemetry.emit(c, "dropout_count",
                           (filled_t[t] & ~survive_t[t]).sum())
            telemetry.emit(c, "straggler_count",
                           (active & ~complete_t[t]).sum())
            telemetry.emit(c, "fire", c["fire"])
            telemetry.emit(c, "scatter_writes", c["fire"].sum())

    def stage_scatter_merge(c):
        """reads population, state (the gathered block), ix, fire, epoch,
        trained, backup, wire_err, conf, best_loss, last_loss, key,
        g_conf_rows, g_conf_raw, g_last_part, g_obs (+ sketch, stamp,
        g_stamp); writes next (the population state). Fire-gated:
        non-firing cohort members and absent users scatter back their
        ORIGINAL (undecayed) rows, so every carried buffer — trust, EF
        residuals, sketch history, stamps — is bit-unchanged across rounds
        a user misses. Cohort indices are distinct within a round, so the
        row scatters never conflict."""
        pop, blk = c["population"], c["state"]
        t, ix, fire = c["epoch"], c["ix"], c["fire"]

        def scat_tree(full, new_rows, old_rows):
            sel = tree_select(fire, new_rows, old_rows)
            return jax.tree.map(lambda f, s: f.at[ix].set(s), full, sel)

        params = scat_tree(pop.params, c["trained"], blk.params)
        backup = scat_tree(pop.backup, c["backup"], blk.backup)
        wire_err = scat_tree(pop.wire_err, c["wire_err"],
                             blk.wire_err) if use_ef else pop.wire_err
        rows_new = c["g_conf_rows"].at[:, ix].set(c["conf"])
        conf = pop.conf.at[ix].set(
            jnp.where(fire[:, None], rows_new, c["g_conf_raw"]))
        if corr:
            sketch = pop.sketch.at[ix].set(
                jnp.where(fire[:, None, None], c["sketch"], blk.sketch))
            stamps = pop.sketch_round.at[ix].set(
                jnp.where(fire[:, None], c["stamp"], c["g_stamp"]))
        else:
            sketch, stamps = pop.sketch, pop.sketch_round
        c["next"] = CrossDeviceState(
            params=params, backup=backup, conf=conf,
            best_loss=pop.best_loss.at[ix].set(
                jnp.where(fire, c["best_loss"], blk.best_loss)),
            last_loss=pop.last_loss.at[ix].set(
                jnp.where(fire, c["last_loss"], blk.last_loss)),
            key=c["key"],
            epoch=pop.epoch.at[ix].add(fire.astype(jnp.int32)),
            last_part=pop.last_part.at[ix].set(
                jnp.where(fire, t, c["g_last_part"])),
            obs=pop.obs.at[ix].set(
                jnp.where(fire, c["g_obs"] + 1, c["g_obs"])),
            wire_err=wire_err, sketch=sketch, sketch_round=stamps)

    stages = (("participation", stage_participation),) + block \
        + (("scatter_merge", stage_scatter_merge),)
    rnd = round_program(stages, rows=n, telemetry=telemetry, shard=shard)
    rnd.cohort = (n, k)
    return rnd
