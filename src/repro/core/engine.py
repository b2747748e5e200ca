"""Unified round-program engine: ONE composable superstep pipeline behind
DeFTA, async DeFTA, FedAvg, and the multi-pod ppermute path.

The DFL surveys (Gabrielli et al. 2023; Hallaji et al. 2024) frame a
decentralized-FL round as a pipeline of interchangeable stages. This module
makes that decomposition executable: a *round program* is an ordered tuple
of named stages over a mutable round context::

    split_keys -> scenario_view -> peer_sample -> transport (mix/wire/EF)
                -> damage_check -> local_train -> attack_inject
                -> trust_update -> finalize/merge

Each execution mode is a *stage selection* over this pipeline:

* sync DeFTA (``core.defta``)    — the full list; static finalize without a
  scenario, churn/straggler merge with one.
* async DeFTA (``core.async_defta``) — the same round wrapped in a
  fire-gated tick (``build_fire_gated_tick``): speed-sampled workers merge
  the new state, the rest freeze.
* FedAvg (``core.fedavg``)       — star topology: ``transport`` degrades to
  a server broadcast going down and a size-weighted mean coming back up;
  no peer sampling, no DTS, no time machine.
* multi-pod (``launch.train --fl``) — ``build_pod_round``: the same
  scenario/sample/transport/trust stages over the pod axis, with the
  ``ppermute`` transport shipping the encoded wire payload on the
  offset-skipping ring (local training happens outside, in
  ``build_fl_train_step``). With ``cfg.time_machine`` + a ``self_eval``
  callable the pod path gains the damage check too: a held-out
  self-evaluation between gossip rounds guards what a pod adopts.

The ``trust_update`` stage is itself a selection
(``DeFTAConfig.dts_signal``): the paper's loss-delta signal (``"loss"``,
bit-exact), the update-geometry signal of ``core.dts.geom_scores``
(``"geom"``), the cross-round collusion-correlation signal of
``core.dts.colluder_scores`` (``"corr"`` — DTS v3, scored over the
[W, R, S] sign-sketch ring buffer the state carries), or their fusions
(``"both"`` = loss+geom, ``"all"`` = loss+geom+corr) — one stage variant
shared by every mode; see docs/ARCHITECTURE.md for the full stage
contract. The sketch history is plain carried state (``DeFTAState.sketch``
/ ``PodState.sketch``): it rotates inside ``trust_update`` and merges
through finalize/fire/tick like every other buffer, so the correlation
signal rides the scan supersteps with zero extra dispatches.

Transports are a pluggable stage (``make_transport``): ``in_jit`` wraps the
einsum/pallas/sparse/quant backends of ``core.gossip.mix_pytree``;
``ppermute`` wraps ``mix_pytree_ppermute`` for cross-pod meshes. Both honor
the full wire stack (fp32/bf16/int8 payloads, EF21 residuals, stochastic
rounding where supported).

Drivers are shared too: ``drive_epochs`` is the chunked-``lax.scan``
superstep driver with donated buffers and dispatch accounting (one XLA
dispatch per eval chunk) used by ``run_defta`` AND ``run_fedavg``;
``drive_ticks`` is the tick driver with the device-side
``lax.while_loop`` early exit used by ``run_async_defta``. The triplicated
scan/while_loop scaffolding the three engines used to carry now lives here
once.

Parity contract: the pipeline reproduces the pre-refactor engines
bit-identically at fixed seed (tests/test_engine.py vs
tests/golden_engine.json) — stages split the old round bodies, they do not
reorder a single op or PRNG split.
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
    single-round use).

    ``scenario``: a ``repro.scenarios.CompiledScenario``. When given, the
    traced ``epoch`` index looks up that epoch's alive/link/fire/attack
    state (and, for time-varying topologies, the segment's regenerated
    adjacency) from the compiled device arrays — churn, partitions,
    stragglers and the whole attack zoo run INSIDE the scan body, no host
    round-trips. Without it the body reproduces the legacy static-topology
    round (with the paper's noise attack on ``malicious`` workers)
    bit-for-bit.

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
    w = adj.shape[0]
    adj_j = jnp.asarray(adj)
    sizes_j = jnp.asarray(np.asarray(sizes, np.float32))
    adj_self = adj | np.eye(w, dtype=bool)
    outdeg = jnp.asarray(adj_self.sum(axis=0).astype(np.float32))
    malicious_j = jnp.asarray(malicious)
    ltrain = local_train_fn(task, train, cfg.local_epochs,
                            dp_clip=cfg.dp_clip, dp_sigma=cfg.dp_sigma)
    channels = resolve_dts_signal(cfg)
    corr = "corr" in channels
    max_staleness = int(cfg.max_staleness)

    from repro.scenarios import attacks as attacks_mod
    from repro.scenarios.compile import ATTACK_CODE, epoch_view
    from repro.scenarios.robust_agg import ROBUST_RULES, robust_mix

    robust = cfg.aggregation in ROBUST_RULES
    if not robust:
        if cfg.aggregation == "defta":
            col_w = sizes_j / outdeg
        elif cfg.aggregation == "defl":
            col_w = sizes_j
        else:  # uniform gossip
            col_w = jnp.ones_like(sizes_j)

    if scenario is not None:
        if scenario.num_workers != w:
            raise ValueError(f"scenario compiled for W="
                             f"{scenario.num_workers}, topology has {w}")
        if "label_flip" in scenario.kinds_present and num_classes <= 0:
            raise ValueError("label_flip scenario needs num_classes > 0")

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
    stochastic = transport.stochastic
    regen = scenario is not None and scenario.adj_seg is not None
    dp_update = uses_update_dp(cfg)
    # masked_geom: the receiver of an aggregate-only secagg sees no
    # per-peer update, so the geometry/correlation channels are replaced
    # by the pooled aggregate-minus-own-contribution signal
    masked_geom = cfg.secagg is not None \
        and cfg.secagg_mode == "masked_geom"

    if telemetry is not None:
        from repro.telemetry.spec import defta_specs
        telemetry.declare(*defta_specs(w, scenario=scenario is not None,
                                       use_ef=use_ef))
        tm_specs = telemetry.specs       # snapshot: wrappers may add more

    # ---- stages -----------------------------------------------------------

    def stage_split_keys(c):
        """reads state.key; writes key (next round), k_sample, k_train,
        k_noise and — build-time gated — k_wire (stochastic int8 wire)
        and k_dp (update-DP stage). The split layout is frozen
        (``split_round_keys``): adding a split changes every downstream
        draw."""
        c.update(split_round_keys(c["state"].key, stochastic, dp_update))

    def stage_scenario_view(c):
        """reads epoch; writes eff_adj (and alive/fire/att_on with a
        scenario): the round's effective topology = (per-segment or static)
        adjacency ∧ link_ok ∧ alive on both endpoints. With
        ``cfg.max_staleness > 0`` (build-time gated: the default 0 traces
        no extra ops) edges from peers whose epoch counter lags the
        receiver's by more than S rounds are additionally dropped — a
        straggler's S-rounds-old model is excluded from the merge instead
        of silently mixed (async ticks and straggler scenarios open
        exactly these gaps)."""
        if scenario is not None:
            view = epoch_view(scenario, c["epoch"])
            c["alive"], c["fire"], c["att_on"] = \
                view["alive"], view["fire"], view["attack_on"]
            base = view["adj"] if regen else adj_j
            c["eff_adj"] = base & view["link_ok"] \
                & c["alive"][None, :] & c["alive"][:, None]
        else:
            c["eff_adj"] = adj_j
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

    def stage_peer_sample(c):
        """reads eff_adj, state.conf, k_sample; writes theta [W,W] (DTS
        sampling weights, observed by theta-aware attacks and reused as
        the geometric reference weights) and sampled [W,W] (Gumbel top-k,
        ≤ num_sampled per row)."""
        if cfg.use_dts:
            theta = dts_mod.sample_weights(c["state"].conf, c["eff_adj"],
                                           cfg.crelu_slope)        # [W,W]
        else:
            theta = c["eff_adj"] / jnp.maximum(
                c["eff_adj"].sum(1, keepdims=True), 1)
        c["theta"] = theta
        skeys = jax.random.split(c["k_sample"], w)
        c["sampled"] = jax.vmap(
            lambda k, t: dts_mod.sample_peers(k, t, cfg.num_sampled)
        )(skeys, theta)                                            # [W,W]
        if telemetry is not None:
            telemetry.emit(c, "theta_in", theta.mean(axis=0))

    def stage_transport(c):
        """reads sampled, eff_adj, state.params, state.wire_err, k_wire;
        writes P (mixing matrix), agg (the mixed models) and wire_err
        (advanced EF21 residuals). This is the pluggable stage: in_jit
        mix_pytree backends, the cross-pod ppermute ring, or a robust
        rule (trimmed_mean/median/krum) replacing the weighted mix."""
        state = c["state"]
        mask = (c["sampled"] & c["eff_adj"]) | jnp.eye(w, dtype=bool)
        if telemetry is not None:
            from repro.telemetry.spec import stacked_payload_bytes
            live = (c["sampled"] & c["eff_adj"]
                    & ~jnp.eye(w, dtype=bool)).sum()
            telemetry.emit(c, "edges", live)
            telemetry.emit(c, "wire_bytes", live.astype(jnp.float32) *
                           stacked_payload_bytes(state.params,
                                                 transport.wire))
        if robust:
            # classical Byzantine-robust baselines: unweighted rule over
            # the sampled set; P degrades to the uniform bookkeeping
            # weights the DTS confidence update needs
            c["agg"] = robust_mix(cfg.aggregation, mask, state.params,
                                  trim=cfg.robust_trim)
            c["P"] = mask / mask.sum(axis=1, keepdims=True)
            c["wire_err"] = state.wire_err
            return
        if scenario is not None:
            # per-epoch outdegree renormalization under the dynamic
            # adjacency (churn/link failures change |D_j|/d_j)
            P = dynamic_mixing_matrix(c["sampled"], c["eff_adj"], sizes_j,
                                      cfg.aggregation)
        else:
            P = mask * col_w[None, :]
            P = P / P.sum(axis=1, keepdims=True)
        c["P"] = P
        round_ = 0 if c["epoch"] is None else c["epoch"]
        if use_ef:
            if state.wire_err is None:
                raise ValueError(
                    "cfg enables gossip error feedback on a lossy wire "
                    "but the state carries no residual buffers — build "
                    "it with init_state(..., wire_error=True)")
            c["agg"], c["wire_err"] = transport.mix(
                P, state.params, residual=state.wire_err, key=c["k_wire"],
                round_=round_)
            if telemetry is not None:
                telemetry.emit(c, "ef_norm", jnp.linalg.norm(
                    dts_mod.flatten_stacked(c["wire_err"]), axis=1))
        else:
            c["agg"] = transport.mix(P, state.params, key=c["k_wire"],
                                     round_=round_)
            c["wire_err"] = state.wire_err

    def stage_damage_check(c):
        """reads agg, state.{backup,best_loss}, data; writes y_data
        (label-flip poisoned labels where active), loss_agg (each worker's
        self-evaluation of the aggregate), damaged [W] and start (the
        params local training departs from — the backup on damaged
        rounds: the §3.3 time machine)."""
        state, data = c["state"], c["data"]
        y_data = data["y"]
        if scenario is not None and "label_flip" in scenario.kinds_present:
            # data poisoning: label-flippers train (and self-evaluate) on
            # y -> C-1-y; their protocol behaviour stays honest
            lf = (scenario.attack_kind == ATTACK_CODE["label_flip"]) \
                & c["att_on"]
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
        if telemetry is not None:
            telemetry.emit(c, "loss_agg", c["loss_agg"])
            telemetry.emit(c, "damaged", c["damaged"])

    def stage_local_train(c):
        """reads start, y_data, data, k_train; writes trained (post-SGD
        stacked params) and train_loss — ``local_epochs`` minibatch epochs
        per worker, vmapped over the worker axis."""
        data = c["data"]
        tkeys = jax.random.split(c["k_train"], w)
        c["trained"], c["train_loss"] = jax.vmap(
            lambda k, p, x, y, m: ltrain(k, p, x, y, m)
        )(tkeys, c["start"], data["x"], c["y_data"], data["mask"])
        if telemetry is not None:
            telemetry.emit(c, "train_loss", c["train_loss"])

    def stage_dp_noise(c):
        """reads trained, start, k_dp; writes trained — per-round
        update-DP (``apply_update_dp``): every worker clips its local-
        update delta and noises it BEFORE it becomes next round's send,
        so both peers and the trust channels only ever observe the
        privatized update. Build-time gated on ``uses_update_dp(cfg)``
        (the default σ=0 compiles this stage away entirely)."""
        c["trained"] = apply_update_dp(cfg, c["k_dp"], c["start"],
                                       c["trained"])

    def stage_attack_inject(c):
        """reads trained, agg, att_on, theta, k_noise; writes trained
        (attacker slots replaced by their poisoned sends — what peers
        consume NEXT round). theta feeds the adaptive theta_aware gate."""
        if scenario is not None:
            c["trained"] = attacks_mod.poison_sends(
                c["k_noise"], scenario.kinds_present, scenario.attack_kind,
                scenario.attack_scale, c["att_on"], c["agg"], c["trained"],
                theta=c["theta"] if cfg.use_dts else None)
        else:
            # legacy path: the paper's aggregate+noise on ``malicious``
            poisoned = attacks_mod.noise(
                c["k_noise"], c["agg"], c["trained"],
                jnp.full((w,), noise_scale, jnp.float32))
            c["trained"] = tree_select(malicious_j, poisoned, c["trained"])

    def stage_trust_update(c):
        """reads loss_agg, damaged, sampled, P, theta, state.{conf,
        best_loss, last_loss} (+ trained, start, eff_adj, fire on the
        geometric/correlation path, + state.sketch on "corr"/"all");
        writes conf, backup, best_loss, last_loss (+ sketch: the rotated
        ring buffer with this round's sign-sketch appended). The
        confidence update is ``c ← c − m ∘ p · signal`` where signal is
        the loss delta (dts_signal="loss", Algorithm 3 line 12,
        bit-exact), the centered update-geometry scores ("geom"), the
        cross-round collusion-correlation scores ("corr"), or their
        fusions ("both"/"all") — geometry and the sketches both observe
        each peer's LOCAL-UPDATE delta ``trained − start`` (the step it
        applied on top of its adopted aggregate; post attack injection,
        so the poison is exactly what gets scored) at per-(receiver,
        peer) resolution."""
        state = c["state"]
        loss_trust = jnp.where(c["damaged"], dts_mod.DAMAGE_PENALTY,
                               c["loss_agg"] - state.last_loss)
        c["sketch"] = state.sketch
        if channels and masked_geom:
            # aggregate-only visibility: the receiver never sees a
            # per-peer delta, so geometry/correlation degrade to the
            # pooled aggregate-minus-own-contribution signal, broadcast
            # uniformly over the receiver's sampled row (it cannot tell
            # WHICH peer moved the pool) — the measured DTS-vs-secagg
            # tension the bench records
            deltas = dts_mod.flatten_stacked(c["trained"]) \
                - dts_mod.flatten_stacked(c["start"])
            gmask = c["eff_adj"] & c["fire"][None, :] \
                if scenario is not None else c["eff_adj"]
            mg = dts_mod.masked_geom_trust(deltas, c["P"], gmask)
            c["conf"] = state.conf - c["sampled"] * c["P"] \
                * (loss_trust + cfg.dts_geom_weight * mg)[:, None]
        elif channels:
            # non-firing peers (stragglers) are excluded: fire_merge
            # discards their this-round delta, so peers never consume it
            # — scoring it would drift trust on phantom updates
            deltas = dts_mod.flatten_stacked(c["trained"]) \
                - dts_mod.flatten_stacked(c["start"])
            gmask = c["eff_adj"] & c["fire"][None, :] \
                if scenario is not None else c["eff_adj"]
            if corr:
                if state.sketch is None:
                    raise ValueError(
                        f"dts_signal={cfg.dts_signal!r} needs the sketch "
                        f"ring buffer — build the state with "
                        f"init_state(..., sketch=sketch_shape(cfg))")
                c["sketch"] = dts_mod.update_sketch(state.sketch, deltas,
                                                    seed=cfg.seed)
            c["conf"] = dts_mod.geom_confidence_update(
                cfg.dts_signal, cfg.dts_geom_weight, state.conf,
                c["sampled"], c["P"], loss_trust, c["damaged"], deltas,
                gmask, c["theta"], sketch=c["sketch"],
                lam_corr=cfg.dts_corr_weight)
        else:
            c["conf"] = state.conf - c["sampled"] * c["P"] \
                * loss_trust[:, None]
        if telemetry is not None:
            telemetry.emit(c, "loss_trust", loss_trust)
            telemetry.emit(c, "conf_in", c["conf"].mean(axis=0))
            # the scored observable: ‖trained − start‖ per worker (on the
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

    stages = (
        ("split_keys", stage_split_keys),
        ("scenario_view", stage_scenario_view),
        ("peer_sample", stage_peer_sample),
        ("transport", stage_transport),
        ("damage_check", stage_damage_check),
        ("local_train", stage_local_train),
    ) + ((("dp_noise", stage_dp_noise),) if dp_update else ()) + (
        ("attack_inject", stage_attack_inject),
        ("trust_update", stage_trust_update),
        ("finalize", stage_finalize) if scenario is None
        else ("fire_merge", stage_fire_merge),
    )

    def round(state: DeFTAState, data, epoch=None):
        c = {"state": state, "data": data, "epoch": epoch}
        run_pipeline(stages, c)
        nxt = constrain_worker_rows(c["next"], shard, w)
        if telemetry is None:
            return nxt
        return nxt, telemetry.collect(c, tm_specs)

    round.stages = stages
    round.telemetry = telemetry
    return round


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
        tm_specs = telemetry.specs

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

    def round(state, data, epoch=None):
        # FedAvg's round is epoch-invariant; the traced index only feeds
        # the telemetry round stamp (dead when telemetry is None)
        c = {"state": state, "data": data, "epoch": epoch}
        run_pipeline(stages, c)
        if telemetry is None:
            return c["next"]
        return c["next"], telemetry.collect(c, tm_specs)

    round.stages = stages
    round.telemetry = telemetry
    return round


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
                    scenario=None, num_appended: int = 0, self_eval=None):
    """The multi-pod gossip round as the SAME stage pipeline over the pod
    axis: scenario_view -> peer_sample (DTS) -> transport (the full wire
    stack, ppermute or in_jit) -> [damage_check] -> attack_inject ->
    trust_update. Local training happens between gossip rounds in
    ``build_fl_train_step``.

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
    local-train deltas ``sent − start``, matching the simulation engines
    exactly (the launcher threads it); when omitted the signals fall back
    to the round displacement ``out − params``, the legacy pod
    approximation. The scenario epoch axis is the GOSSIP ROUND index
    (pstate.round).

    ``num_appended`` attackers from the scenario occupy the LAST pod slots
    (paper §4.3: attackers newly joined) — the caller sizes the mesh so
    vanilla + appended == npods.
    """
    from repro.scenarios import attacks as attacks_mod
    from repro.scenarios.compile import ATTACK_CODE, epoch_view
    from repro.scenarios.robust_agg import ROBUST_RULES, robust_mix

    del num_appended                      # slots are already in npods
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
    regen = scenario is not None and scenario.adj_seg is not None
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
    # without self_eval the selection quietly stays TM-less (the
    # pre-existing pod contract — sim configs default time_machine=True
    # and are reused here)
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
        """reads pstate.round; writes eff_adj (+ alive/fire/att_on with a
        scenario) — the gossip-round axis is the scenario's epoch axis."""
        if scenario is not None:
            view = epoch_view(scenario, c["pstate"].round)
            c["alive"], c["fire"], c["att_on"] = \
                view["alive"], view["fire"], view["attack_on"]
            base = view["adj"] if regen else adj_j
            c["eff_adj"] = base & view["link_ok"] \
                & c["alive"][None, :] & c["alive"][:, None]
        else:
            c["eff_adj"] = adj_j

    def stage_peer_sample(c):
        """reads eff_adj, pstate.conf, k_sample; writes theta and sampled
        (without DTS every live peer is listened to and theta is the
        uniform row-normalized adjacency)."""
        if cfg.use_dts:
            theta = dts_mod.sample_weights(c["pstate"].conf, c["eff_adj"],
                                           cfg.crelu_slope)
            skeys = jax.random.split(c["k_sample"], w)
            c["sampled"] = jax.vmap(
                lambda k, t: dts_mod.sample_peers(k, t, cfg.num_sampled)
            )(skeys, theta)
        else:
            theta = c["eff_adj"] / jnp.maximum(
                c["eff_adj"].sum(1, keepdims=True), 1)
            c["sampled"] = c["eff_adj"]    # listen to every live peer
        c["theta"] = theta

    def stage_transport(c):
        """reads sampled, eff_adj, params, pstate.wire_err, k_wire; writes
        P, agg, wire_err — the wire stack (fp32/bf16/int8 + EF21) over the
        in_jit backends or the cross-pod ppermute ring, or a robust rule."""
        pstate = c["pstate"]
        mask = (c["sampled"] & c["eff_adj"]) | jnp.eye(w, dtype=bool)
        c["mask"] = mask
        if robust:
            c["agg"] = robust_mix(cfg.aggregation, mask, c["params"],
                                  trim=cfg.robust_trim)
            c["P"] = mask / mask.sum(axis=1, keepdims=True)
            c["wire_err"] = pstate.wire_err
            return
        P = dynamic_mixing_matrix(c["sampled"], c["eff_adj"], sizes_j,
                                  cfg.aggregation)
        c["P"] = P
        if use_ef:
            c["agg"], c["wire_err"] = transport.mix(
                P, c["params"], residual=pstate.wire_err, key=c["k_wire"],
                round_=pstate.round)
        else:
            c["agg"] = transport.mix(P, c["params"], key=c["k_wire"],
                                     round_=pstate.round)
            c["wire_err"] = pstate.wire_err

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
        """reads losses, sampled, P, theta, out, params, att_active,
        start_params, pstate.{conf, last_loss} (+ pstate.sketch on
        "corr"/"all"); writes conf (+ sketch: the rotated ring buffer).
        The same fused loss/geometry/correlation signal as the simulation
        engines. The observable: with ``start_params`` it is each pod's
        TRUE local-train delta — the post-attack send (poison for active
        attackers, the trained params peers actually consume otherwise)
        minus the params the pod departed from — exact parity with the
        sim engines' ``trained − start``; without it, the legacy round
        displacement ``out − params``."""
        pstate = c["pstate"]
        damaged = c.get("damaged")
        if damaged is None:
            damaged = jnp.zeros((w,), bool)
        loss_trust = jnp.where(damaged, dts_mod.DAMAGE_PENALTY,
                               c["losses"] - pstate.last_loss)
        c["sketch"] = pstate.sketch
        if channels:
            # same contract as the sim engines (geom_confidence_update):
            # score the FULL live neighborhood (centering over only the
            # ~2 sampled peers degenerates to a pairwise coin flip);
            # non-firing pods' phantom deltas are excluded like
            # stragglers
            if c["start_params"] is not None:
                sent = tree_select(c["att_active"], c["out"], c["params"])
                deltas = dts_mod.flatten_stacked(sent) \
                    - dts_mod.flatten_stacked(c["start_params"])
            else:
                deltas = dts_mod.flatten_stacked(c["out"]) \
                    - dts_mod.flatten_stacked(c["params"])
            gmask = c["eff_adj"] & c["fire"][None, :] \
                if scenario is not None else c["eff_adj"]
            if corr:
                if pstate.sketch is None:
                    raise ValueError(
                        f"dts_signal={cfg.dts_signal!r} needs the sketch "
                        f"ring buffer — build the pod state with "
                        f"init_pod_state(..., sketch=sketch_shape(cfg))")
                c["sketch"] = dts_mod.update_sketch(pstate.sketch, deltas,
                                                    seed=cfg.seed)
            c["conf"] = dts_mod.geom_confidence_update(
                cfg.dts_signal, cfg.dts_geom_weight, pstate.conf,
                c["sampled"], c["P"], loss_trust, damaged, deltas,
                gmask, c["theta"], sketch=c["sketch"],
                lam_corr=cfg.dts_corr_weight)
        else:
            c["conf"] = pstate.conf - c["sampled"] * c["P"] \
                * loss_trust[:, None]

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
             "start_params": start_params}
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
    """``sketch``: the (R, S) dims from ``sketch_shape(cfg)`` when the
    correlation channel is on, else None. Stamps start at −1: an empty
    ring slot can never stamp-match, so fresh users carry zero correlation
    evidence by construction."""
    keys = jax.random.split(key, enrolled + 1)
    params = jax.vmap(task.init)(keys[:enrolled])
    return CrossDeviceState(
        params=params,
        backup=jax.tree.map(jnp.copy, params),
        conf=jnp.zeros((enrolled, enrolled)),
        best_loss=jnp.full((enrolled,), jnp.inf),
        last_loss=jnp.zeros((enrolled,)),
        key=keys[-1],
        epoch=jnp.zeros((enrolled,), jnp.int32),
        last_part=jnp.zeros((enrolled,), jnp.int32),
        obs=jnp.zeros((enrolled,), jnp.int32),
        wire_err=jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        if wire_error else None,
        sketch=jnp.zeros((enrolled,) + tuple(sketch), jnp.float32)
        if sketch else None,
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
    round's k-member cohort out of the enrolled population, the dense
    stages the engine already runs execute on the k-block, and
    ``scatter_merge`` writes the survivors' state back — one scannable
    body, so ``drive_epochs`` fuses a whole eval window of gather →
    superstep → scatter into a single XLA dispatch exactly like the dense
    path.

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
    n = int(world.enrolled)
    k = int(world.sample_k)
    ltrain = local_train_fn(task, train, cfg.local_epochs,
                            dp_clip=cfg.dp_clip, dp_sigma=cfg.dp_sigma)
    channels = resolve_dts_signal(cfg)
    corr = "corr" in channels
    decay = float(cfg.dts_conf_decay)
    max_staleness = int(cfg.max_staleness)
    k_min = int(world.k_min)
    sizes_j = jnp.asarray(np.asarray(sizes, np.float32))

    from repro.scenarios import attacks as attacks_mod
    from repro.scenarios.compile import ATTACK_CODE
    from repro.scenarios.robust_agg import ROBUST_RULES

    if world.epochs <= 0:
        raise ValueError("cross-device world compiled for 0 rounds")
    if "label_flip" in world.kinds_present and num_classes <= 0:
        raise ValueError("label_flip cross-device world needs "
                         "num_classes > 0")
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
    stochastic = transport.stochastic
    dp_update = uses_update_dp(cfg)
    masked_geom = cfg.secagg is not None \
        and cfg.secagg_mode == "masked_geom"

    if telemetry is not None:
        from repro.telemetry.spec import cross_device_specs
        telemetry.declare(*cross_device_specs(k, use_ef=use_ef))
        tm_specs = telemetry.specs

    part_ix = jnp.asarray(world.part_ix)        # [T, k] int32, per-round
    filled_t = jnp.asarray(world.filled)        # [T, k] bool
    survive_t = jnp.asarray(world.survive)      # [T, k] bool
    complete_t = jnp.asarray(world.complete)    # [T, k] bool
    adj_t = jnp.asarray(world.adj)              # [T, k, k] bool
    att_kind_u = jnp.asarray(world.attack_kind)     # [N] int32
    att_scale_u = jnp.asarray(world.attack_scale)   # [N] float32
    eye_k = jnp.eye(k, dtype=bool)

    # ---- stages -----------------------------------------------------------

    def stage_participation(c):
        """reads epoch (the global round t), state.*, data; writes ix (the
        cohort), active/fire (dropout ∧ straggler ∧ filled), eff_adj (the
        survivor-masked cohort topology), the gathered g_* k-blocks of
        every population buffer (confidence rows decayed by the time since
        their user last fired), and the gathered data shards / attack
        assignment. The gather: one x[ix] per buffer — XLA fuses it into
        the scan body, no extra dispatch."""
        state, t = c["state"], c["epoch"]
        ix = part_ix[t]
        c["ix"] = ix
        active = filled_t[t] & survive_t[t]
        c["active"] = active
        c["fire"] = active & complete_t[t]
        c["g_params"] = jax.tree.map(lambda x: x[ix], state.params)
        c["g_backup"] = jax.tree.map(lambda x: x[ix], state.backup)
        c["g_wire_err"] = jax.tree.map(lambda x: x[ix], state.wire_err) \
            if use_ef else None
        c["g_last_part"] = state.last_part[ix]
        c["g_conf_raw"] = state.conf[ix]                 # [k, N]
        rows = c["g_conf_raw"]
        if decay < 1.0:
            gap = jnp.maximum(t - c["g_last_part"], 0).astype(jnp.float32)
            rows = rows * jnp.power(jnp.float32(decay), gap)[:, None]
        c["g_conf_rows"] = rows
        c["conf"] = rows[:, ix]                          # the [k, k] block
        c["g_best"] = state.best_loss[ix]
        c["g_last"] = state.last_loss[ix]
        c["g_obs"] = state.obs[ix]
        if corr:
            c["g_sketch"] = state.sketch[ix]
            c["g_stamp"] = state.sketch_round[ix]
        data = c["data"]
        c["g_x"] = data["x"][ix]
        c["g_y"] = data["y"][ix]
        c["g_mask"] = data["mask"][ix]
        c["g_sizes"] = sizes_j[ix]
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

    def stage_split_keys(c):
        """reads state.key; writes key, k_sample, k_train, k_noise
        (+ build-time gated k_wire / k_dp) — the same frozen split
        layout as the dense round (``split_round_keys``)."""
        c.update(split_round_keys(c["state"].key, stochastic, dp_update))

    def stage_peer_sample(c):
        """reads conf (the decayed k-block), eff_adj, k_sample; writes
        theta and sampled over the cohort."""
        if cfg.use_dts:
            theta = dts_mod.sample_weights(c["conf"], c["eff_adj"],
                                           cfg.crelu_slope)
        else:
            theta = c["eff_adj"] / jnp.maximum(
                c["eff_adj"].sum(1, keepdims=True), 1)
        c["theta"] = theta
        skeys = jax.random.split(c["k_sample"], k)
        c["sampled"] = jax.vmap(
            lambda kk, th: dts_mod.sample_peers(kk, th, cfg.num_sampled)
        )(skeys, theta)

    def stage_transport(c):
        """reads sampled, eff_adj, g_params, g_wire_err; writes P, agg,
        wire_err. The mixing matrix renormalizes over SURVIVORS (dropped
        slots left eff_adj in participation) and rows with < k_min
        surviving sampled peers degrade to the identity self-loop."""
        P = dynamic_mixing_matrix(c["sampled"], c["eff_adj"], c["g_sizes"],
                                  cfg.aggregation)
        if k_min > 1:
            npeers = (c["sampled"] & c["eff_adj"] & ~eye_k).sum(axis=1)
            P = jnp.where((npeers >= k_min)[:, None], P,
                          eye_k.astype(P.dtype))
        c["P"] = P
        if telemetry is not None:
            from repro.telemetry.spec import stacked_payload_bytes
            live = (c["sampled"] & c["eff_adj"] & ~eye_k).sum()
            telemetry.emit(c, "edges", live)
            telemetry.emit(c, "wire_bytes", live.astype(jnp.float32) *
                           stacked_payload_bytes(c["g_params"],
                                                 transport.wire))
        round_ = 0 if c["epoch"] is None else c["epoch"]
        if use_ef:
            c["agg"], c["wire_err"] = transport.mix(
                P, c["g_params"], residual=c["g_wire_err"],
                key=c["k_wire"], round_=round_)
            if telemetry is not None:
                telemetry.emit(c, "ef_norm", jnp.linalg.norm(
                    dts_mod.flatten_stacked(c["wire_err"]), axis=1))
        else:
            c["agg"] = transport.mix(P, c["g_params"], key=c["k_wire"],
                                     round_=round_)
            c["wire_err"] = c["g_wire_err"]

    def stage_damage_check(c):
        """reads agg, g_y, g_x, g_mask, g_best, g_backup, att_kind,
        att_on; writes y_data, loss_agg, damaged, start — identical to
        the dense round, on the gathered cohort block."""
        y = c["g_y"]
        if "label_flip" in world.kinds_present:
            lf = (c["att_kind"] == ATTACK_CODE["label_flip"]) & c["att_on"]
            y = attacks_mod.flip_labels(y, lf, num_classes)
        c["y_data"] = y
        c["loss_agg"] = jax.vmap(task.loss)(c["agg"], c["g_x"], y,
                                            c["g_mask"])
        if cfg.time_machine:
            c["damaged"] = dts_mod.is_damaged(c["loss_agg"], c["g_best"])
            c["start"] = tree_select(c["damaged"], c["g_backup"], c["agg"])
        else:
            c["damaged"] = jnp.zeros_like(c["loss_agg"], bool)
            c["start"] = c["agg"]
        if telemetry is not None:
            telemetry.emit(c, "loss_agg", c["loss_agg"])

    def stage_local_train(c):
        """reads start, g_x, y_data, g_mask, k_train; writes trained,
        train_loss — the dense stage body vmapped over the k cohort."""
        tkeys = jax.random.split(c["k_train"], k)
        c["trained"], c["train_loss"] = jax.vmap(
            lambda kk, p, x, y, m: ltrain(kk, p, x, y, m)
        )(tkeys, c["start"], c["g_x"], c["y_data"], c["g_mask"])
        if telemetry is not None:
            telemetry.emit(c, "train_loss", c["train_loss"])

    def stage_dp_noise(c):
        """reads trained, start, k_dp; writes trained — the dense
        round's per-round update-DP stage on the cohort block (see
        ``apply_update_dp``; build-time gated on ``uses_update_dp``)."""
        c["trained"] = apply_update_dp(cfg, c["k_dp"], c["start"],
                                       c["trained"])

    def stage_attack_inject(c):
        """reads trained, agg, att_kind, att_scale, att_on, theta,
        k_noise; writes trained. Attackers attack whenever they
        participate — attack_on is the participation mask itself,
        gathered from the enrolled-population assignment (29% of
        ENROLLED means ~29% of every cohort in expectation)."""
        if world.kinds_present:
            c["trained"] = attacks_mod.poison_sends(
                c["k_noise"], world.kinds_present, c["att_kind"],
                c["att_scale"], c["att_on"], c["agg"], c["trained"],
                theta=c["theta"] if cfg.use_dts else None)

    def stage_trust_update(c):
        """reads conf, sampled, P, theta, eff_adj, fire, loss_agg,
        damaged, g_last, g_best, g_backup, trained, start (+ g_sketch,
        g_stamp on the corr channel); writes conf_new, backup,
        best_loss, last_loss (+ sketch, stamp). The dense trust_update
        on the cohort block, with the correlation channel swapped for
        its sparse-observation variant: ring buffers rotate WITH a
        global-round stamp, correlation is scored over stamp-matched
        slot pairs only, and pairs with < cfg.dts_min_obs common
        observations are excluded from both the suspicion and its
        median+MAD baseline."""
        loss_trust = jnp.where(c["damaged"], dts_mod.DAMAGE_PENALTY,
                               c["loss_agg"] - c["g_last"])
        if channels and masked_geom:
            # aggregate-only visibility on the cohort block: pooled
            # signal only, no per-peer geometry, and the sketch ring
            # never rotates (a receiver cannot sketch deltas it never
            # saw) — stamps pass through unchanged
            deltas = dts_mod.flatten_stacked(c["trained"]) \
                - dts_mod.flatten_stacked(c["start"])
            gmask = c["eff_adj"] & c["fire"][None, :]
            mg = dts_mod.masked_geom_trust(deltas, c["P"], gmask)
            if corr:
                c["sketch"], c["stamp"] = c["g_sketch"], c["g_stamp"]
            c["conf_new"] = c["conf"] - c["sampled"] * c["P"] \
                * (loss_trust + cfg.dts_geom_weight * mg)[:, None]
        elif channels:
            deltas = dts_mod.flatten_stacked(c["trained"]) \
                - dts_mod.flatten_stacked(c["start"])
            gmask = c["eff_adj"] & c["fire"][None, :]
            gs = dts_mod.geom_scores(deltas, gmask, weights=c["theta"]) \
                if "geom" in channels else None
            cs = None
            if corr:
                c["sketch"] = dts_mod.update_sketch(c["g_sketch"], deltas,
                                                    seed=cfg.seed)
                c["stamp"] = jnp.concatenate(
                    [c["g_stamp"][:, 1:],
                     jnp.full((k, 1), c["epoch"], jnp.int32)], axis=1)
                cmat, valid = dts_mod.stamped_correlation(
                    c["sketch"], c["stamp"], min_obs=cfg.dts_min_obs)
                cs = dts_mod.correlation_suspicion(
                    cmat, gmask, weights=c["theta"], valid=valid)
            signal = dts_mod.fused_trust_signal(
                cfg.dts_signal, loss_trust, gs, c["damaged"],
                cfg.dts_geom_weight, corr=cs,
                lam_corr=cfg.dts_corr_weight)
            c["conf_new"] = c["conf"] - c["sampled"] * c["P"] * signal
        else:
            c["conf_new"] = c["conf"] - c["sampled"] * c["P"] \
                * loss_trust[:, None]
        if telemetry is not None:
            telemetry.emit(c, "loss_trust", loss_trust)
            telemetry.emit(c, "conf_in", c["conf_new"].mean(axis=0))
            telemetry.emit(c, "update_norm", jnp.linalg.norm(
                dts_mod.flatten_stacked(c["trained"])
                - dts_mod.flatten_stacked(c["start"]), axis=1))

        improved = (c["loss_agg"] < c["g_best"]) & ~c["damaged"]
        c["backup"] = tree_select(improved | c["damaged"], c["trained"],
                                  c["g_backup"])
        c["best_loss"] = jnp.where(improved, c["loss_agg"], c["g_best"])
        c["last_loss"] = jnp.where(c["damaged"], c["g_last"],
                                   c["loss_agg"])

    def stage_scatter_merge(c):
        """reads fire + every updated cohort buffer; writes next (the
        population state). Fire-gated: non-firing cohort members and
        absent users scatter back their ORIGINAL (undecayed) rows, so
        every carried buffer — trust, EF residuals, sketch history,
        stamps — is bit-unchanged across rounds a user misses. Cohort
        indices are distinct within a round, so the row scatters never
        conflict."""
        state, t, ix, fire = c["state"], c["epoch"], c["ix"], c["fire"]

        def scat_tree(full, new_rows, old_rows):
            sel = tree_select(fire, new_rows, old_rows)
            return jax.tree.map(lambda f, s: f.at[ix].set(s), full, sel)

        params = scat_tree(state.params, c["trained"], c["g_params"])
        backup = scat_tree(state.backup, c["backup"], c["g_backup"])
        wire_err = scat_tree(state.wire_err, c["wire_err"],
                             c["g_wire_err"]) if use_ef else state.wire_err
        rows_new = c["g_conf_rows"].at[:, ix].set(c["conf_new"])
        conf = state.conf.at[ix].set(
            jnp.where(fire[:, None], rows_new, c["g_conf_raw"]))
        if corr:
            sketch = state.sketch.at[ix].set(
                jnp.where(fire[:, None, None], c["sketch"],
                          c["g_sketch"]))
            stamps = state.sketch_round.at[ix].set(
                jnp.where(fire[:, None], c["stamp"], c["g_stamp"]))
        else:
            sketch, stamps = state.sketch, state.sketch_round
        c["next"] = CrossDeviceState(
            params=params, backup=backup, conf=conf,
            best_loss=state.best_loss.at[ix].set(
                jnp.where(fire, c["best_loss"], c["g_best"])),
            last_loss=state.last_loss.at[ix].set(
                jnp.where(fire, c["last_loss"], c["g_last"])),
            key=c["key"],
            epoch=state.epoch.at[ix].add(fire.astype(jnp.int32)),
            last_part=state.last_part.at[ix].set(
                jnp.where(fire, t, c["g_last_part"])),
            obs=state.obs.at[ix].set(
                jnp.where(fire, c["g_obs"] + 1, c["g_obs"])),
            wire_err=wire_err, sketch=sketch, sketch_round=stamps)

    stages = (
        ("participation", stage_participation),
        ("split_keys", stage_split_keys),
        ("peer_sample", stage_peer_sample),
        ("transport", stage_transport),
        ("damage_check", stage_damage_check),
        ("local_train", stage_local_train),
    ) + ((("dp_noise", stage_dp_noise),) if dp_update else ()) + (
        ("attack_inject", stage_attack_inject),
        ("trust_update", stage_trust_update),
        ("scatter_merge", stage_scatter_merge),
    )

    def round(state: CrossDeviceState, data, epoch=None):
        c = {"state": state, "data": data, "epoch": epoch}
        run_pipeline(stages, c)
        nxt = constrain_worker_rows(c["next"], shard, n)
        if telemetry is None:
            return nxt
        return nxt, telemetry.collect(c, tm_specs)

    round.stages = stages
    round.cohort = (n, k)
    round.telemetry = telemetry
    return round
