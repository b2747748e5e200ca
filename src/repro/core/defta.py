"""Synchronous DeFTA engine (Algorithm 1) — simulation mode.

All W workers are carried as stacked pytrees (leading axis W) and advanced
by one jitted super-step per global epoch:

    sample peers (DTS θ) → aggregate (outdegree-corrected P) → time-machine
    check → local SGD epochs → DTS confidence update → backup

Since the unified round-program refactor, the round body itself lives in
``repro.core.engine`` as a stage pipeline (``build_defta_round``) and the
superstep loop is the shared chunked-scan driver (``drive_epochs``); this
module is the sync *mode*: stage selection, scenario resolution and the
end-to-end ``run_defta`` entry point. Attack injection is pluggable
(``repro.scenarios.attacks``): by default malicious workers broadcast
``aggregate + noise`` (the paper's attack model); a compiled ``scenario``
replays an arbitrary event timeline — churn, link failures, partitions,
stragglers, time-varying topologies and any mix of the attack zoo — as
per-epoch device arrays indexed inside the scanned superstep, so scenarios
cost ZERO extra dispatches.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import DeFTAConfig, TrainConfig
from repro.core.engine import (DeFTAState, build_defta_round, drive_epochs,
                               init_state, local_train_fn)
from repro.core.tasks import Task
from repro.core.topology import make_topology
from repro.scenarios.attacks import tree_select  # noqa: F401 (re-export:
                                                 # async_defta/fedavg/tests
                                                 # import it from here)
from repro.telemetry.trace import span

__all__ = ["DeFTAState", "build_round", "build_round_fn", "evaluate",
           "global_model", "init_state", "local_train_fn",
           "resolve_scenario", "run_defta", "tree_select"]


def build_round_fn(task: Task, cfg: DeFTAConfig, train: TrainConfig,
                   adj: np.ndarray, sizes: np.ndarray,
                   malicious: np.ndarray, *,
                   gossip_backend: str = "einsum",
                   noise_scale: float = 200.0,
                   scenario=None, num_classes: int = 0,
                   telemetry=None, shard=None):
    """Returns an UN-jitted round(state, data, epoch=None) -> state body —
    scannable, so drivers can fuse many rounds into one XLA dispatch (and
    jittable as-is for single-round use; see ``build_round``). The body is
    the engine's stage pipeline: split_keys → scenario_view → peer_sample →
    transport → damage_check → local_train → attack_inject → trust_update →
    finalize/fire_merge (``repro.core.engine.build_defta_round``).
    ``telemetry``: a ``repro.telemetry.Telemetry`` registry — when given
    the round also returns a per-round probe frame (see the engine)."""
    return build_defta_round(task, cfg, train, adj, sizes, malicious,
                             gossip_backend=gossip_backend,
                             noise_scale=noise_scale, scenario=scenario,
                             num_classes=num_classes, telemetry=telemetry,
                             shard=shard)


def build_round(*args, **kwargs):
    """Returns a jitted round(state, data) -> state super-step."""
    return jax.jit(build_round_fn(*args, **kwargs))


def evaluate(task: Task, state: DeFTAState, test_x, test_y,
             malicious: np.ndarray):
    """Mean/std test accuracy across vanilla (non-malicious) workers."""
    with span("evaluate"):
        with span("evaluate.copy"):
            tx, ty = jnp.asarray(test_x), jnp.asarray(test_y)
        with span("evaluate.accuracy"):
            accs = jax.vmap(lambda p: task.accuracy(
                p, tx, ty, jnp.ones(tx.shape[0])))(state.params)
        with span("evaluate.fetch"):
            accs = np.asarray(accs)[~malicious]
    return float(accs.mean()), float(accs.std()), accs


def resolve_scenario(scenario, cfg: DeFTAConfig, epochs: int):
    """Accept a ScenarioSpec (compiled here over ``epochs``), an
    already-compiled CompiledScenario, or a preset name string."""
    from repro.scenarios.compile import CompiledScenario, compile_scenario
    from repro.scenarios.spec import ScenarioSpec, get_scenario

    if isinstance(scenario, str):
        scenario = get_scenario(scenario, cfg.num_workers)
    if isinstance(scenario, ScenarioSpec):
        scenario = compile_scenario(scenario, cfg.num_workers, epochs)
    if not isinstance(scenario, CompiledScenario):
        raise TypeError(f"scenario must be a ScenarioSpec, "
                        f"CompiledScenario or preset name, got "
                        f"{type(scenario).__name__}")
    if scenario.num_vanilla != cfg.num_workers:
        raise ValueError(f"scenario compiled for {scenario.num_vanilla} "
                         f"vanilla workers, cfg has {cfg.num_workers}")
    if scenario.epochs < epochs:
        # the topology state clamps past the horizon fine, but the
        # per-epoch fire/attack_on schedules would freeze at whatever the
        # last epoch's random draw happened to be — a straggler could be
        # stuck never firing. Precompiled scenarios must cover the run.
        raise ValueError(f"scenario horizon {scenario.epochs} is shorter "
                         f"than the run ({epochs} epochs) — recompile "
                         f"with compile_scenario(spec, W, {epochs})")
    return scenario


def _pad_workers(data, sizes, extra: int):
    """Pad stacked per-worker data/sizes with ``extra`` attacker slots
    (unused training slots — only what attackers *send* matters)."""
    sizes = np.concatenate([np.asarray(sizes),
                            np.full(extra, int(np.mean(sizes)))])
    if extra:
        pad = lambda a: np.concatenate(
            [a, np.repeat(a[-1:], extra, 0)], 0)
        data = {**data, "x": pad(data["x"]), "y": pad(data["y"]),
                "mask": pad(data["mask"])}
    return data, sizes


def run_defta(key, task: Task, cfg: DeFTAConfig, train: TrainConfig, data,
              *, epochs: int, num_malicious: int = 0, scenario=None,
              gossip_backend: str = "einsum", eval_every: int = 0,
              test_x=None, test_y=None, superstep: bool = True,
              stats: Optional[dict] = None, ledger=None,
              shards: Optional[int] = None):
    """End-to-end driver. Malicious workers are appended after the vanilla
    ones (paper §4.3: normal workers fixed, attackers newly joined).

    ``scenario`` (a ``repro.scenarios`` ScenarioSpec / CompiledScenario /
    preset name) replaces ``num_malicious`` with a full event timeline:
    its attackers are appended the same way, and churn/link/straggler
    events replay inside the scanned supersteps — same dispatch count as a
    static run.

    With ``superstep`` (default) epochs advance inside ``jax.lax.scan``
    chunks bounded by eval points (the engine's ``drive_epochs`` driver): a
    run is ceil(epochs / eval_every) XLA dispatches (one, if eval_every=0)
    instead of one per epoch, and the state buffers are donated across
    chunks so params/backup are not double-buffered between dispatches.
    ``superstep=False`` keeps the per-epoch dispatch loop (the reference
    the fused path is tested against). Pass ``stats={}`` to get
    ``{"dispatches": n, ...}`` back.

    ``ledger``: a ``repro.telemetry.RunLedger``. When given, the round is
    built with a Telemetry registry — per-round probe frames (trust, wire
    bytes, fire masks, losses …) ride the scan supersteps as stacked ys
    and flush into the ledger (and its JSONL sink) at eval boundaries,
    with the SAME dispatch count; the traced state update is bit-identical
    to a ledger-less run. Without it nothing extra is traced.

    ``shards``: shard the worker axis across that many local devices (a
    1-D ``repro.sharding.worker_mesh``): per-device worker blocks carry
    their own params/confidence/EF-residual/sketch rows, the transport
    becomes the local-block-CSR + cross-shard-ring mix, and the donated
    superstep buffers stay row-sharded — same dispatch count, W is a mesh
    dimension instead of a memory ceiling. W need not divide ``shards``.
    """
    num_classes = 0
    if scenario is not None:
        if num_malicious:
            raise ValueError("pass attackers via the scenario, not "
                             "num_malicious, when a scenario is given")
        scenario = resolve_scenario(scenario, cfg, epochs)
        w = scenario.num_workers
        malicious = scenario.malicious.copy()
        num_classes = int(np.max(data["y"])) + 1
    else:
        w = cfg.num_workers + num_malicious
        malicious = np.zeros(w, bool)
        malicious[cfg.num_workers:] = True
    adj = make_topology(cfg.topology, w, cfg.avg_peers, cfg.seed)
    # attacker slots need (unused) data slots — pad stacked data
    data, sizes = _pad_workers(data, data["sizes"], w - cfg.num_workers)

    from repro.core.engine import sketch_shape
    from repro.core.gossip import uses_error_feedback
    state = init_state(key, task, w, wire_error=uses_error_feedback(cfg),
                       sketch=sketch_shape(cfg))
    telemetry = None
    if ledger is not None:
        from repro.telemetry import Telemetry
        telemetry = Telemetry()
    shard = None
    if shards is not None and shards > 1:
        from repro.sharding import WorkerShards, worker_mesh
        shard = WorkerShards(mesh=worker_mesh(shards))
    rnd_fn = build_round_fn(task, cfg, train, adj, sizes, malicious,
                            gossip_backend=gossip_backend,
                            scenario=scenario, num_classes=num_classes,
                            telemetry=telemetry, shard=shard)
    jdata = {k: jnp.asarray(v) for k, v in data.items()
             if k in ("x", "y", "mask")}

    eval_fn = None
    if test_x is not None:
        def eval_fn(st, done):
            m, s, _ = evaluate(task, st, test_x, test_y, malicious)
            return (done, m, s)
    state, history = drive_epochs(rnd_fn, state, jdata, epochs,
                                  eval_every=eval_every, eval_fn=eval_fn,
                                  superstep=superstep, stats=stats,
                                  ledger=ledger, shard=shard,
                                  shard_rows=w)
    return state, adj, malicious, history


def global_model(state: DeFTAState, sizes, sample: int = 0, key=None):
    """Paper §5.3: obtain the stable global model from a decentralized
    cluster — connect to (a sample of) workers and average their models
    with dataset-size weights  Σ_k (n_k / Σn) w_k."""
    sizes = jnp.asarray(np.asarray(sizes, np.float32))
    w = sizes.shape[0]
    if sample and key is not None:
        idx = jax.random.choice(key, w, (min(sample, w),), replace=False)
        mask = jnp.zeros((w,)).at[idx].set(1.0)
    else:
        mask = jnp.ones((w,))
    weights = mask * sizes
    weights = weights / weights.sum()
    return jax.tree.map(
        lambda x: jnp.einsum("i,i...->...", weights.astype(x.dtype), x),
        state.params)
