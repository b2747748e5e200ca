"""Production training driver.

Three modes:
* single-pod:  standard data+tensor-parallel training of one model.
* multi-pod (``--fl``): DeFTA across pods — each pod is a federated worker
  with its own model replica and data stream; every ``--gossip-every``
  steps the pods run one gossip round of the unified engine's pod
  pipeline (``core.engine.build_pod_round``): scenario replay → DTS peer
  sampling (``--pod-dts``) → the full wire stack (``--gossip-wire``
  fp32/bf16/int8 + EF21) over the ``--transport`` of choice (``ppermute``
  = the offset-skipping, nnz-row-selected collective_permute ring;
  ``in_jit`` = the einsum/pallas/sparse/quant backends) → attack
  injection → trust update. ``--scenario NAME`` replays a compiled
  adversarial timeline over the GOSSIP ROUND axis and ``--aggregation``
  selects defta/defl/uniform or the Byzantine-robust baselines — the
  same knobs the simulation engines take.
* scenario replay (``--scenario NAME`` without ``--fl``): run the
  simulation engines through a named adversarial scenario (churn + attack
  zoo + faults, compiled to device arrays — see ``repro/scenarios``).
  Presets: ``paper_noise[@K]``, ``churn_signflip``, ``storm``.
  ``--async-ticks`` routes it through ``run_async_defta`` instead of
  ``run_defta``; ``--assert-acc X`` exits nonzero if final vanilla
  accuracy < X (the CI smoke hook).
* cross-device (``--cross-device``): churn-as-default participation — an
  enrolled population of ``--enrolled`` users, ``--sample-k`` gathered
  per round under ``--cd-availability`` with default-on mid-round dropout
  (``--cd-dropout``) and straggler timeouts (``--cd-straggle``);
  ``--cd-attacks kind:frac[,kind:frac]`` assigns attackers as a fraction
  of the ENROLLED population. The run exits 1 if the dispatch count ever
  exceeds ceil(rounds / eval_every) — the gather/scatter-fused superstep
  contract the CI smoke gates.

On a CPU use tiny configs (e.g. --arch paper-small --debug-mesh) — the
full meshes are exercised by dryrun.py. On TPU chips, ``--fl`` builds a
``pod=N, data=1, model=1`` mesh of the chips present when there is one per
pod; ``chip_smoke.py`` drives this module that way.
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import numpy as np


def make_ledger(args, cfg, mode: str):
    """``--telemetry PATH`` → (RunLedger streaming to a JSONL sink with a
    run manifest first row, sink) — or (None, None) when the flag is off.
    The ledger rides the engine's scan supersteps (zero extra dispatches;
    see ``repro.telemetry``)."""
    if not getattr(args, "telemetry", ""):
        return None, None
    import dataclasses
    import sys

    from repro.telemetry import JsonlSink, RunLedger, run_manifest

    sink = JsonlSink(args.telemetry)
    meta = run_manifest(config={"mode": mode,
                                **dataclasses.asdict(cfg)},
                        seed=cfg.seed, argv=sys.argv)
    return RunLedger(sink=sink, meta=meta), sink


def start_profile(args):
    """``--profile DIR`` → start a jax.profiler trace. A trace that cannot
    start raises: a run asked to profile does not go on without one."""
    if not getattr(args, "profile", ""):
        return False
    import jax

    jax.profiler.start_trace(args.profile)
    return True


def stop_profile(args, started: bool):
    if not started:
        return
    import jax

    jax.profiler.stop_trace()
    print(f"profile trace written to {args.profile}")


def run_scenario_sim(args) -> int:
    """--scenario: replay a named scenario through the DeFTA engines."""
    import jax

    from repro.config import DeFTAConfig, TrainConfig
    from repro.core.async_defta import run_async_defta
    from repro.core.defta import evaluate, resolve_scenario, run_defta
    from repro.core.tasks import mlp_task
    from repro.data.synthetic import federated_dataset

    # robust rules run PURE (no DTS, no time machine) — same contract as
    # table3_robustness.DEFENSES; crediting a classical baseline with
    # DeFTA's own rollback would inflate it (robust_agg.py docstring)
    robust = args.aggregation in ("trimmed_mean", "median", "krum")
    if args.dts_signal != "loss" and args.aggregation != "defta":
        # resolve_dts_signal gates the geometric channel on use_dts: a
        # non-defta aggregation never runs DTS, so the flag would be
        # silently inert — refuse rather than fake a defended run
        raise SystemExit(f"--dts-signal {args.dts_signal} needs DTS "
                         f"(--aggregation defta); aggregation="
                         f"{args.aggregation} never runs a trust update")
    cfg = DeFTAConfig(num_workers=args.sim_workers, avg_peers=4,
                      num_sampled=2, local_epochs=args.sim_local_epochs,
                      aggregation=args.aggregation,
                      use_dts=args.aggregation == "defta",
                      time_machine=not robust,
                      dts_signal=args.dts_signal,
                      gossip_dtype="float32" if robust
                      else args.gossip_wire,
                      gossip_error_feedback=not args.no_gossip_ef,
                      secagg="pairwise" if args.secagg and not robust
                      else None,
                      secagg_mode=args.secagg_mode,
                      dp_sigma=args.dp_sigma,
                      dp_update_clip=args.dp_update_clip)
    if args.secagg and robust:
        # make_transport would refuse anyway (robust rules inspect
        # plaintext models); drop to the same purity downgrade as the
        # wire so robust baselines stay runnable under a sweep script
        print(f"aggregation={args.aggregation}: secagg disabled "
              f"(robust rules need plaintext models)")
    if args.aggregation != "defta":
        print(f"aggregation={args.aggregation}: use_dts={cfg.use_dts} "
              f"time_machine={cfg.time_machine} (baseline purity)")
    train = TrainConfig(learning_rate=0.05, batch_size=32)
    data = federated_dataset("vector", cfg.num_workers,
                             np.random.default_rng(cfg.seed),
                             n_per_worker=120, alpha=0.5)
    task = mlp_task(32, 10)
    horizon = args.async_ticks or args.sim_epochs
    compiled = resolve_scenario(args.scenario, cfg, horizon)
    print(f"scenario {compiled.spec.name}: {compiled.summary()}")

    key = jax.random.PRNGKey(cfg.seed)
    stats: dict = {}
    shards = args.shard_workers if args.shard_workers > 1 else None
    if shards:
        print(f"worker axis sharded over {shards} devices "
              f"({len(jax.devices())} visible)")
    ledger, sink = make_ledger(args, cfg, "async" if args.async_ticks
                               else "scenario")
    profiling = start_profile(args)
    t0 = time.time()
    if args.async_ticks:
        st, adj, mal, _ = run_async_defta(
            key, task, cfg, train, data, ticks=args.async_ticks,
            scenario=compiled, target_epochs=args.sim_epochs, stats=stats,
            ledger=ledger, shards=shards)
    else:
        st, adj, mal, hist = run_defta(
            key, task, cfg, train, data, epochs=args.sim_epochs,
            scenario=compiled, eval_every=max(args.sim_epochs // 4, 1),
            test_x=data["test_x"], test_y=data["test_y"], stats=stats,
            ledger=ledger, shards=shards)
        for e, m, s in hist:
            print(f"  epoch {e:4d}: vanilla acc {m:.3f} ± {s:.3f}")
    stop_profile(args, profiling)
    if sink is not None:
        sink.close()
        print(f"telemetry ledger: {args.telemetry} "
              f"({ledger.rounds_done} rounds, "
              f"{len(ledger.names())} probes, "
              f"wall {ledger.wall_s:.2f}s)")
    m, s, _ = evaluate(task, st, data["test_x"], data["test_y"], mal)
    print(f"final vanilla acc {m:.3f} ± {s:.3f} "
          f"({stats.get('dispatches', '?')} dispatches, "
          f"{time.time() - t0:.1f}s, epochs={np.asarray(st.epoch).tolist()})")
    if (shards or args.secagg) and not args.async_ticks:
        budget = -(-args.sim_epochs // max(args.sim_epochs // 4, 1))
        if stats.get("dispatches", 0) > budget:
            print(f"FAIL: {stats['dispatches']} dispatches > "
                  f"ceil(epochs/eval_every) = {budget} — the "
                  f"{'sharded ' if shards else 'secagg '}round program "
                  f"broke the superstep fusion")
            return 1
    if args.assert_acc and m < args.assert_acc:
        print(f"FAIL: vanilla accuracy {m:.3f} < --assert-acc "
              f"{args.assert_acc}")
        return 1
    return 0


def parse_cd_attacks(text: str):
    """``"label_flip:0.15,alie:0.14"`` → ((kind, frac), ...)."""
    if not text:
        return ()
    out = []
    for part in text.split(","):
        kind, _, frac = part.partition(":")
        out.append((kind.strip(), float(frac)))
    return tuple(out)


def run_cross_device_sim(args) -> int:
    """--cross-device: an enrolled population with k sampled per round."""
    import jax

    from repro.config import DeFTAConfig, TrainConfig
    from repro.core.cross_device import (evaluate_probe, probe_indices,
                                         resolve_world, run_cross_device)
    from repro.core.tasks import mlp_task
    from repro.data.synthetic import federated_dataset
    from repro.scenarios.cross_device import CrossDeviceSpec

    cfg = DeFTAConfig(num_workers=args.enrolled, avg_peers=4,
                      num_sampled=2, local_epochs=args.sim_local_epochs,
                      dts_signal=args.dts_signal,
                      dts_conf_decay=args.cd_conf_decay,
                      max_staleness=args.max_staleness,
                      gossip_dtype=args.gossip_wire,
                      gossip_error_feedback=not args.no_gossip_ef,
                      secagg="pairwise" if args.secagg else None,
                      secagg_mode=args.secagg_mode,
                      dp_sigma=args.dp_sigma,
                      dp_update_clip=args.dp_update_clip)
    train = TrainConfig(learning_rate=0.05, batch_size=32)
    data = federated_dataset("vector", args.enrolled,
                             np.random.default_rng(cfg.seed),
                             n_per_worker=args.cd_shard_size, alpha=0.5)
    task = mlp_task(32, 10)
    spec = CrossDeviceSpec(
        enrolled=args.enrolled, sample_k=args.sample_k,
        availability=args.cd_availability, dropout=args.cd_dropout,
        straggle=args.cd_straggle,
        attacks=parse_cd_attacks(args.cd_attacks), seed=cfg.seed)
    world = resolve_world(spec, args.sim_epochs)
    print(f"cross-device world: {world.summary()}")

    eval_every = max(args.sim_epochs // 4, 1)
    budget = -(-args.sim_epochs // eval_every)
    stats: dict = {}
    shards = args.shard_workers if args.shard_workers > 1 else None
    if shards:
        print(f"enrolled axis sharded over {shards} devices "
              f"({len(jax.devices())} visible)")
    ledger, sink = make_ledger(args, cfg, "cross_device")
    profiling = start_profile(args)
    t0 = time.time()
    state, hist = run_cross_device(
        jax.random.PRNGKey(cfg.seed), task, cfg, train, data, world=world,
        epochs=args.sim_epochs, eval_every=eval_every,
        test_x=data["test_x"], test_y=data["test_y"], stats=stats,
        ledger=ledger, shards=shards)
    stop_profile(args, profiling)
    if sink is not None:
        sink.close()
        print(f"telemetry ledger: {args.telemetry} "
              f"({ledger.rounds_done} rounds, "
              f"{len(ledger.names())} probes, "
              f"wall {ledger.wall_s:.2f}s)")
    for e, m, s in hist:
        print(f"  round {e:4d}: honest probe acc {m:.3f} ± {s:.3f}")
    pix = probe_indices(world, 32, seed=cfg.seed)
    m, s = evaluate_probe(task, state, data["test_x"], data["test_y"], pix)
    mean_part = float(np.asarray(state.obs).mean())
    print(f"final honest probe acc {m:.3f} ± {s:.3f} "
          f"({stats.get('dispatches', '?')} dispatches, budget {budget}, "
          f"{time.time() - t0:.1f}s, mean participations/user "
          f"{mean_part:.1f})")
    if stats.get("dispatches", 0) > budget:
        print(f"FAIL: {stats['dispatches']} dispatches > "
              f"ceil(rounds/eval_every) = {budget} — the gather/scatter "
              f"superstep is no longer fused")
        return 1
    if args.assert_acc and m < args.assert_acc:
        print(f"FAIL: honest probe accuracy {m:.3f} < --assert-acc "
              f"{args.assert_acc}")
        return 1
    return 0


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="paper-small")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--optimizer", default="adam")
    ap.add_argument("--fl", action="store_true",
                    help="DeFTA-across-pods mode")
    ap.add_argument("--pods", type=int, default=2)
    ap.add_argument("--gossip-every", type=int, default=4)
    ap.add_argument("--gossip-wire", default="fp32",
                    choices=["fp32", "bf16", "int8"],
                    help="gossip payload precision (bf16/int8; the "
                         "~2x/~4x byte cut is realized on the multi-host "
                         "ppermute path — in-jit backends reproduce the "
                         "numerics)")
    ap.add_argument("--no-gossip-ef", action="store_true",
                    help="disable EF21 error feedback on lossy wires")
    ap.add_argument("--gossip-wire-round", default="nearest",
                    choices=["nearest", "stochastic"],
                    help="int8 wire rounding (stochastic = unbiased per "
                         "round; see core/gossip.quantize_rows_int8)")
    ap.add_argument("--transport", default="in_jit",
                    choices=["in_jit", "ppermute"],
                    help="--fl gossip transport: in_jit mix_pytree "
                         "backends, or the cross-pod ppermute ring "
                         "(offset-skipping + nnz row selection; realizes "
                         "the wire-format byte cut)")
    ap.add_argument("--pod-dts", action="store_true",
                    help="--fl: DTS peer sampling + trust reweighting "
                         "across pods (default: listen to all live peers)")
    ap.add_argument("--dts-signal", default="loss",
                    choices=["loss", "geom", "both", "corr", "all"],
                    help="DTS trust signal (core/dts.py): the paper's "
                         "loss delta, the update-geometry scores "
                         "(cosine-to-median / norm-ratio / "
                         "sign-agreement), the cross-round collusion-"
                         "correlation scores (sign-sketch clustering, "
                         "the anti-ALIE signal), or their fusions "
                         "(both = loss+geom, all = loss+geom+corr) — "
                         "applies to --scenario sim runs and to "
                         "--fl --pod-dts")
    ap.add_argument("--pod-time-machine", action="store_true",
                    help="--fl: pod time machine — held-out self-eval "
                         "between gossip rounds; a pod whose candidate "
                         "aggregate explodes on the held-out batch "
                         "restores its best-eval backup instead of "
                         "adopting the mix")
    ap.add_argument("--debug-mesh", action="store_true",
                    help="2x2(x pods) host-device mesh for CPU")
    ap.add_argument("--memory-report", action="store_true",
                    help="after the run, print the compiled memory "
                         "footprint (XLA memory_analysis) of the train "
                         "step and, with --fl, of the gossip round")
    ap.add_argument("--checkpoint-dir", default="")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-sized)")
    ap.add_argument("--scenario", default="",
                    help="replay a named adversarial scenario through the "
                         "simulation engines (paper_noise[@K], "
                         "churn_signflip, storm)")
    ap.add_argument("--sim-epochs", type=int, default=12)
    ap.add_argument("--sim-workers", type=int, default=8)
    ap.add_argument("--sim-local-epochs", type=int, default=3)
    ap.add_argument("--aggregation", default="defta",
                    choices=["defta", "defl", "uniform", "trimmed_mean",
                             "median", "krum"],
                    help="aggregation rule for --scenario runs (robust "
                         "rules are the Byzantine baselines)")
    ap.add_argument("--async-ticks", type=int, default=0,
                    help="route --scenario through run_async_defta for "
                         "this many ticks")
    ap.add_argument("--assert-acc", type=float, default=0.0,
                    help="exit 1 if the --scenario run's final vanilla "
                         "accuracy is below this (CI smoke)")
    ap.add_argument("--cross-device", action="store_true",
                    help="churn-as-default participation sim: sample "
                         "--sample-k of --enrolled users per round "
                         "(exits 1 on dispatch-parity violation)")
    ap.add_argument("--enrolled", type=int, default=10_000,
                    help="--cross-device enrolled population size")
    ap.add_argument("--sample-k", type=int, default=64,
                    help="--cross-device per-round cohort size")
    ap.add_argument("--cd-availability", type=float, default=0.7,
                    help="P(user reachable at round start)")
    ap.add_argument("--cd-dropout", type=float, default=0.05,
                    help="P(mid-round departure | selected) — the "
                         "slot's partial contribution is masked out of "
                         "the mixing row-normalization")
    ap.add_argument("--cd-straggle", type=float, default=0.10,
                    help="P(straggler timeout | survived) — peers "
                         "consume the slot but its own update misses "
                         "the merge")
    ap.add_argument("--cd-attacks", default="",
                    help="attack assignment over the ENROLLED "
                         "population: kind:frac[,kind:frac], e.g. "
                         "label_flip:0.15,alie:0.14")
    ap.add_argument("--cd-shard-size", type=int, default=48,
                    help="training examples per enrolled user")
    ap.add_argument("--cd-conf-decay", type=float, default=0.98,
                    help="per-round decay of an absent user's trust-"
                         "confidence row toward the uninformative "
                         "prior (1.0 = off)")
    ap.add_argument("--telemetry", nargs="?", const="run_ledger.jsonl",
                    default="", metavar="PATH",
                    help="stream a per-round JSONL run ledger (trust / "
                         "fire / wire-byte / loss probes riding the scan "
                         "supersteps — zero extra dispatches; see "
                         "docs/ARCHITECTURE.md 'Telemetry plane'). "
                         "Default path: run_ledger.jsonl")
    ap.add_argument("--profile", nargs="?", const="profile_trace",
                    default="", metavar="DIR",
                    help="dump a jax.profiler trace of the run to DIR — "
                         "every engine stage is wrapped in a named scope "
                         "so the trace viewer shows per-stage spans")
    ap.add_argument("--secagg", action="store_true",
                    help="pairwise secure-aggregation wire: payloads "
                         "cross every gossip transport one-time-padded "
                         "per directed edge in the wire format's integer "
                         "ring; the receiver unmasks before the weighted "
                         "sum, so aggregates are exact and int8/bf16+EF "
                         "compose untouched (docs/ARCHITECTURE.md "
                         "'Privacy wire'). Scenario runs exit 1 on "
                         "dispatch-parity violation")
    ap.add_argument("--secagg-mode", default="edge",
                    choices=["edge", "masked_geom"],
                    help="secagg trust fidelity: 'edge' keeps per-peer "
                         "DTS (receiver-side unmask — simulation "
                         "fidelity), 'masked_geom' restricts trust to "
                         "the aggregate-only signal a strong group-sum "
                         "deployment would leave (the bench's attacked-"
                         "accuracy delta quantifies the cost)")
    ap.add_argument("--dp-sigma", type=float, default=0.0,
                    help="DP noise multiplier on the per-round local-"
                         "update delta: whole-model L2 clip to "
                         "--dp-update-clip, then N(0,(sigma*clip)^2) "
                         "per coordinate (0 = off; stage traces away)")
    ap.add_argument("--dp-update-clip", type=float, default=1.0,
                    help="L2 clip norm for the --dp-sigma update delta")
    ap.add_argument("--max-staleness", type=int, default=0,
                    help="drop a peer's contribution when its model is "
                         "more than this many rounds stale (0 = off)")
    ap.add_argument("--shard-workers", type=int, default=0,
                    help="shard the worker/enrolled axis of the "
                         "simulation engines over this many devices "
                         "(sets XLA_FLAGS to force that many host "
                         "devices on CPU; see docs/ARCHITECTURE.md "
                         "'Sharded worker axis'). Scenario runs exit 1 "
                         "on dispatch-parity violation")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    """Run the mode the flags select; returns the process exit code."""
    args = parse_args(argv)
    if args.shard_workers > 1:
        # must land before ANY jax import — the sim paths import jax inside
        import os
        os.environ.setdefault(
            "XLA_FLAGS",
            f"--xla_force_host_platform_device_count={args.shard_workers}")

    if args.debug_mesh:
        # the CPU-test route: host devices stand in for chips
        import os
        n = 4 * (args.pods if args.fl else 1)
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        os.environ.setdefault(
            "XLA_FLAGS", f"--xla_force_host_platform_device_count={n}")

    from repro.cache import enable_compile_cache
    enable_compile_cache()

    if args.cross_device:
        return run_cross_device_sim(args)
    if args.scenario and not args.fl:
        return run_scenario_sim(args)

    losses = run_model(args)["losses"]
    if not np.isfinite(losses).all():
        print("FAIL: non-finite training loss")
        return 1
    return 0


def run_model(args) -> dict:
    """Train one model (or, with ``--fl``, one replica per pod that gossip
    every ``--gossip-every`` steps). Returns ``{"params", "losses" [steps,
    pods] (or [steps]), "step_s" [steps], "memory"}``; each step's seconds
    are its ``step`` span (``telemetry.trace``), from the train-step call
    to its loss on the host, so they include that step's compiles; the
    next step's batch is made meanwhile, started as the step's span opens
    (``TokenBatcher.prefetch``; the batch thread is done long before the
    span closes, so the span's two clocks stay together).
    ``memory`` maps each step program to its compiled footprint
    (``roofline.compiled_bytes``) with ``--memory-report``, else it is
    empty."""
    import jax
    import jax.numpy as jnp
    from repro.config import ShapeConfig, reduced
    from repro.configs import get_config
    from repro.core.topology import make_topology
    from repro.data.loader import TokenBatcher
    from repro.launch.mesh import make_debug_mesh, make_pod_mesh
    from repro.launch.roofline import compiled_bytes
    from repro.launch.sharding_rules import base_rules
    from repro.launch.steps import (build_fl_train_step, build_train_step,
                                    input_specs)
    from repro.models import model as model_mod
    from repro.optim import make_optimizer
    from repro.sharding import logical_rules
    from repro.telemetry.trace import span

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = reduced(cfg)
    opt = make_optimizer(args.optimizer, args.lr)
    pods = args.pods if args.fl else 0

    if args.debug_mesh:
        mesh = make_debug_mesh(pods=pods)
    elif pods and (args.transport == "ppermute"
                   or 1 < pods <= len(jax.devices())):
        mesh = make_pod_mesh(pods)           # one chip per pod
    else:
        mesh = None
    rules = base_rules(multi_pod=bool(pods)) if mesh else {}
    if pods and mesh:
        # inside the vmap(spmd_axis_name="pod") body, constraints must not
        # mention the pod axis — vmap supplies it for the batched dims
        rules = {**rules, "batch": ("data",)}
    batcher = TokenBatcher(cfg.vocab_size, args.seq, args.batch)

    key = jax.random.PRNGKey(0)
    params = model_mod.init_params(key, cfg)
    opt_state = opt.init(params)
    step = jnp.zeros((), jnp.int32)

    memory = {}
    ctx = logical_rules(mesh, rules) if mesh else _nullcontext()
    with (mesh if mesh else _nullcontext()), ctx:
        if args.fl:
            import dataclasses as _dc

            from repro.config import DeFTAConfig
            from repro.core.engine import (init_pod_state,
                                           resolve_dts_signal,
                                           sketch_shape)
            from repro.core.gossip import normalize_wire, \
                uses_error_feedback
            from repro.launch.steps import build_pod_gossip_step
            from repro.scenarios import compile_scenario, get_scenario
            from repro.scenarios.robust_agg import ROBUST_RULES
            from repro.sharding import WorkerShards

            def on_pods(fn, *a):
                """fn(*a) built straight into its placement: leaves with
                a leading pod axis row-sharded over the mesh's pod axis,
                the rest replicated (no mesh: the default device)."""
                if mesh is None:
                    return fn(*a)
                ws = WorkerShards(mesh, axis="pod")
                out = jax.tree.map(
                    lambda s: ws.row_sharding(len(s.shape))
                    if s.shape and s.shape[0] == pods
                    else ws.replicated(), jax.eval_shape(fn, *a))
                return jax.jit(fn, out_shardings=out)(*a)

            stack = lambda t: jax.tree.map(
                lambda x: jnp.stack([x] * pods), t)
            params, opt_state = on_pods(
                lambda p, o: (stack(p), stack(o)), params, opt_state)
            fl_step = jax.jit(build_fl_train_step(
                cfg, opt, spmd_axis_name="pod" if mesh else None),
                donate_argnums=(0, 1))
            adj = make_topology("dense", pods, pods - 1)
            sizes = np.full(pods, args.batch)

            robust = args.aggregation in ROBUST_RULES
            if args.dts_signal != "loss" and not (args.pod_dts
                                                  and not robust):
                raise SystemExit(f"--dts-signal {args.dts_signal} needs "
                                 f"--pod-dts (and a non-robust "
                                 f"--aggregation): without DTS no trust "
                                 f"update runs, the flag would be "
                                 f"silently inert")
            dcfg = DeFTAConfig(
                num_workers=pods, avg_peers=pods - 1,
                num_sampled=min(2, pods - 1), topology="dense",
                aggregation=args.aggregation,
                use_dts=args.pod_dts and not robust,
                dts_signal=args.dts_signal,
                time_machine=args.pod_time_machine and not robust,
                gossip_dtype="float32" if robust else args.gossip_wire,
                gossip_error_feedback=not args.no_gossip_ef,
                gossip_wire_round=args.gossip_wire_round,
                secagg="pairwise" if args.secagg and not robust else None,
                secagg_mode=args.secagg_mode,
                dp_sigma=args.dp_sigma,
                dp_update_clip=args.dp_update_clip)

            # gossip-round horizon = how many gossip rounds the run holds;
            # the scenario's epoch axis is the gossip round index
            rounds = max(args.steps // args.gossip_every, 1)
            scenario = None
            if args.scenario:
                n_app = get_scenario(
                    args.scenario, pods).num_appended_attackers()
                vanilla = pods - n_app
                if vanilla <= 0:
                    raise SystemExit(
                        f"scenario {args.scenario} appends {n_app} "
                        f"attackers but the mesh only has {pods} pods — "
                        f"attackers occupy pod slots; use more --pods")
                scenario = compile_scenario(
                    get_scenario(args.scenario, vanilla), vanilla, rounds)
                assert scenario.num_workers == pods
                print(f"--fl scenario {scenario.spec.name}: "
                      f"{scenario.summary(adj)}")

            self_eval = None
            if dcfg.time_machine:
                # the held-out self-eval batch: an index the training
                # loop never reaches (it consumes 0..steps-1), sliced to
                # a per-pod-sized share — every pod evaluates the SAME
                # slice (comparability) at 1/pods the full-batch cost
                hb = batcher.batch_at(args.steps + 1)
                hbatch = {k: jnp.asarray(v)[:args.batch // pods]
                          for k, v in hb.items()}

                def self_eval(stacked):
                    return jax.vmap(
                        lambda p: model_mod.loss_fn(p, cfg, hbatch)[0]
                    )(stacked)

            gossip_rnd, pod_tr = build_pod_gossip_step(
                cfg, dcfg, pods, sizes, adjacency=adj,
                transport=args.transport, mesh=mesh, scenario=scenario,
                self_eval=self_eval)
            gossip = jax.jit(gossip_rnd, donate_argnums=(0, 1))
            pstate = on_pods(lambda p: init_pod_state(
                jax.random.PRNGKey(101), pods, p,
                wire_error=uses_error_feedback(dcfg) and not robust,
                time_machine=dcfg.time_machine,
                sketch=sketch_shape(dcfg)), params)
            print(f"--fl pod pipeline: transport={pod_tr.kind} "
                  f"wire={pod_tr.wire or 'fp32'} ef={pod_tr.use_ef} "
                  f"aggregation={args.aggregation} dts={dcfg.use_dts} "
                  f"signal={dcfg.dts_signal} tm={dcfg.time_machine}")

            # geometry/correlation trust signals score TRUE local-train
            # deltas: snapshot what the pods depart from each gossip
            # interval (jnp.copy — fl_step donates the params buffer, so
            # a bare alias would be invalidated by the next train step)
            track_start = bool(resolve_dts_signal(dcfg))
            gossip_start = jax.tree.map(jnp.copy, params) \
                if track_start else None

            losses = jnp.zeros((pods,))
            hist, step_s = [], []
            for i in range(args.steps):
                b = batcher.batch_at(i)
                batch = {k: jnp.asarray(v).reshape(
                    pods, args.batch // pods, -1) for k, v in b.items()}
                with span("step", step=i) as sp:
                    if i + 1 < args.steps:
                        batcher.prefetch(i + 1)
                    params, opt_state, step, losses = fl_step(
                        params, opt_state, step, batch)
                    if (i + 1) % args.gossip_every == 0:
                        with span("gossip", step=i):
                            pstate, params = gossip(pstate, params, losses,
                                                    gossip_start)
                            if track_start:
                                gossip_start = jax.tree.map(jnp.copy,
                                                            params)
                    with span("loss_sync", step=i):
                        hist.append(np.asarray(losses, np.float64))
                step_s.append(sp.seconds)
                print(f"step {i:4d} losses="
                      f"{[round(float(x), 4) for x in hist[-1]]} "
                      f"({step_s[-1]:.2f}s)"
                      + ("  [gossip]" if (i + 1) % args.gossip_every == 0
                         else ""))
            if args.memory_report:
                memory["train_step"] = compiled_bytes(fl_step.lower(
                    params, opt_state, step, batch).compile())
                memory["gossip_round"] = compiled_bytes(gossip.lower(
                    pstate, params, losses, gossip_start).compile())
        else:
            tstep = jax.jit(build_train_step(cfg, opt),
                            donate_argnums=(0, 1))
            hist, step_s = [], []
            for i in range(args.steps):
                b = batcher.batch_at(i)
                batch = {k: jnp.asarray(v) for k, v in b.items()}
                with span("step", step=i) as sp:
                    if i + 1 < args.steps:
                        batcher.prefetch(i + 1)
                    params, opt_state, step, loss = tstep(
                        params, opt_state, step, batch)
                    with span("loss_sync", step=i):
                        hist.append(float(loss))
                step_s.append(sp.seconds)
                print(f"step {i:4d} loss={hist[-1]:.4f} "
                      f"({step_s[-1]:.2f}s)")
            if args.memory_report:
                memory["train_step"] = compiled_bytes(tstep.lower(
                    params, opt_state, step, batch).compile())
    for name, mem in memory.items():
        print(f"memory {name}: " + " ".join(
            f"{k}={v}" for k, v in mem.items()))

    if args.checkpoint_dir:
        from repro.checkpoint import save_checkpoint
        path = save_checkpoint(args.checkpoint_dir,
                               {"params": params, "opt": opt_state},
                               int(step))
        print("checkpoint saved:", path)
    return {"params": params, "losses": np.asarray(hist),
            "step_s": step_s, "memory": memory}


class _nullcontext:
    def __enter__(self):
        return None

    def __exit__(self, *a):
        return False


if __name__ == "__main__":
    raise SystemExit(main())
