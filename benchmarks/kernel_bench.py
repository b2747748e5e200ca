"""Kernel micro-bench: us_per_call of the Pallas kernels (interpret mode on
CPU — regression numbers, not TPU latencies) vs their jnp oracles.

Also emits ``BENCH_gossip.json``: the dense-vs-sparse-vs-einsum gossip
trajectory over (world size, topology density) — now including the
quantized wire sweep (bytes-on-wire by format + fused int8 kernel time) —
plus the super-step driver check (dispatch count and per-epoch-driver loss
agreement), the quantized-convergence parity check (int8 wire with EF21
error feedback lands within tolerance of the fp32 run), the geometric and
correlation trust_update cost contracts (dispatch parity + superstep
overhead vs loss-only DTS, sketch ring buffer included), the DTS v2/v3
headline cells (label_flip and alie × signal on the non-iid partition,
benchmarks/table_trust.py), the cross-device participation
acceptance runs (dispatch parity, clean sampled-vs-dense parity, the
sparse-observation trust headline) and the telemetry-plane cost contract
(probe-on vs probe-off superstep ratio + dispatch parity — the in-scan
metrics buffers must stay free; bench_telemetry)."""
from __future__ import annotations

import dataclasses
import json
import os
import time

if __name__ == "__main__":
    # the w-scaling sweep shards over the devices present; on the CPU
    # that is 8 host devices, forced before JAX starts (no effect on TPU)
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import (flash_attention, gossip_mix, gossip_mix_quant,
                           gossip_mix_sparse, moe_router_topk)
from repro.kernels.ref import (flash_attention_ref, gossip_mix_ref,
                               gossip_mix_quant_ref, moe_router_topk_ref)


def _time(fn, *args, iters=9):
    """Best-of-iters µs — min is the robust microbench estimator on a
    shared/noisy CPU (mean folds in scheduler hiccups)."""
    fn(*args)                       # compile
    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(iters):
        t0 = time.time()
        jax.block_until_ready(fn(*args))
        best = min(best, time.time() - t0)
    return best * 1e6


def _interleaved_best(runners, iters=3):
    """Best-of-``iters`` seconds for each runner, with the timed runs
    INTERLEAVED round-robin (a, b, a, b, ...) instead of blocked. The
    trust-overhead gates in bench_guard are RATIOS between runners; when
    each runner's runs are blocked together, CPU frequency scaling and
    cache-warmth drift between the blocks (each separated by seconds of
    compilation) leaks straight into the ratio. Interleaving makes every
    runner sample the same machine states."""
    best = [float("inf")] * len(runners)
    for _ in range(iters):
        for i, run in enumerate(runners):
            t0 = time.time()
            run()
            best[i] = min(best[i], time.time() - t0)
    return best


def bench_gossip(f: int = 4096, out_path: str = "BENCH_gossip.json"):
    """Dense Pallas vs padded-CSR sparse Pallas vs fused int8 quant-sparse
    Pallas vs jnp einsum across world sizes and topology densities.
    Density 1.0 = fully connected (sparse kernel degenerates to K=W);
    DeFTA's regime is the 0.05 column.

    Each row also accounts BYTES ON WIRE for the exchange the kernel
    mixes: nnz edges × payload, with the payload priced by wire format
    (fp32 / bf16 / int8 + per-row scale) — the sparse-topology economy and
    the wire-format economy compose (~4× on top of nnz/W²).

    The kernels' parameter block is capped at f (block_f=f), so they run
    single-tile wherever the tile fits VMEM: interpret mode pays a large
    fixed cost per grid step that would otherwise swamp the compute
    difference being measured."""
    import functools

    from repro.core.gossip import quantize_rows_int8, sparse_weights
    from repro.launch.roofline import gossip_wire_bytes

    dense_fn = functools.partial(gossip_mix, block_f=f)
    sparse_fn = functools.partial(gossip_mix_sparse, block_f=f)
    quant_fn = functools.partial(gossip_mix_quant, block_f=f)

    rows = []
    for w in (20, 100, 500):
        for density in (0.05, 0.3, 1.0):
            rng = np.random.default_rng(w)
            k_peers = max(1, round(density * w) - 1)
            adj = np.zeros((w, w), bool)
            for i in range(w):
                peers = rng.choice([j for j in range(w) if j != i],
                                   size=min(k_peers, w - 1), replace=False)
                adj[i, peers] = True
            P = (adj | np.eye(w, dtype=bool)).astype(np.float32)
            P /= P.sum(1, keepdims=True)
            P_j = jnp.asarray(P)
            idx_j, val_j = sparse_weights(P_j, adj)
            stack = jax.random.normal(jax.random.PRNGKey(w), (w, f))
            q_j, scale_j = quantize_rows_int8(stack)
            q_j, scale_j = jax.block_until_ready((q_j, scale_j))

            # the W=500/d=0.05 cell is the CI-guarded headline
            # (bench_guard.py compares its dense/sparse/quant ratios
            # against the committed baseline) — give the min-estimator
            # more samples there so the gate doesn't flake on scheduler
            # noise; best-of-N within one run cancels machine speed.
            iters = 15 if (w == 500 and density == 0.05) else 9
            dense_us = _time(dense_fn, P_j, stack, iters=iters)
            sparse_us = _time(sparse_fn, idx_j, val_j, stack, iters=iters)
            quant_us = _time(quant_fn, idx_j, val_j, scale_j, q_j,
                             iters=iters)
            einsum_us = _time(jax.jit(gossip_mix_ref), P_j, stack,
                              iters=iters)
            ref = gossip_mix_ref(P_j, stack)
            out_q = quant_fn(idx_j, val_j, scale_j, q_j)
            err = float(jnp.abs(
                sparse_fn(idx_j, val_j, stack) - ref).max())
            err_q_kernel = float(jnp.abs(
                out_q - gossip_mix_quant_ref(idx_j, val_j, scale_j,
                                             q_j)).max())
            err_q_wire = float(jnp.abs(out_q - ref).max())

            # bytes on wire for this exchange: one row payload per real
            # edge — self-loops excluded (a worker never ships its model
            # to itself; matches roofline.gossip_round_wire_bytes)
            nnz = int(adj.sum())
            wire_mb = {fmt or "fp32":
                       nnz * gossip_wire_bytes(f, fmt, rows=1) / 1e6
                       for fmt in (None, "bf16", "int8")}
            wire_mb["dense_fp32"] = w * (w - 1) * gossip_wire_bytes(f) / 1e6

            rows.append(dict(
                W=w, density=density, K=int(idx_j.shape[1]), nnz=nnz,
                dense_us=dense_us, sparse_us=sparse_us,
                quant_us=quant_us, einsum_us=einsum_us, max_err=err,
                quant_kernel_err=err_q_kernel, quant_wire_err=err_q_wire,
                wire_mb=wire_mb,
                int8_fp32_byte_ratio=wire_mb["int8"] / wire_mb["fp32"]))
            print(f"gossip W={w:4d} density={density:.2f} K={idx_j.shape[1]:3d}"
                  f" dense={dense_us:9.0f}us sparse={sparse_us:9.0f}us"
                  f" quant={quant_us:9.0f}us einsum={einsum_us:9.0f}us"
                  f" err={err:.2e} int8_bytes={wire_mb['int8']:.1f}MB"
                  f" ({wire_mb['int8'] / wire_mb['fp32']:.2f}x fp32)")

    superstep = bench_superstep()
    quant_convergence = bench_quant_convergence()
    scenario_overhead = bench_scenario_overhead()
    fedavg_dispatch = bench_fedavg_dispatch()
    geom_trust = bench_geom_trust()
    corr_trust = bench_corr_trust()
    telemetry = bench_telemetry()
    trust_grid = bench_trust_grid()
    cross_device = bench_cross_device(trust_grid=trust_grid)
    w_scaling = bench_w_scaling()
    privacy = bench_secagg()
    payload = dict(feature_dim=f, rows=rows, superstep=superstep,
                   quant_convergence=quant_convergence,
                   scenario_overhead=scenario_overhead,
                   fedavg_dispatch=fedavg_dispatch,
                   geom_trust=geom_trust, corr_trust=corr_trust,
                   telemetry=telemetry,
                   trust_grid=trust_grid, cross_device=cross_device,
                   w_scaling=w_scaling, privacy=privacy)
    with open(out_path, "w") as fh:
        json.dump(payload, fh, indent=2)
    print(f"wrote {os.path.abspath(out_path)}")
    return payload


def bench_superstep(epochs: int = 200, eval_every: int = 50):
    """The fused-driver contract: a 200-epoch run is ceil(epochs /
    eval_every) XLA dispatches and its losses match the per-epoch driver."""
    from repro.config import DeFTAConfig, TrainConfig
    from repro.core.defta import run_defta
    from repro.core.tasks import mlp_task
    from repro.data.synthetic import federated_dataset

    w = 4
    data = federated_dataset("vector", w, np.random.default_rng(0),
                             n_per_worker=64, alpha=0.5)
    task = mlp_task(32, 10)
    cfg = DeFTAConfig(num_workers=w, avg_peers=2, num_sampled=1,
                      local_epochs=1)
    train = TrainConfig(learning_rate=0.05, batch_size=32)
    key = jax.random.PRNGKey(0)

    stats = {}
    t0 = time.time()
    st_fused, _, _, _ = run_defta(
        key, task, cfg, train, data, epochs=epochs, eval_every=eval_every,
        test_x=data["test_x"], test_y=data["test_y"], stats=stats)
    fused_s = time.time() - t0
    t0 = time.time()
    st_loop, _, _, _ = run_defta(
        key, task, cfg, train, data, epochs=epochs, eval_every=eval_every,
        test_x=data["test_x"], test_y=data["test_y"], superstep=False)
    loop_s = time.time() - t0
    delta = float(jnp.abs(st_fused.last_loss - st_loop.last_loss).max())
    budget = -(-epochs // eval_every)
    print(f"superstep {epochs} epochs: {stats['dispatches']} dispatches "
          f"(budget {budget}), {fused_s:.1f}s fused vs {loop_s:.1f}s "
          f"per-epoch, max loss delta {delta:.2e}")
    assert stats["dispatches"] <= budget, stats
    assert delta < 1e-4, delta
    return dict(epochs=epochs, eval_every=eval_every,
                dispatches=stats["dispatches"], dispatch_budget=budget,
                fused_s=fused_s, per_epoch_s=loop_s, max_loss_delta=delta)


def bench_quant_convergence(epochs: int = 200, tolerance: float = 0.02):
    """Convergence parity of the quantized wire: a 200-epoch paper_small
    run on the int8 wire WITH EF21 error feedback must land within
    ``tolerance`` (relative) of the fp32 run's final loss — the lossy wire
    is a wire-bytes optimization, not an accuracy trade."""
    import dataclasses

    from repro.config import DeFTAConfig, TrainConfig
    from repro.core.defta import run_defta
    from repro.core.tasks import mlp_task
    from repro.data.synthetic import federated_dataset

    w = 4
    data = federated_dataset("vector", w, np.random.default_rng(0),
                             n_per_worker=64, alpha=0.5)
    task = mlp_task(32, 10)
    cfg = DeFTAConfig(num_workers=w, avg_peers=2, num_sampled=1,
                      local_epochs=1)
    train = TrainConfig(learning_rate=0.05, batch_size=32)
    key = jax.random.PRNGKey(0)

    def final_loss(c, backend):
        st, _, _, _ = run_defta(key, task, c, train, data, epochs=epochs,
                                gossip_backend=backend)
        return float(jnp.mean(st.last_loss))

    loss_fp32 = final_loss(cfg, "einsum")
    loss_int8 = final_loss(
        dataclasses.replace(cfg, gossip_dtype="int8"), "auto")
    loss_int8_noef = final_loss(
        dataclasses.replace(cfg, gossip_dtype="int8",
                            gossip_error_feedback=False), "auto")
    rel = abs(loss_int8 - loss_fp32) / abs(loss_fp32)
    print(f"quant convergence {epochs} epochs: fp32={loss_fp32:.4f} "
          f"int8+EF={loss_int8:.4f} (rel {rel:.3%}) "
          f"int8/no-EF={loss_int8_noef:.4f}")
    assert rel < tolerance, (loss_fp32, loss_int8, rel)
    return dict(epochs=epochs, loss_fp32=loss_fp32, loss_int8_ef=loss_int8,
                loss_int8_no_ef=loss_int8_noef, rel_delta=rel,
                tolerance=tolerance)


def bench_fedavg_dispatch(epochs: int = 120):
    """Unified-driver dispatch parity: FedAvg rides the SAME chunked-scan
    superstep driver as the DeFTA engines since the round-program
    refactor, so a run with nothing to eval is ONE dispatch for both —
    and the per-epoch reference loop still matches the fused run's final
    server loss. CI gates the parity (bench_guard.py)."""
    from repro.config import DeFTAConfig, TrainConfig
    from repro.core.defta import run_defta
    from repro.core.fedavg import evaluate_server, run_fedavg
    from repro.core.tasks import mlp_task
    from repro.data.synthetic import federated_dataset

    w = 4
    data = federated_dataset("vector", w, np.random.default_rng(0),
                             n_per_worker=64, alpha=0.5)
    task = mlp_task(32, 10)
    cfg = DeFTAConfig(num_workers=w, avg_peers=2, num_sampled=1,
                      local_epochs=1)
    train = TrainConfig(learning_rate=0.05, batch_size=32)
    key = jax.random.PRNGKey(0)

    # dispatches AND wall-clock both come from the RunLedger — the
    # telemetry plane's unified accounting (repro/telemetry/ledger.py)
    from repro.telemetry import RunLedger
    led_f, led_d = RunLedger(), RunLedger()
    st_f = run_fedavg(key, task, cfg, train, data, epochs=epochs,
                      ledger=led_f)
    run_defta(key, task, cfg, train, data, epochs=epochs, ledger=led_d)
    st_ref = run_fedavg(key, task, cfg, train, data, epochs=epochs,
                        superstep=False)
    acc_fused = evaluate_server(task, st_f, data["test_x"], data["test_y"])
    acc_ref = evaluate_server(task, st_ref, data["test_x"],
                              data["test_y"])
    print(f"fedavg dispatch parity {epochs} epochs: fedavg "
          f"{led_f.dispatches} vs defta {led_d.dispatches} "
          f"dispatches ({led_f.wall_s:.1f}s vs {led_d.wall_s:.1f}s); "
          f"fused acc {acc_fused:.3f} vs per-epoch {acc_ref:.3f}")
    # no assert here: a parity break must still emit the bench file so
    # bench_guard can report its purpose-built diagnostic
    return dict(epochs=epochs, dispatches_fedavg=led_f.dispatches,
                dispatches_defta=led_d.dispatches,
                wall_fedavg_s=led_f.wall_s, wall_defta_s=led_d.wall_s,
                acc_fused=acc_fused, acc_per_epoch=acc_ref)


def bench_scenario_overhead(epochs: int = 60):
    """Scenario-engine overhead on the superstepped driver: the same run
    with a churn+sign-flip+straggler scenario vs the static topology, both
    end-to-end (host compile_scenario + trace + XLA compile + execute).
    The contract: identical dispatch counts (scenarios are data, not
    control flow) and bounded wall-clock overhead per superstep — the
    per-epoch mask lookups, dynamic outdegree renormalization and attack
    transforms ride inside the scan."""
    from repro.config import DeFTAConfig, TrainConfig
    from repro.core.defta import run_defta
    from repro.core.tasks import mlp_task
    from repro.data.synthetic import federated_dataset
    from repro.scenarios import (AttackSpec, ChurnSpec, ScenarioSpec,
                                 StragglerSpec, compile_scenario)

    w = 6
    data = federated_dataset("vector", w, np.random.default_rng(0),
                             n_per_worker=64, alpha=0.5)
    task = mlp_task(32, 10)
    cfg = DeFTAConfig(num_workers=w, avg_peers=3, num_sampled=2,
                      local_epochs=1)
    train = TrainConfig(learning_rate=0.05, batch_size=32)
    spec = ScenarioSpec(
        name="bench", attacks=(AttackSpec("sign_flip"),),
        churn=(ChurnSpec(worker=0, leave=epochs // 2),),
        stragglers=(StragglerSpec(worker=1, speed=0.5),))

    t0 = time.time()
    compiled = compile_scenario(spec, w, epochs)
    compile_s = time.time() - t0

    def once(scenario):
        stats = {}
        t0 = time.time()
        run_defta(jax.random.PRNGKey(0), task, cfg, train, data,
                  epochs=epochs, scenario=scenario, stats=stats)
        return time.time() - t0, stats["dispatches"]

    # best-of-2: run_defta re-traces per call, so each timing includes the
    # full trace+compile+execute pipeline — exactly the per-superstep cost
    # a user pays; best-of filters scheduler noise
    static_s, d_static = min(once(None) for _ in range(2))
    scn_s, d_scn = min(once(compiled) for _ in range(2))
    ratio = scn_s / static_s
    print(f"scenario overhead {epochs} epochs: static {static_s:.2f}s vs "
          f"scenario {scn_s:.2f}s ({ratio:.2f}x, compile_scenario "
          f"{compile_s * 1e3:.1f}ms, dispatches {d_static} vs {d_scn})")
    assert d_scn == d_static, (d_scn, d_static)
    return dict(epochs=epochs, static_s=static_s, scenario_s=scn_s,
                ratio=ratio, compile_scenario_s=compile_s,
                dispatches_static=d_static, dispatches_scenario=d_scn)


def bench_geom_trust(epochs: int = 20):
    """DTS v2 cost contract, CI-gated by bench_guard: the geometric
    trust_update stage variant (``dts_signal="geom"``) must keep DISPATCH
    PARITY with loss-only (geometry is data flow inside the scanned round
    body, never control flow) and the STEADY-STATE scanned superstep must
    stay within the overhead gate (≤ 1 + tolerance ×) at the paper's
    round shape (local_epochs=10) — geometry is a fixed per-round cost,
    so the contract is defined against a representative round, not a
    local_epochs=1 microbench where any fixed cost looks huge. Compile
    is excluded (the one-off trace/compile delta is reported separately):
    the two signals compile DIFFERENT graphs, and compile-time variance
    across CI machines would swamp a ratio gate. The best-of-3 timed
    runs are INTERLEAVED across the two signals (see _interleaved_best)
    so machine-state drift cancels out of the ratio."""
    import dataclasses

    from repro.config import DeFTAConfig, TrainConfig
    from repro.core.defta import (_pad_workers, build_round_fn,
                                  resolve_scenario)
    from repro.core.engine import init_state
    from repro.core.tasks import mlp_task
    from repro.core.topology import make_topology
    from repro.data.synthetic import federated_dataset
    from repro.scenarios import AttackSpec, ScenarioSpec

    w, k = 8, 4
    data = federated_dataset("vector", w, np.random.default_rng(0),
                             n_per_worker=64, alpha=0.5)
    task = mlp_task(32, 10)
    train = TrainConfig(learning_rate=0.05, batch_size=32)
    spec = ScenarioSpec(
        name="geom_bench",
        attacks=tuple(AttackSpec("label_flip") for _ in range(k)))

    def measure(signal):
        cfg = DeFTAConfig(num_workers=w, avg_peers=3, num_sampled=2,
                          local_epochs=10, dts_signal=signal)
        scn = resolve_scenario(spec, cfg, epochs)
        d2, sizes = _pad_workers(data, data["sizes"], k)
        jdata = {kk: jnp.asarray(v) for kk, v in d2.items()
                 if kk in ("x", "y", "mask")}
        adj = make_topology(cfg.topology, scn.num_workers, cfg.avg_peers,
                            cfg.seed)
        rnd = build_round_fn(task, cfg, train, adj, sizes,
                             scn.malicious.copy(), scenario=scn,
                             num_classes=10)

        @jax.jit
        def chunk(st, jd):
            return jax.lax.scan(lambda s, e: (rnd(s, jd, e), None), st,
                                jnp.arange(epochs))[0]

        st = init_state(jax.random.PRNGKey(0), task, scn.num_workers)
        t0 = time.time()
        jax.block_until_ready(chunk(st, jdata))      # trace + compile
        compile_s = time.time() - t0
        # one XLA dispatch per call; timing happens interleaved below
        return (lambda: jax.block_until_ready(chunk(st, jdata))), compile_s

    run_loss, loss_compile = measure("loss")
    run_geom, geom_compile = measure("geom")
    loss_s, geom_s = _interleaved_best([run_loss, run_geom])
    ratio = geom_s / loss_s
    # dispatch parity on the end-to-end driver (stats accounting)
    from repro.core.defta import run_defta
    stats_l, stats_g = {}, {}
    base = DeFTAConfig(num_workers=w, avg_peers=3, num_sampled=2,
                       local_epochs=1)
    run_defta(jax.random.PRNGKey(0), task, base, train, data, epochs=6,
              scenario=spec, stats=stats_l)
    run_defta(jax.random.PRNGKey(0), task,
              dataclasses.replace(base, dts_signal="geom"), train, data,
              epochs=6, scenario=spec, stats=stats_g)
    print(f"geom trust overhead {epochs}x10-local-epoch supersteps: "
          f"loss {loss_s:.2f}s vs geom {geom_s:.2f}s ({ratio:.2f}x "
          f"steady-state; compile {loss_compile:.1f}s vs "
          f"{geom_compile:.1f}s; dispatches {stats_l['dispatches']} vs "
          f"{stats_g['dispatches']})")
    return dict(epochs=epochs, loss_s=loss_s, geom_s=geom_s, ratio=ratio,
                compile_loss_s=loss_compile, compile_geom_s=geom_compile,
                dispatches_loss=stats_l["dispatches"],
                dispatches_geom=stats_g["dispatches"])


def bench_corr_trust(epochs: int = 20):
    """DTS v3 cost contract, CI-gated by bench_guard: the correlation
    trust channel ("corr", and "all" = loss+geom+corr — per-round sketch
    rotation plus the [W, W] sign-matmul over the flattened ring buffer)
    must keep DISPATCH PARITY with loss-only DTS (sketches are carried
    scan state, never control flow) and hold the STEADY-STATE scanned
    superstep within the ≤ 1.25× overhead gate at the paper's round shape
    (local_epochs=10). Same methodology as bench_geom_trust: compile
    excluded, best-of-3 single-dispatch chunks timed INTERLEAVED across
    the three signals (so CPU frequency/cache drift cancels out of the
    ratios), alie colluders in the scenario so the sketch path scores
    real collusion."""
    import dataclasses

    from repro.config import DeFTAConfig, TrainConfig
    from repro.core.defta import (_pad_workers, build_round_fn, run_defta,
                                  resolve_scenario)
    from repro.core.engine import init_state, sketch_shape
    from repro.core.tasks import mlp_task
    from repro.core.topology import make_topology
    from repro.data.synthetic import federated_dataset
    from repro.scenarios import AttackSpec, ScenarioSpec

    w, k = 8, 4
    data = federated_dataset("vector", w, np.random.default_rng(0),
                             n_per_worker=64, alpha=0.5)
    task = mlp_task(32, 10)
    train = TrainConfig(learning_rate=0.05, batch_size=32)
    spec = ScenarioSpec(
        name="corr_bench",
        attacks=tuple(AttackSpec("alie") for _ in range(k)))

    def measure(signal):
        cfg = DeFTAConfig(num_workers=w, avg_peers=3, num_sampled=2,
                          local_epochs=10, dts_signal=signal)
        scn = resolve_scenario(spec, cfg, epochs)
        d2, sizes = _pad_workers(data, data["sizes"], k)
        jdata = {kk: jnp.asarray(v) for kk, v in d2.items()
                 if kk in ("x", "y", "mask")}
        adj = make_topology(cfg.topology, scn.num_workers, cfg.avg_peers,
                            cfg.seed)
        rnd = build_round_fn(task, cfg, train, adj, sizes,
                             scn.malicious.copy(), scenario=scn,
                             num_classes=10)

        @jax.jit
        def chunk(st, jd):
            return jax.lax.scan(lambda s, e: (rnd(s, jd, e), None), st,
                                jnp.arange(epochs))[0]

        st = init_state(jax.random.PRNGKey(0), task, scn.num_workers,
                        sketch=sketch_shape(cfg))
        t0 = time.time()
        jax.block_until_ready(chunk(st, jdata))      # trace + compile
        compile_s = time.time() - t0
        # one XLA dispatch per call; timing happens interleaved below
        return (lambda: jax.block_until_ready(chunk(st, jdata))), compile_s

    run_loss, _ = measure("loss")
    run_corr, _ = measure("corr")
    run_all, _ = measure("all")
    loss_s, corr_s, all_s = _interleaved_best([run_loss, run_corr, run_all])
    ratio_corr, ratio_all = corr_s / loss_s, all_s / loss_s
    # dispatch parity on the end-to-end driver (stats accounting)
    base = DeFTAConfig(num_workers=w, avg_peers=3, num_sampled=2,
                       local_epochs=1)
    stats = {}
    dispatches = {}
    for sig in ("loss", "corr", "all"):
        stats = {}
        run_defta(jax.random.PRNGKey(0), task,
                  dataclasses.replace(base, dts_signal=sig), train, data,
                  epochs=6, scenario=spec, stats=stats)
        dispatches[sig] = stats["dispatches"]
    print(f"corr trust overhead {epochs}x10-local-epoch supersteps: "
          f"loss {loss_s:.2f}s vs corr {corr_s:.2f}s ({ratio_corr:.2f}x) "
          f"vs all {all_s:.2f}s ({ratio_all:.2f}x); dispatches "
          f"{dispatches['loss']} / {dispatches['corr']} / "
          f"{dispatches['all']}")
    return dict(epochs=epochs, loss_s=loss_s, corr_s=corr_s, all_s=all_s,
                ratio_corr=ratio_corr, ratio_all=ratio_all,
                dispatches_loss=dispatches["loss"],
                dispatches_corr=dispatches["corr"],
                dispatches_all=dispatches["all"])


def bench_telemetry(epochs: int = 20):
    """Telemetry-plane cost contract, CI-gated by bench_guard: building
    the round with a Telemetry registry (per-round trust / wire-byte /
    loss / fire probes riding the scan as stacked ys) must keep DISPATCH
    PARITY with a probe-less run (telemetry is data flow, never control
    flow) and hold the STEADY-STATE scanned superstep within the hard
    ≤ 1.10× overhead gate at the paper's round shape (local_epochs=10).
    Same methodology as bench_geom_trust: compile excluded, best-of-3
    single-dispatch chunks timed INTERLEAVED across on/off so machine
    drift cancels out of the ratio; a scenario is attached so the full
    probe set (alive/fire included) is the thing being priced."""
    from repro.config import DeFTAConfig, TrainConfig
    from repro.core.defta import (_pad_workers, build_round_fn, run_defta,
                                  resolve_scenario)
    from repro.core.engine import init_state
    from repro.core.tasks import mlp_task
    from repro.core.topology import make_topology
    from repro.data.synthetic import federated_dataset
    from repro.scenarios import AttackSpec, ScenarioSpec
    from repro.telemetry import RunLedger, Telemetry
    from repro.telemetry.spec import defta_specs, frame_bytes

    w, k = 8, 2
    data = federated_dataset("vector", w, np.random.default_rng(0),
                             n_per_worker=64, alpha=0.5)
    task = mlp_task(32, 10)
    train = TrainConfig(learning_rate=0.05, batch_size=32)
    spec = ScenarioSpec(
        name="telemetry_bench",
        attacks=tuple(AttackSpec("sign_flip") for _ in range(k)))

    def measure(telemetry):
        cfg = DeFTAConfig(num_workers=w, avg_peers=3, num_sampled=2,
                          local_epochs=10)
        scn = resolve_scenario(spec, cfg, epochs)
        d2, sizes = _pad_workers(data, data["sizes"], k)
        jdata = {kk: jnp.asarray(v) for kk, v in d2.items()
                 if kk in ("x", "y", "mask")}
        adj = make_topology(cfg.topology, scn.num_workers, cfg.avg_peers,
                            cfg.seed)
        rnd = build_round_fn(task, cfg, train, adj, sizes,
                             scn.malicious.copy(), scenario=scn,
                             num_classes=10, telemetry=telemetry)

        if telemetry is None:
            @jax.jit
            def chunk(st, jd):
                return jax.lax.scan(lambda s, e: (rnd(s, jd, e), None),
                                    st, jnp.arange(epochs))[0]
        else:
            # the probe frames stack into the scan ys — the realized
            # telemetry buffer; timing includes materializing it
            @jax.jit
            def chunk(st, jd):
                return jax.lax.scan(lambda s, e: rnd(s, jd, e), st,
                                    jnp.arange(epochs))

        st = init_state(jax.random.PRNGKey(0), task, scn.num_workers)
        jax.block_until_ready(chunk(st, jdata))      # trace + compile
        return lambda: jax.block_until_ready(chunk(st, jdata))

    run_off = measure(None)
    run_on = measure(Telemetry())
    off_s, on_s = _interleaved_best([run_off, run_on])
    ratio = on_s / off_s

    # dispatch parity + buffer accounting on the end-to-end driver
    base = DeFTAConfig(num_workers=w, avg_peers=3, num_sampled=2,
                       local_epochs=1)
    stats_off, led = {}, RunLedger()
    run_defta(jax.random.PRNGKey(0), task, base, train, data, epochs=6,
              scenario=spec, stats=stats_off)
    run_defta(jax.random.PRNGKey(0), task, base, train, data, epochs=6,
              scenario=spec, ledger=led)
    specs = defta_specs(w + k, scenario=True)
    per_round = frame_bytes(specs)
    print(f"telemetry overhead {epochs}x10-local-epoch supersteps: "
          f"off {off_s:.2f}s vs on {on_s:.2f}s ({ratio:.2f}x; "
          f"{len(specs)} probes, {per_round} B/round; dispatches "
          f"{stats_off['dispatches']} vs {led.dispatches})")
    return dict(epochs=epochs, off_s=off_s, on_s=on_s, ratio=ratio,
                dispatches_off=stats_off["dispatches"],
                dispatches_on=led.dispatches, probes=len(specs),
                bytes_per_round=float(per_round),
                buffer_bytes=float(per_round * epochs))


def bench_trust_grid(epochs: int = 40):
    """The DTS v2+v3 headline cells for the BENCH trajectory:
    (label_flip, alie) × (loss / geom / both / corr / all) on the non-iid
    partition — the PR-3 failure case the geometric signal fixes plus the
    alie collusion case the correlation signal fixes (k=8 attackers on 20
    vanilla workers ≈ 29% malicious). Full grid (more attacks, iid
    column, trust trajectories) in benchmarks/table_trust.py; this
    compact slice rides BENCH_gossip.json so bench_guard and the
    dashboard track both headlines across PRs."""
    try:
        from benchmarks.table_trust import (alie_headline_check,
                                            headline_check, sweep)
    except ImportError:                    # run as benchmarks/kernel_bench.py
        from table_trust import alie_headline_check, headline_check, sweep

    rows = sweep(epochs=epochs, attacks=("label_flip", "alie"),
                 partitions=(("non_iid", 0.5),))
    ok, accs = headline_check(rows, verbose=False)
    alie_ok, alie_accs = alie_headline_check(rows, verbose=False)
    return dict(epochs=epochs, headline_ok=bool(ok), accs=accs,
                alie_headline_ok=bool(alie_ok), alie_accs=alie_accs,
                rows=rows)


def bench_cross_device(rounds: int = 120, dense_epochs: int = 40,
                       trust_grid=None):
    """Cross-device acceptance bench, CI-gated by bench_guard: the
    churn-as-default participation engine (enrolled population, sampled
    cohorts, default-on dropout/stragglers, sparsely-observed DTS with
    lazy confidence decay) must

    * keep DISPATCH PARITY — a T-round world is ceil(T / eval_every)
      XLA dispatches, gather/scatter fused into the scan body;
    * match clean full-participation: an all-honest cross-device world
      (participation rate ~0.43, so ``rounds`` gives each user at least
      the ``dense_epochs`` training budget) lands within the margin of
      the dense clean run; and
    * hold the DTS v3 headline under sparse observation: label_flip +
      alie at ~29% of the ENROLLED population (so ~29% of every cohort
      in expectation, but any one attacker is only observed every ~1/rate
      rounds) must keep final honest probe accuracy within the margin of
      the DENSE alie × non-iid headline cell (``trust_grid``).
    """
    from repro.config import DeFTAConfig, TrainConfig
    from repro.core.cross_device import (evaluate_probe, probe_indices,
                                         run_cross_device)
    from repro.core.defta import evaluate, run_defta
    from repro.core.tasks import mlp_task
    from repro.data.synthetic import federated_dataset
    from repro.scenarios.cross_device import CrossDeviceSpec, compile_world

    task = mlp_task(32, 10)
    train = TrainConfig(learning_rate=0.05, batch_size=32)
    eval_every = 30
    budget = -(-rounds // eval_every)

    def cd_run(enrolled, k, attacks, signal, *, avg_peers=4,
               num_sampled=2):
        cfg = DeFTAConfig(num_workers=enrolled, avg_peers=avg_peers,
                          num_sampled=num_sampled, local_epochs=3,
                          dts_signal=signal, dts_conf_decay=0.98, seed=0)
        data = federated_dataset("vector", enrolled,
                                 np.random.default_rng(0),
                                 n_per_worker=120, alpha=0.5)
        spec = CrossDeviceSpec(enrolled=enrolled, sample_k=k,
                               avg_peers=avg_peers, availability=0.7,
                               dropout=0.05, straggle=0.10,
                               attacks=attacks, seed=0)
        world = compile_world(spec, rounds)
        # dispatches + wall from the same source of truth: the telemetry
        # plane's RunLedger (also exercises the cohort probes in-scan)
        from repro.telemetry import RunLedger
        led = RunLedger()
        state, _ = run_cross_device(
            jax.random.PRNGKey(0), task, cfg, train, data, world=world,
            epochs=rounds, eval_every=eval_every,
            test_x=data["test_x"], test_y=data["test_y"], ledger=led)
        pix = probe_indices(world, 32, seed=0)
        m, s = evaluate_probe(task, state, data["test_x"],
                              data["test_y"], pix)
        return dict(acc=m, std=s, dispatches=led.dispatches,
                    wall_s=led.wall_s,
                    participation_rate=world.summary()
                    ["participation_rate"])

    # clean full-participation reference: dense run_defta, same shards
    data_d = federated_dataset("vector", 20, np.random.default_rng(0),
                               n_per_worker=120, alpha=0.5)
    cfg_d = DeFTAConfig(num_workers=20, avg_peers=4, num_sampled=2,
                        local_epochs=3, seed=0)
    st, _, mal, _ = run_defta(jax.random.PRNGKey(0), task, cfg_d, train,
                              data_d, epochs=dense_epochs)
    clean_dense_acc, _, _ = evaluate(task, st, data_d["test_x"],
                                     data_d["test_y"], mal)

    clean = cd_run(20, 10, (), "loss")
    # 20 honest + 4 label_flip + 4 alie = 28.6% of enrolled malicious —
    # the dense headline's attacker fraction, now sparsely observed.
    # The attacked cohort listens wider (degree 6, sample 3) than the
    # dense world's 4/2: with any one peer observed only every ~1/rate
    # rounds, per-pair trust evidence accrues 1/rate as fast, and a
    # denser cohort graph buys the evidence back without touching the
    # threat model.
    attacks = (("label_flip", 4 / 28), ("alie", 4 / 28))
    attacked = {sig: cd_run(28, 14, attacks, sig, avg_peers=6,
                            num_sampled=3)
                for sig in ("corr", "all")}

    dense_alie_accs = (trust_grid or {}).get("alie_accs", {})
    print(f"cross-device clean: dense {clean_dense_acc:.3f} vs sampled "
          f"{clean['acc']:.3f} (rate {clean['participation_rate']:.2f}, "
          f"{clean['dispatches']} dispatches, budget {budget})")
    for sig, r in attacked.items():
        ref = dense_alie_accs.get(sig)
        print(f"cross-device attacked 29% × {sig}: probe acc "
              f"{r['acc']:.3f} (dense headline "
              f"{'n/a' if ref is None else format(ref, '.3f')}, "
              f"{r['dispatches']} dispatches, {r['wall_s']:.0f}s)")
    return dict(rounds=rounds, dense_epochs=dense_epochs,
                eval_every=eval_every, dispatch_budget=budget,
                clean_dense_acc=float(clean_dense_acc), clean=clean,
                attacked=attacked, dense_alie_accs=dense_alie_accs)


def bench_secagg(epochs: int = 24, eval_every: int = 6):
    """Privacy-wire acceptance bench, CI-gated by bench_guard:

    * MASK-BYTE ACCOUNTING — ``core.secagg.secagg_mask_bytes`` over the
      run's realized topology must EQUAL the independent
      ``roofline.secagg_pad_bytes`` re-derivation for every wire format
      (and the wire overhead is structurally zero: the OTP masks in
      place in the wire format's integer ring);
    * CLEAN PARITY — a secagg run must land within 0.01 of the unmasked
      run at the same seed (the masked wire decodes bit for bit, so the
      delta is 0.0 by construction — the gate catches any future mask
      scheme that starts re-rounding payloads);
    * DISPATCH PARITY — secagg runs stay on the ceil(epochs/eval_every)
      superstep budget (pads are traced data flow, never control flow);
    * the MASKED_GEOM row family — churn_signflip under geom DTS with
      per-peer trust (``secagg_mode="edge"``) vs aggregate-only trust
      (``"masked_geom"``): the attacked-accuracy delta is the price of
      hiding individual updates from the trust engine;
    * the naive DP accountant column for the update-noise stage.
    """
    from repro.config import DeFTAConfig, TrainConfig
    from repro.core.defta import evaluate, run_defta
    from repro.core.secagg import secagg_mask_bytes
    from repro.core.tasks import mlp_task
    from repro.data.synthetic import federated_dataset
    from repro.launch.roofline import dp_epsilon, secagg_pad_bytes

    task = mlp_task(32, 10)
    train = TrainConfig(learning_rate=0.05, batch_size=32)
    cfg = DeFTAConfig(num_workers=10, avg_peers=4, num_sampled=2,
                      local_epochs=3, seed=0)
    data = federated_dataset("vector", cfg.num_workers,
                             np.random.default_rng(0), n_per_worker=120,
                             alpha=0.5)
    budget = -(-epochs // eval_every)

    def run_one(c, scenario=None, d=None):
        d = data if d is None else d
        stats = {}
        st, adj, mal, _ = run_defta(
            jax.random.PRNGKey(0), task, c, train, d, epochs=epochs,
            scenario=scenario, eval_every=eval_every,
            test_x=d["test_x"], test_y=d["test_y"], stats=stats)
        m, _, _ = evaluate(task, st, d["test_x"], d["test_y"], mal)
        return float(m), stats.get("dispatches", 0), st, adj

    clean_acc, d0, st, adj = run_one(cfg)
    sec_acc, d1, _, _ = run_one(dataclasses.replace(cfg, secagg="pairwise"))
    dp_acc, d2, _, _ = run_one(dataclasses.replace(
        cfg, secagg="pairwise", dp_sigma=1.0))

    # mask-byte accounting over the run's realized support: the engine's
    # own accounting vs the roofline's independent re-derivation
    a = np.asarray(adj, bool).copy()
    np.fill_diagonal(a, False)
    leaves = jax.tree.leaves(st.params)
    n_params = sum(int(np.prod(v.shape[1:])) for v in leaves)
    n_edges = int(a.sum())
    mask_rows = {}
    for fmt in (None, "bf16", "int8"):
        realized = secagg_mask_bytes(n_edges, n_params, fmt,
                                     rows=len(leaves))
        roof = secagg_pad_bytes(a, n_params, fmt, rows=len(leaves))
        mask_rows[fmt or "fp32"] = dict(
            realized_bytes=float(realized),
            roofline_bytes=roof["pad_bytes"],
            wire_overhead_bytes=roof["wire_overhead_bytes"],
            ok=float(realized) == roof["pad_bytes"])
    mask_bytes_ok = all(r["ok"] for r in mask_rows.values())

    # masked_geom attacked row family: per-peer vs aggregate-only trust
    # (churn_signflip appends its attackers on top of num_workers, so the
    # scenario runs carry their own 8-worker dataset)
    cfg_g = dataclasses.replace(cfg, num_workers=8, dts_signal="geom")
    data_g = federated_dataset("vector", 8, np.random.default_rng(0),
                               n_per_worker=120, alpha=0.5)
    att = {}
    for mode, c in (("plain", cfg_g),
                    ("edge", dataclasses.replace(cfg_g,
                                                 secagg="pairwise")),
                    ("masked_geom", dataclasses.replace(
                        cfg_g, secagg="pairwise",
                        secagg_mode="masked_geom"))):
        acc, disp, _, _ = run_one(c, scenario="churn_signflip", d=data_g)
        att[mode] = dict(acc=acc, dispatches=disp)
    mg_delta = att["edge"]["acc"] - att["masked_geom"]["acc"]

    print(f"secagg clean: unmasked {clean_acc:.3f} vs masked {sec_acc:.3f}"
          f" (delta {abs(clean_acc - sec_acc):.4f}); dp_sigma=1.0 "
          f"{dp_acc:.3f}; dispatches {d0}/{d1}/{d2} (budget {budget})")
    print(f"secagg mask bytes: {n_edges} directed edges, ok="
          f"{mask_bytes_ok} " + " ".join(
              f"{k}={v['realized_bytes'] / 1e6:.2f}MB"
              for k, v in mask_rows.items()))
    print(f"secagg masked_geom churn_signflip: plain "
          f"{att['plain']['acc']:.3f} edge {att['edge']['acc']:.3f} "
          f"masked_geom {att['masked_geom']['acc']:.3f} "
          f"(delta {mg_delta:+.3f})")
    return dict(
        epochs=epochs, eval_every=eval_every, dispatch_budget=budget,
        clean_acc=clean_acc, secagg_acc=sec_acc,
        clean_delta=abs(clean_acc - sec_acc), dp_acc=dp_acc,
        dispatches=dict(clean=d0, secagg=d1, dp=d2),
        n_params=n_params, directed_edges=n_edges, mask_bytes=mask_rows,
        mask_bytes_ok=bool(mask_bytes_ok), attacked=att,
        masked_geom_delta=mg_delta,
        dp_epsilon={f"{s:g}": dp_epsilon(s, epochs)
                    for s in (0.5, 1.0, 2.0)})


def bench_w_scaling():
    """Worker-axis scaling rows, CI-gated by bench_guard: the sharded
    transport (``core.gossip.mix_pytree_sharded`` — per-shard padded-CSR
    local blocks + block-granular cross-shard ppermute ring) across
    W ∈ {500, 2k, 10k} × shards ∈ {1, 4, 8}, plus the sharded ENGINE's
    dispatch-parity check at W=500 per shard count.

    Each row records the per-round transport wall time, the realized
    cross-shard ring bytes, and ``ring_bytes_ok`` — the transport's
    ``WorkerShardPlan.ring_bytes`` must equal the independent
    ``launch.roofline.sharded_ring_bytes`` re-derivation (the contract
    the dry-run cost column prints). Numerics: every shard count must
    agree with the single-shard mix at the same W.

    The sweep runs in this process on the devices present, over the
    shard counts they allow (run as a script on the CPU, this module
    forces 8 host devices before JAX starts); wall times are best-of-3,
    regression trajectories on the platform that ran them."""
    from repro.config import DeFTAConfig, TrainConfig
    from repro.core.defta import run_defta
    from repro.core.gossip import mix_pytree_sharded, worker_shard_plan
    from repro.core.tasks import mlp_task
    from repro.core.topology import make_topology
    from repro.data.synthetic import federated_dataset
    from repro.launch.roofline import sharded_ring_bytes
    from repro.sharding import WorkerShards, worker_mesh
    from repro.telemetry import RunLedger

    n_dev = len(jax.devices())
    shard_counts = [s for s in (1, 4, 8) if s <= n_dev]
    skipped = [s for s in (1, 4, 8) if s > n_dev]
    if skipped:
        print(f"w_scaling: {n_dev} device(s) present, shard counts "
              f"{skipped} skipped; the sharded ring-byte and dispatch "
              f"contracts are checked only for shards={shard_counts}")
    F = 256
    rows = []
    for w in (500, 2000, 10000):
        adj = make_topology("random_kout", w, 4, seed=0)
        P = (adj | np.eye(w, dtype=bool)).astype(np.float32)
        P = jnp.asarray(P / P.sum(1, keepdims=True))
        stack = {"p": jax.random.normal(jax.random.PRNGKey(w), (w, F))}
        base = None                     # the shards=1 mix at this W
        for shards in shard_counts:
            shard = WorkerShards(mesh=worker_mesh(shards))
            plan = worker_shard_plan(adj, shards)
            roof = sharded_ring_bytes(F, adj, shards, None, rows=1)

            def mix(P_, s_, _mesh=shard.mesh, _ax=shard.axis):
                return mix_pytree_sharded(P_, s_, _mesh, axis=_ax,
                                          adjacency=adj)
            fn = jax.jit(mix)
            out = jax.block_until_ready(fn(P, stack))
            best = float("inf")
            for _ in range(3):
                t0 = time.time()
                jax.block_until_ready(fn(P, stack))
                best = min(best, time.time() - t0)
            # pull to host: shard-count runs live on different device
            # sets, jnp ops across them are rejected
            out = np.asarray(jax.device_get(out["p"]))
            if base is None:
                base = out
            err = float(np.max(np.abs(out - base)))
            rows.append(dict(
                W=w, shards=shards, mix_ms=best * 1e3,
                ring_bytes=float(plan.ring_bytes(F)),
                ring_bytes_ok=bool(
                    plan.ring_bytes(F) == roof["ring_bytes"]),
                bytes_per_boundary=roof["bytes_per_boundary"],
                used_pairs=roof["used_pairs"],
                intra_edges=roof["intra_edges"],
                cross_edges=roof["cross_edges"],
                err_vs_single_shard=err))
            assert err < 5e-5, (w, shards, err)

    # engine dispatch parity per shard count: a 2-epoch W=500 run with
    # eval_every=2 is ONE dispatch, sharded or not
    w = 500
    cfg = DeFTAConfig(num_workers=w, avg_peers=4, num_sampled=2,
                      local_epochs=1)
    train = TrainConfig(learning_rate=0.05, batch_size=16)
    data = federated_dataset("vector", w, np.random.default_rng(0),
                             n_per_worker=16, alpha=0.5)
    task = mlp_task(32, 10)
    engine = []
    for shards in shard_counts:
        led = RunLedger()
        run_defta(jax.random.PRNGKey(0), task, cfg, train, data, epochs=2,
                  eval_every=2, ledger=led,
                  shards=None if shards == 1 else shards)
        engine.append(dict(W=w, shards=shards, epochs=2,
                           dispatches=led.dispatches, dispatch_budget=1,
                           wall_s=led.wall_s, round_s=led.wall_s / 2))
    payload = dict(feature_dim=F, avg_peers=4, rows=rows, engine=engine,
                   skipped_shard_counts=skipped)
    for row in payload["rows"]:
        print(f"w_scaling W={row['W']:6d} shards={row['shards']} "
              f"mix={row['mix_ms']:8.1f}ms ring="
              f"{row['ring_bytes'] / 1e6:7.2f}MB "
              f"({row['used_pairs']:2d} pairs, {row['cross_edges']:6d} "
              f"cross edges) err={row['err_vs_single_shard']:.1e} "
              f"roofline_ok={row['ring_bytes_ok']}")
    for e in payload["engine"]:
        print(f"w_scaling engine W={e['W']} shards={e['shards']}: "
              f"{e['dispatches']} dispatches (budget "
              f"{e['dispatch_budget']}), {e['round_s']:.2f}s/round")
    return payload


def run():
    key = jax.random.PRNGKey(0)
    rows = []

    P = jax.nn.softmax(jax.random.normal(key, (20, 20)), -1)
    w = jax.random.normal(key, (20, 1 << 16))
    rows.append(("gossip_mix_20x65k", _time(gossip_mix, P, w),
                 _time(gossip_mix_ref, P, w)))

    q = jax.random.normal(key, (1, 4, 512, 64))
    rows.append(("flash_attention_512", _time(flash_attention, q, q, q),
                 _time(flash_attention_ref, q, q, q)))

    logits = jax.random.normal(key, (2048, 64))
    rows.append(("moe_router_2048x64",
                 _time(lambda x: moe_router_topk(x, 6), logits),
                 _time(lambda x: moe_router_topk_ref(x, 6), logits)))

    from repro.kernels.ops import ssd_chunk
    from repro.kernels.ref import ssd_chunk_ref
    g, h, t, n, p2 = 4, 4, 128, 64, 64
    C = jax.random.normal(key, (g, t, n))
    B2 = jax.random.normal(jax.random.fold_in(key, 1), (g, t, n))
    ac = -jnp.abs(jax.random.normal(key, (g, h, t))).cumsum(-1)
    dt = jax.nn.softplus(jax.random.normal(key, (g, h, t)))
    xx = jax.random.normal(key, (g, h, t, p2))
    rows.append(("ssd_chunk_4x4x128",
                 _time(ssd_chunk, C, B2, ac, dt, xx),
                 _time(ssd_chunk_ref, C, B2, ac, dt, xx)))

    for name, us, ref_us in rows:
        print(f"kernel {name}: {us:.0f}us (ref {ref_us:.0f}us)")
    return [dict(name=n, us_per_call=u, ref_us=r) for n, u, r in rows]


if __name__ == "__main__":
    from repro.cache import enable_compile_cache
    enable_compile_cache()
    run()
    bench_gossip()
