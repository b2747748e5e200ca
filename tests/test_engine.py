"""Unified round-program engine tests.

* Golden parity: the engine's stage pipelines reproduce the PRE-REFACTOR
  ``run_defta`` / ``run_async_defta`` / ``run_fedavg`` outputs
  BIT-IDENTICALLY at fixed seed (``golden_engine.json`` was captured from
  the PR-3 engines before the refactor), dispatch counts included.
* Stage introspection: each mode is the documented stage selection.
* FedAvg on the unified driver: dispatch accounting + superstep == loop.
* Time-varying topologies: per-segment regenerated adjacency
  (``TopologySpec``) with the support-union padded-CSR contract.
* Multi-pod: the pod round program end-to-end on a 2×2(×pods) host-local
  mesh via ``train.py --fl --scenario`` (subprocess, like
  test_distributed).
"""
import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from capture_engine_goldens import (ROUND_GOLDENS, defta_state_digest, setup,
                                    tree_digest)
from repro.config import DeFTAConfig, TrainConfig
from repro.core.async_defta import run_async_defta
from repro.core.defta import run_defta
from repro.core.fedavg import evaluate_server, run_fedavg

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

# golden / assert_golden / env / trees_bit_equal fixtures: tests/conftest.py


# ---------------------------------------------------------------------------
# Golden parity (bit-identical vs the pre-refactor engines)
# ---------------------------------------------------------------------------

def test_golden_defta_static(env, assert_golden):
    data, task, cfg, train = env
    stats = {}
    st, _, _, _ = run_defta(jax.random.PRNGKey(0), task, cfg, train, data,
                            epochs=6, stats=stats)
    assert_golden("defta_static", defta_state_digest(st, stats))


def test_golden_defta_scenario(env, assert_golden):
    data, task, cfg, train = env
    stats = {}
    st, _, _, _ = run_defta(jax.random.PRNGKey(0), task, cfg, train, data,
                            epochs=6, scenario="churn_signflip",
                            eval_every=3, test_x=data["test_x"],
                            test_y=data["test_y"], stats=stats)
    assert_golden("defta_scenario", defta_state_digest(st, stats))


def test_golden_defta_int8_ef(env, assert_golden):
    data, task, cfg, train = env
    cfg_q = dataclasses.replace(cfg, gossip_dtype="int8")
    stats = {}
    st, _, _, _ = run_defta(jax.random.PRNGKey(0), task, cfg_q, train,
                            data, epochs=6, gossip_backend="auto",
                            stats=stats)
    assert_golden("defta_int8_ef", defta_state_digest(st, stats))


def test_golden_async_target(env, assert_golden):
    data, task, cfg, train = env
    stats = {}
    st, _, _, _ = run_async_defta(jax.random.PRNGKey(0), task, cfg, train,
                                  data, ticks=10, target_epochs=3,
                                  stats=stats)
    assert_golden("async_target", defta_state_digest(st, stats))


def test_golden_async_scenario(env, assert_golden):
    data, task, cfg, train = env
    stats = {}
    st, _, _, _ = run_async_defta(jax.random.PRNGKey(0), task, cfg, train,
                                  data, ticks=8,
                                  scenario="churn_signflip", stats=stats)
    assert_golden("async_scenario", defta_state_digest(st, stats))


def test_golden_fedavg_variants(env, assert_golden):
    data, task, cfg, train = env
    st = run_fedavg(jax.random.PRNGKey(0), task, cfg, train, data,
                    epochs=4)
    assert_golden("fedavg", {"server": tree_digest(st.server)})
    st = run_fedavg(jax.random.PRNGKey(0), task, cfg, train, data,
                    epochs=4, num_malicious=1, server_opt="fedadam")
    assert_golden("fedavg_fedadam", {"server": tree_digest(st.server)})
    st = run_fedavg(jax.random.PRNGKey(0), task, cfg, train, data,
                    epochs=4, sample_workers=2)
    assert_golden("fedavg_sampled", {"server": tree_digest(st.server)})


@pytest.mark.parametrize("name", ["pod_round_loss", "pod_round_all",
                                  "pod_round_nodts", "defta_all_storm",
                                  "defta_masked_geom"])
def test_golden_round_stages(name, assert_golden):
    """The pod round and the dense round's trust channels reproduce the
    state captured on the engine before the three round builders shared
    their peer-sample, mixing and confidence-update code: key, counters,
    confidences, backups, EF residuals, sketches and params, bit for
    bit."""
    assert_golden(name, ROUND_GOLDENS[name]())


# ---------------------------------------------------------------------------
# Stage introspection: each mode is a documented stage selection
# ---------------------------------------------------------------------------

def test_stage_selections(env):
    from repro.core.engine import (build_defta_round, build_fedavg_round,
                                   build_pod_round, make_transport,
                                   stage_names)
    data, task, cfg, train = env
    w = cfg.num_workers
    adj = np.eye(w, k=1, dtype=bool) | np.eye(w, k=-1, dtype=bool)
    sizes = np.full(w, 64)
    mal = np.zeros(w, bool)

    rnd = build_defta_round(task, cfg, train, adj, sizes, mal)
    assert stage_names(rnd) == (
        "split_keys", "scenario_view", "peer_sample", "transport",
        "damage_check", "local_train", "attack_inject", "trust_update",
        "finalize")

    from repro.core.defta import resolve_scenario
    scn = resolve_scenario("churn_signflip", cfg, 4)
    rnd_s = build_defta_round(task, cfg, train,
                              np.ones((scn.num_workers,) * 2, bool)
                              ^ np.eye(scn.num_workers, dtype=bool),
                              np.full(scn.num_workers, 64),
                              scn.malicious, scenario=scn, num_classes=10)
    assert stage_names(rnd_s)[-1] == "fire_merge"

    fed = build_fedavg_round(task, cfg, train, sizes, mal)
    assert stage_names(fed) == (
        "split_keys", "star_broadcast", "local_train", "attack_inject",
        "star_aggregate", "server_update")

    tr = make_transport(cfg, adjacency=adj)
    pod = build_pod_round(cfg, w, sizes, transport=tr, adj=adj)
    assert "damage_check" not in stage_names(pod)     # no time machine
    assert stage_names(pod)[:4] == (
        "split_keys", "scenario_view", "peer_sample", "transport")


# ---------------------------------------------------------------------------
# FedAvg on the unified driver
# ---------------------------------------------------------------------------

def test_fedavg_superstep_dispatch_accounting(env):
    data, task, cfg, train = env
    stats = {}
    st = run_fedavg(jax.random.PRNGKey(0), task, cfg, train, data,
                    epochs=6, stats=stats)
    assert stats == {"dispatches": 1, "epochs": 6}
    stats_d = {}
    run_defta(jax.random.PRNGKey(0), task, cfg, train, data, epochs=6,
              stats=stats_d)
    # dispatch parity with the DeFTA engines for the same run shape
    assert stats["dispatches"] == stats_d["dispatches"]
    # and the per-epoch reference loop reproduces the fused run exactly
    st_ref = run_fedavg(jax.random.PRNGKey(0), task, cfg, train, data,
                        epochs=6, superstep=False)
    for a, b in zip(jax.tree.leaves(st.server),
                    jax.tree.leaves(st_ref.server)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_fedavg_eval_history(env):
    data, task, cfg, train = env
    stats = {}
    run_fedavg(jax.random.PRNGKey(0), task, cfg, train, data, epochs=6,
               eval_every=3, test_x=data["test_x"],
               test_y=data["test_y"], stats=stats)
    assert stats["dispatches"] == 2
    assert [e for e, _ in stats["history"]] == [3, 6]


# ---------------------------------------------------------------------------
# Time-varying topologies (TopologySpec)
# ---------------------------------------------------------------------------

def _tv_spec(every=0):
    from repro.scenarios import (AttackSpec, ChurnSpec, ScenarioSpec,
                                 TopologySpec)
    return ScenarioSpec(
        name="tv", attacks=(AttackSpec("sign_flip"),),
        churn=(ChurnSpec(worker=0, leave=3),),
        topology=TopologySpec(kind="random_kout", avg_peers=2,
                              every=every),
        seed=3)


def test_time_varying_topology_compiles_distinct_segments():
    from repro.scenarios import compile_scenario
    scn = compile_scenario(_tv_spec(), 4, 6)
    assert scn.adj_seg is not None and scn.num_segments >= 2
    a = np.asarray(scn.adj_seg_np)
    # rekeyed draws: at least one pair of segments differs
    assert any(not np.array_equal(a[0], a[s])
               for s in range(1, scn.num_segments))
    # support union covers every segment
    assert (a.any(0) == scn.adj_union).all()
    # epoch_view surfaces the segment's adjacency
    from repro.scenarios import epoch_view
    v0 = epoch_view(scn, 0)
    assert v0["adj"].shape == (scn.num_workers, scn.num_workers)


def test_time_varying_topology_every_forces_segments():
    from repro.scenarios import compile_scenario
    spec = dataclasses.replace(_tv_spec(every=2), churn=())
    scn = compile_scenario(spec, 4, 6)
    # no churn/link events: segments exist purely from the every=2 re-draw
    assert scn.num_segments == 3


def test_time_varying_topology_runs_and_support_union_memo(env):
    data, task, cfg, train = env
    from repro.core.gossip import SUPPORT_CACHE_STATS
    before = dict(SUPPORT_CACHE_STATS)
    stats = {}
    st, _, mal, _ = run_defta(jax.random.PRNGKey(0), task, cfg, train,
                              data, epochs=6, scenario=_tv_spec(),
                              gossip_backend="sparse", stats=stats)
    # scenarios stay data: dispatch count matches a static run
    assert stats["dispatches"] == 1
    assert all(bool(jnp.isfinite(x).all())
               for x in jax.tree.leaves(st.params))
    # the sparse backend keyed ONE support (the union), not one per epoch
    assert SUPPORT_CACHE_STATS["misses"] - before["misses"] <= 1


def test_time_varying_topology_learns(env):
    data, task, cfg, train = env
    from repro.core.defta import evaluate
    spec = dataclasses.replace(_tv_spec(), attacks=())   # clean run: the
    # regenerated topology itself must not break convergence
    st, _, mal, _ = run_defta(jax.random.PRNGKey(0), task, cfg, train,
                              data, epochs=16, scenario=spec)
    m, _, _ = evaluate(task, st, data["test_x"], data["test_y"], mal)
    assert m > 0.3, m


def test_dynamic_mixing_matrix_matches_static_reference():
    """The engine's traced per-round P (gossip.dynamic_mixing_matrix)
    reproduces the host-side np.float64 reference
    (aggregation.sampled_mixing_matrix) on a static topology."""
    from repro.core.aggregation import sampled_mixing_matrix
    from repro.core.gossip import dynamic_mixing_matrix
    from repro.core.topology import make_topology

    rng = np.random.default_rng(0)
    w = 8
    adj = make_topology("random_kout", w, 3, seed=1)
    sizes = rng.integers(10, 100, w)
    sampled = rng.random((w, w)) < 0.5
    for scheme in ("defta", "defl", "uniform"):
        ref = sampled_mixing_matrix(adj, sizes, sampled, scheme)
        dyn = np.asarray(dynamic_mixing_matrix(
            jnp.asarray(sampled & adj), jnp.asarray(adj),
            jnp.asarray(sizes, jnp.float32), scheme))
        np.testing.assert_allclose(dyn, ref, atol=1e-6, err_msg=scheme)


# ---------------------------------------------------------------------------
# Pod round program (in_jit transport — single device)
# ---------------------------------------------------------------------------

def test_pod_round_program_in_jit(env):
    from repro.core.engine import (build_pod_round, init_pod_state,
                                   make_transport)
    from repro.core.topology import make_topology

    pods = 4
    cfg = DeFTAConfig(num_workers=pods, avg_peers=pods - 1,
                      num_sampled=2, topology="dense", use_dts=True,
                      time_machine=False, gossip_dtype="int8")
    adj = make_topology("dense", pods, pods - 1)
    sizes = np.full(pods, 8)
    tr = make_transport(cfg, backend="auto", adjacency=adj)
    rnd = build_pod_round(cfg, pods, sizes, transport=tr, adj=adj)
    params = {"w": jax.random.normal(jax.random.PRNGKey(0), (pods, 16))}
    pstate = init_pod_state(jax.random.PRNGKey(1), pods, params,
                            wire_error=True)
    losses = jnp.asarray([1.0, 2.0, 0.5, 1.5])
    rnd_j = jax.jit(rnd)
    pstate, params = rnd_j(pstate, params, losses)
    assert int(pstate.round) == 1
    assert pstate.last_loss.tolist() == losses.tolist()
    # int8+EF: residual buffers advanced
    assert float(jnp.abs(pstate.wire_err["w"]).max()) > 0
    # a second round consumes the state cleanly
    pstate, params = rnd_j(pstate, params, losses)
    assert int(pstate.round) == 2
    assert bool(jnp.isfinite(params["w"]).all())


def test_pod_round_scenario_honest_pods_adopt_aggregate():
    """Regression: with a scenario attached, honest pods must ADOPT the
    gossip aggregate (an earlier cut left them on their pre-mix params —
    gossip silently became a no-op for every non-attacking pod)."""
    from repro.core.engine import (build_pod_round, init_pod_state,
                                   make_transport)
    from repro.core.gossip import dynamic_mixing_matrix, mix_pytree
    from repro.core.topology import make_topology
    from repro.scenarios import AttackSpec, ScenarioSpec, compile_scenario

    pods = 4
    cfg = DeFTAConfig(num_workers=pods, avg_peers=pods - 1, num_sampled=2,
                      topology="dense", use_dts=False, time_machine=False)
    adj = make_topology("dense", pods, pods - 1)
    scn = compile_scenario(
        ScenarioSpec(name="p", attacks=(AttackSpec("sign_flip",
                                                   worker=3),)),
        pods, 4)
    tr = make_transport(cfg, adjacency=adj)
    rnd = jax.jit(build_pod_round(cfg, pods, np.full(pods, 8),
                                  transport=tr, adj=adj, scenario=scn))
    params = {"w": jax.random.normal(jax.random.PRNGKey(0), (pods, 16))}
    pstate = init_pod_state(jax.random.PRNGKey(1), pods, params)
    _, out = rnd(pstate, params, jnp.zeros((pods,)))

    # expected aggregate: no DTS -> every pod listens to all live peers
    adj_j = jnp.asarray(adj)
    P = dynamic_mixing_matrix(adj_j, adj_j, jnp.full((pods,), 8.0),
                              "defta")
    agg = mix_pytree(P, params, adjacency=adj)
    np.testing.assert_allclose(np.asarray(out["w"][:3]),
                               np.asarray(agg["w"][:3]), atol=1e-6)
    # ... and the attacker ships the sign-flipped send, not the aggregate
    assert float(jnp.abs(out["w"][3] - agg["w"][3]).max()) > 1e-3


def test_pod_round_program_robust_rule(env):
    from repro.core.engine import (build_pod_round, init_pod_state,
                                   make_transport)
    from repro.core.topology import make_topology

    pods = 4
    cfg = DeFTAConfig(num_workers=pods, avg_peers=pods - 1,
                      num_sampled=2, topology="dense", use_dts=False,
                      time_machine=False, aggregation="median")
    adj = make_topology("dense", pods, pods - 1)
    tr = make_transport(cfg, adjacency=adj,
                        robust=True)
    rnd = jax.jit(build_pod_round(cfg, pods, np.full(pods, 8),
                                  transport=tr, adj=adj))
    params = {"w": jnp.stack([jnp.full((8,), v)
                              for v in (1.0, 2.0, 3.0, 100.0)])}
    pstate = init_pod_state(jax.random.PRNGKey(1), pods, params)
    pstate, mixed = rnd(pstate, params, jnp.zeros((pods,)))
    # the median rule ignores the outlier pod
    assert float(jnp.abs(mixed["w"]).max()) < 10.0


# ---------------------------------------------------------------------------
# Multi-pod end-to-end smoke (2×2(×pods) host-local mesh)
# ---------------------------------------------------------------------------

def test_train_fl_scenario_multipod_smoke():
    """train.py --fl --scenario on a 2x2(x4 pods) host-local mesh with the
    quantized wire + ppermute ring — the acceptance smoke."""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--fl", "--pods",
         "4", "--steps", "2", "--gossip-every", "1", "--debug-mesh",
         "--smoke", "--scenario", "churn_signflip", "--gossip-wire",
         "int8", "--transport", "ppermute"],
        capture_output=True, text=True, timeout=520, env=env)
    assert r.returncode == 0, r.stdout + "\n" + r.stderr
    assert "transport=ppermute wire=int8" in r.stdout, r.stdout
    assert "[gossip]" in r.stdout
