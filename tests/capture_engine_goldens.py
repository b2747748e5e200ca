"""Capture golden reference outputs for the unified-engine parity gate.

Run against the engine an entry must stay bit-identical to, to freeze
its fixed-seed outputs; ``tests/test_engine.py`` and
``tests/test_cross_device.py`` then assert that the engine reproduces them
bit-identically:

    PYTHONPATH=src python tests/capture_engine_goldens.py [NAME ...]

With names, only those entries are captured and merged into
``tests/golden_engine.json``; without, every entry is captured. The first
eight (``capture``) date from the engines before the round program was
unified; the pod-round, cross-device and trust-channel entries
(``ROUND_GOLDENS``) from the engine before the three round builders shared
their stages. The digests are exact float64 sums of float32 state — any
reordering of the round's ops changes them, so equality really is
bit-identity of the state tensors (summation order is fixed).
"""
from __future__ import annotations

import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import DeFTAConfig, TrainConfig
from repro.core.async_defta import run_async_defta
from repro.core.defta import run_defta
from repro.core.fedavg import run_fedavg
from repro.core.tasks import mlp_task
from repro.data.synthetic import federated_dataset

OUT = os.path.join(os.path.dirname(__file__), "golden_engine.json")


def tree_digest(tree):
    """Order-fixed exact digest: per-leaf float64 sum + abs-sum."""
    leaves = jax.tree.leaves(tree)
    return [[float(np.asarray(x, np.float64).sum()),
             float(np.abs(np.asarray(x, np.float64)).sum())]
            for x in leaves]


def setup(w=4):
    data = federated_dataset("vector", w, np.random.default_rng(0),
                             n_per_worker=64, alpha=0.5)
    task = mlp_task(32, 10)
    cfg = DeFTAConfig(num_workers=w, avg_peers=2, num_sampled=1,
                      local_epochs=2)
    train = TrainConfig(learning_rate=0.05, batch_size=32)
    return data, task, cfg, train


def defta_state_digest(st, stats=None):
    d = {
        "last_loss": [float(x) for x in np.asarray(st.last_loss)],
        "best_loss": [float(x) for x in np.asarray(st.best_loss)],
        "epoch": [int(x) for x in np.asarray(st.epoch)],
        "conf_sum": float(np.asarray(st.conf, np.float64).sum()),
        "params": tree_digest(st.params),
        "backup": tree_digest(st.backup),
    }
    if st.wire_err is not None:
        d["wire_err"] = tree_digest(st.wire_err)
    if stats is not None:
        d["dispatches"] = stats["dispatches"]
    return d


def capture() -> dict:
    """Every golden entry, computed by the engines as they stand."""
    import dataclasses
    goldens = {}
    data, task, cfg, train = setup()

    # 1. sync DeFTA, static topology, superstep driver
    stats = {}
    st, _, _, _ = run_defta(jax.random.PRNGKey(0), task, cfg, train, data,
                            epochs=6, stats=stats)
    goldens["defta_static"] = defta_state_digest(st, stats)

    # 2. sync DeFTA + scenario (churn + sign_flip) with eval chunking
    stats = {}
    st, _, _, _ = run_defta(jax.random.PRNGKey(0), task, cfg, train, data,
                            epochs=6, scenario="churn_signflip",
                            eval_every=3, test_x=data["test_x"],
                            test_y=data["test_y"], stats=stats)
    goldens["defta_scenario"] = defta_state_digest(st, stats)

    # 3. sync DeFTA on the int8+EF wire, sparse backend
    cfg_q = dataclasses.replace(cfg, gossip_dtype="int8")
    stats = {}
    st, _, _, _ = run_defta(jax.random.PRNGKey(0), task, cfg_q, train, data,
                            epochs=6, gossip_backend="auto", stats=stats)
    goldens["defta_int8_ef"] = defta_state_digest(st, stats)

    # 4. async DeFTA, device-side early exit (the while_loop path)
    stats = {}
    st, _, _, _ = run_async_defta(jax.random.PRNGKey(0), task, cfg, train,
                                  data, ticks=10, target_epochs=3,
                                  stats=stats)
    goldens["async_target"] = defta_state_digest(st, stats)

    # 5. async DeFTA, untargeted single scan + scenario
    stats = {}
    st, _, _, _ = run_async_defta(jax.random.PRNGKey(0), task, cfg, train,
                                  data, ticks=8, scenario="churn_signflip",
                                  stats=stats)
    goldens["async_scenario"] = defta_state_digest(st, stats)

    # 6. FedAvg (CFL-F) and FedAdam server optimizer
    st = run_fedavg(jax.random.PRNGKey(0), task, cfg, train, data, epochs=4)
    goldens["fedavg"] = {"server": tree_digest(st.server)}
    st = run_fedavg(jax.random.PRNGKey(0), task, cfg, train, data, epochs=4,
                    num_malicious=1, server_opt="fedadam")
    goldens["fedavg_fedadam"] = {"server": tree_digest(st.server)}
    st = run_fedavg(jax.random.PRNGKey(0), task, cfg, train, data, epochs=4,
                    sample_workers=2)
    goldens["fedavg_sampled"] = {"server": tree_digest(st.server)}
    return goldens


def state_digest(st):
    """Every non-None field of a state dataclass, each leaf digested —
    the key, counters, confidences and sketch history included."""
    return {f.name: tree_digest(getattr(st, f.name))
            for f in dataclasses.fields(st)
            if getattr(st, f.name) is not None}


# ---------------------------------------------------------------------------
# Pod round: the cross-silo gossip round over stacked pod params
# ---------------------------------------------------------------------------

def _pod_loss(stacked, target):
    """Per-pod quadratic loss against per-pod targets: [pods]."""
    return sum(((x - t) ** 2).reshape(x.shape[0], -1).mean(axis=1)
               for x, t in zip(jax.tree.leaves(stacked),
                               jax.tree.leaves(target)))


def pod_round_run(cfg, scenario: str, rounds: int, *,
                  time_machine: bool = False, start: bool = False):
    """``rounds`` gossip rounds of ``engine.build_pod_round`` (in-jit
    einsum transport) on a dense pod graph, each preceded by a
    deterministic "local step" toward per-pod targets. Returns the pod
    state and the stacked params the last round adopted."""
    from repro.core.defta import resolve_scenario
    from repro.core.engine import (build_pod_round, init_pod_state,
                                   make_transport, sketch_shape)
    from repro.core.gossip import uses_error_feedback
    from repro.core.topology import make_topology

    scn = resolve_scenario(scenario, cfg, rounds)
    pods = scn.num_workers
    adj = make_topology("dense", pods, pods - 1)
    support = scn.adj_union if scn.adj_union is not None else adj
    tr = make_transport(cfg, backend="einsum", adjacency=support)
    k = jax.random.split(jax.random.PRNGKey(0), 4)
    params = {"b": jax.random.normal(k[0], (pods, 5)),
              "w": jax.random.normal(k[1], (pods, 6, 5))}
    target = {"b": jax.random.normal(k[2], (pods, 5)),
              "w": jax.random.normal(k[3], (pods, 6, 5))}
    held = jax.tree.map(lambda t: t[::-1], target)
    self_eval = (lambda stacked: _pod_loss(stacked, held)) \
        if time_machine else None
    rnd = jax.jit(build_pod_round(cfg, pods, np.arange(pods) + 8.0,
                                  transport=tr, adj=adj, scenario=scn,
                                  self_eval=self_eval))
    pstate = init_pod_state(jax.random.PRNGKey(1), pods, params,
                            wire_error=uses_error_feedback(cfg),
                            time_machine=time_machine,
                            sketch=sketch_shape(cfg))
    local = jax.jit(lambda p: jax.tree.map(
        lambda x, t: 0.7 * x + 0.3 * t, p, target))
    for _ in range(rounds):
        departed = params
        params = local(params)
        losses = jax.jit(_pod_loss)(params, target)
        pstate, params = rnd(pstate, params, losses,
                             departed if start else None)
    return pstate, params


def _pod_entry(cfg_kw, scenario, vanilla, rounds, **kw):
    cfg = DeFTAConfig(num_workers=vanilla, avg_peers=2, num_sampled=2,
                      topology="dense", **cfg_kw)
    pstate, params = pod_round_run(cfg, scenario, rounds, **kw)
    return dict(state_digest(pstate), params=tree_digest(params))


# ---------------------------------------------------------------------------
# Cross-device round: gather -> cohort block -> scatter
# ---------------------------------------------------------------------------

def cross_device_run(cfg_kw, spec_kw, epochs: int, enrolled: int = 14,
                     k: int = 5):
    from repro.core.cross_device import run_cross_device
    from repro.scenarios.cross_device import CrossDeviceSpec

    task = mlp_task(8, 4, hidden=16)
    data = federated_dataset("vector", enrolled, np.random.default_rng(3),
                             n_per_worker=24, dim=8, num_classes=4)
    train = TrainConfig(learning_rate=0.05, batch_size=8)
    cfg = DeFTAConfig(num_workers=enrolled, avg_peers=2, num_sampled=2,
                      local_epochs=1, seed=0, **cfg_kw)
    spec = CrossDeviceSpec(enrolled=enrolled, sample_k=k, avg_peers=2,
                           seed=2, **spec_kw)
    st, _ = run_cross_device(jax.random.PRNGKey(0), task, cfg, train, data,
                             world=spec, epochs=epochs)
    return state_digest(st)


def _defta_entry(cfg_kw, scenario, epochs=6):
    data, task, cfg, train = setup()
    cfg = dataclasses.replace(cfg, **cfg_kw)
    st, _, _, _ = run_defta(jax.random.PRNGKey(0), task, cfg, train, data,
                            epochs=epochs, scenario=scenario)
    return state_digest(st)


ROUND_GOLDENS = {
    # the pod round: int8 + EF21, pod DTS on the loss delta, churn and
    # sign-flippers, the time machine with a held-out self-eval
    "pod_round_loss": lambda: _pod_entry(
        dict(gossip_dtype="int8", dts_signal="loss", time_machine=True),
        "churn_signflip", 2, 8, time_machine=True),
    # every trust channel on the true local deltas (start_params), under
    # churn, a partition, a straggler and three attack kinds
    "pod_round_all": lambda: _pod_entry(
        dict(dts_signal="all", time_machine=False),
        "storm", 4, 10, start=True),
    # no DTS: pods listen to every live peer; bf16 wire without EF21
    "pod_round_nodts": lambda: _pod_entry(
        dict(use_dts=False, aggregation="uniform", gossip_dtype="bfloat16",
             gossip_error_feedback=False, time_machine=False),
        "churn_signflip", 2, 6),
    # cross-device: label-flip + ALIE attackers, dropout, stragglers,
    # int8 + EF21, loss trust
    "cross_device_loss": lambda: cross_device_run(
        dict(gossip_dtype="int8", dts_signal="loss"),
        dict(availability=0.8, dropout=0.15, straggle=0.2,
             attacks=(("label_flip", 0.2), ("alie", 0.2))), 6),
    # every channel with stamp-aligned correlation, lazy confidence
    # decay, a staleness cap and the k_min identity degrade
    "cross_device_all": lambda: cross_device_run(
        dict(dts_signal="all", dts_conf_decay=0.9, max_staleness=3,
             dts_min_obs=1),
        dict(availability=0.7, dropout=0.1, straggle=0.1, k_min=2,
             attacks=(("alie", 0.25),)), 10),
    # aggregate-only trust under the masked wire, with update-level DP
    "cross_device_masked": lambda: cross_device_run(
        dict(dts_signal="all", secagg="pairwise", secagg_mode="masked_geom",
             dp_sigma=0.5),
        dict(availability=0.9, attacks=(("sign_flip", 0.2),)), 6),
    # the dense round's trust channels: every channel with a staleness
    # cap under the storm scenario, and aggregate-only masked trust
    "defta_all_storm": lambda: _defta_entry(
        dict(dts_signal="all", max_staleness=2), "storm"),
    "defta_masked_geom": lambda: _defta_entry(
        dict(dts_signal="both", secagg="pairwise",
             secagg_mode="masked_geom"), "churn_signflip"),
}


def main(names=()):
    if names:
        with open(OUT) as fh:
            goldens = json.load(fh)
        for name in names:
            goldens[name] = ROUND_GOLDENS[name]()
    else:
        goldens = capture()
        goldens.update({name: run() for name, run in ROUND_GOLDENS.items()})
    with open(OUT, "w") as fh:
        json.dump(goldens, fh, indent=1, sort_keys=True)
    print(f"wrote {OUT}")
    for k in names or goldens:
        print(f"  {k}: {str(goldens[k])[:100]}...")


if __name__ == "__main__":
    main(sys.argv[1:])
