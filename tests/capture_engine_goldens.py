"""Capture golden reference outputs for the unified-engine parity gate.

Run ONCE against the pre-refactor engines (PR 3 state) to freeze their
fixed-seed outputs; ``tests/test_engine.py`` then asserts the unified
round-program engine reproduces them bit-identically:

    PYTHONPATH=src python tests/capture_engine_goldens.py

Writes ``tests/golden_engine.json``. The digests are exact float64 sums of
float32 state — any reordering of the round's ops changes them, so equality
really is bit-identity of the state tensors (summation order is fixed).
"""
from __future__ import annotations

import json
import os

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


def main():
    goldens = capture()
    with open(OUT, "w") as fh:
        json.dump(goldens, fh, indent=1, sort_keys=True)
    print(f"wrote {OUT}")
    for k, v in goldens.items():
        print(f"  {k}: {str(v)[:100]}...")


if __name__ == "__main__":
    main()
