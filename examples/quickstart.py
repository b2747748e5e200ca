"""Quickstart: DeFTA in ~60 lines — 8 workers, non-iid data, one malicious
actor, DeFTA vs FedAvg vs DeFL.

    PYTHONPATH=src python examples/quickstart.py
"""
import dataclasses

import jax
import numpy as np

from repro.cache import enable_compile_cache
from repro.config import DeFTAConfig, TrainConfig
from repro.core.defta import evaluate, run_defta
from repro.core.fedavg import evaluate_server, run_fedavg
from repro.core.tasks import mlp_task
from repro.data.synthetic import federated_dataset


def main():
    # 1. a federated dataset: 8 workers, Dirichlet non-iid label split,
    #    heterogeneous |D_i| (that heterogeneity is what DeFTA's
    #    outdegree-corrected weights are for).
    rng = np.random.default_rng(0)
    data = federated_dataset("vector", num_workers=8, rng=rng,
                             n_per_worker=150)
    print("worker dataset sizes:", data["sizes"].tolist())

    # 2. a local task (the paper's MLP class) and the DeFTA knobs
    task = mlp_task(input_dim=32, num_classes=10)
    cfg = DeFTAConfig(num_workers=8, avg_peers=4, num_sampled=2,
                      local_epochs=5)
    train = TrainConfig(learning_rate=0.05, batch_size=32)
    key = jax.random.PRNGKey(0)
    tx, ty = data["test_x"], data["test_y"]

    # 3. DeFTA (decentralized, trustless)
    state, adj, malicious, _ = run_defta(key, task, cfg, train, data,
                                         epochs=30, num_malicious=1)
    m, s, _ = evaluate(task, state, tx, ty, malicious)
    print(f"DeFTA   (+1 malicious): {m:.3f} ± {s:.3f}")

    # 4. baselines: FedAvg (collapses under attack), DeFL (no defense)
    st = run_fedavg(key, task, cfg, train, data, epochs=30, num_malicious=1)
    print(f"FedAvg  (+1 malicious): {evaluate_server(task, st, tx, ty):.3f}")

    cfg_defl = dataclasses.replace(cfg, aggregation="defl", use_dts=False)
    st2, _, mal2, _ = run_defta(key, task, cfg_defl, train, data, epochs=30,
                                num_malicious=1)
    m2, s2, _ = evaluate(task, st2, tx, ty, mal2)
    print(f"DeFL    (+1 malicious): {m2:.3f} ± {s2:.3f}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
