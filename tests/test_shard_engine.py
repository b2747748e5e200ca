"""Sharded-engine parity: the worker-axis-sharded round programs must be
numerically indistinguishable from the single-device engine at matched W.

Every test runs in a forced-8-device CPU subprocess (test_distributed's
``run_py``) and compares a ``shards=...`` run against the plain run of the
SAME driver with the same seed: the sharded transport re-encodes payloads
row-locally and the GSPMD placement only changes layout, so everything
downstream (trust, time machine, evaluation) agrees to float tolerance.

The 10k-worker scale check is gated on ``RUN_SHARD_SCALE=1`` (the shard CI
lane sets it; it is too heavy for the default tier-1 run).
"""
import os

import pytest

from test_distributed import run_py

PARITY_PRELUDE = """
    import warnings
    import jax, jax.numpy as jnp, numpy as np
    from repro.config import DeFTAConfig, TrainConfig
    from repro.core.defta import run_defta
    from repro.core.gossip import SHARD_PARITY_TOL
    from repro.core.tasks import mlp_task
    from repro.data.synthetic import federated_dataset

    TOL, TOL_INT8 = SHARD_PARITY_TOL[None], SHARD_PARITY_TOL["int8"]

    def err(a, b):
        la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
        assert len(la) == len(lb)
        return max(float(jnp.max(jnp.abs(x.astype(jnp.float32) -
                                         y.astype(jnp.float32))))
                   for x, y in zip(la, lb))

    def build(w, n_per_worker=64):
        cfg = DeFTAConfig(num_workers=w, avg_peers=4, num_sampled=2,
                          local_epochs=2)
        train = TrainConfig(learning_rate=0.05, batch_size=32)
        data = federated_dataset("vector", w, np.random.default_rng(0),
                                 n_per_worker=n_per_worker, alpha=0.5)
        return cfg, train, data, mlp_task(32, 10)
"""


def test_sharded_run_matches_single_device():
    """W divisible by the shard count: the full sharded path (row-sharded
    state + local-CSR/ring transport) == the plain engine."""
    run_py(PARITY_PRELUDE + """
        cfg, train, data, task = build(16)
        key = jax.random.PRNGKey(0)
        s0, s1 = {}, {}
        st0, *_ = run_defta(key, task, cfg, train, data, epochs=4,
                            stats=s0)
        st1, *_ = run_defta(key, task, cfg, train, data, epochs=4,
                            stats=s1, shards=4)
        assert s0["dispatches"] == s1["dispatches"] == 1, (s0, s1)
        assert err(st0.params, st1.params) < TOL
        assert err(st0.backup, st1.backup) < TOL
        assert err(st0.conf, st1.conf) < TOL
        assert err(st0.best_loss, st1.best_loss) < TOL
        assert (np.asarray(st0.epoch) == np.asarray(st1.epoch)).all()
        print("ok", err(st0.params, st1.params))
    """)


def test_sharded_secagg_wire_parity():
    """The privacy wire rides the sharded ring (DOMAIN_SHARD pads on the
    ppermute channels): pads cancel edge-exactly across shard boundaries,
    so a secagg sharded run matches both the plaintext sharded run and
    the single-device secagg run — on the fp32 and the int8+EF wire."""
    run_py(PARITY_PRELUDE + """
        import dataclasses
        cfg, train, data, task = build(16)
        key = jax.random.PRNGKey(0)
        cfg_s = dataclasses.replace(cfg, secagg="pairwise")
        st_plain, *_ = run_defta(key, task, cfg, train, data, epochs=3,
                                 shards=4)
        st_sec, *_ = run_defta(key, task, cfg_s, train, data, epochs=3,
                               shards=4)
        st_one, *_ = run_defta(key, task, cfg_s, train, data, epochs=3)
        assert err(st_plain.params, st_sec.params) < 5e-4
        assert err(st_one.params, st_sec.params) < TOL
        cfg_q = dataclasses.replace(cfg_s, gossip_dtype="int8")
        st_q, *_ = run_defta(key, task, cfg_q, train, data, epochs=3,
                             shards=4)
        st_q1, *_ = run_defta(key, task, cfg_q, train, data, epochs=3)
        assert err(st_q.params, st_q1.params) < TOL_INT8
        print("ok", err(st_plain.params, st_sec.params))
    """)


def test_sharded_run_padded_remainder():
    """W=100 on 8 shards: placement falls back to replicated (warned
    once), the transport pads internally — numerics still match."""
    run_py(PARITY_PRELUDE + """
        cfg, train, data, task = build(100, n_per_worker=32)
        key = jax.random.PRNGKey(1)
        s0, s1 = {}, {}
        st0, *_ = run_defta(key, task, cfg, train, data, epochs=2,
                            stats=s0)
        with warnings.catch_warnings(record=True) as rec:
            warnings.simplefilter("always")
            st1, *_ = run_defta(key, task, cfg, train, data, epochs=2,
                                stats=s1, shards=8)
        assert any("not divisible" in str(r.message) for r in rec), \\
            [str(r.message) for r in rec]
        assert s0["dispatches"] == s1["dispatches"] == 1
        assert err(st0.params, st1.params) < TOL
        assert err(st0.conf, st1.conf) < TOL
        print("ok", err(st0.params, st1.params))
    """)


def test_sharded_telemetry_ledger_layout_independent():
    """A sharded ledger run leaves state identical to a ledger-less
    sharded run, and its probe series match the single-device ledger's —
    RunLedger rows must not depend on the layout."""
    run_py(PARITY_PRELUDE + """
        from repro.telemetry import RunLedger
        cfg, train, data, task = build(16)
        key = jax.random.PRNGKey(0)

        led0, led1 = RunLedger(), RunLedger()
        st0, *_ = run_defta(key, task, cfg, train, data, epochs=4,
                            ledger=led0)
        st1, *_ = run_defta(key, task, cfg, train, data, epochs=4,
                            ledger=led1, shards=4)
        st2, *_ = run_defta(key, task, cfg, train, data, epochs=4,
                            shards=4)
        # telemetry off vs on under sharding: state unchanged
        assert err(st1.params, st2.params) < 1e-6
        # sharded vs single-device ledger: same probes, same series
        assert led0.names() == led1.names() and led0.names()
        assert led0.rounds_done == led1.rounds_done == 4
        for name in led0.names():
            a, b = led0.series(name), led1.series(name)
            assert a.shape == b.shape, name
            d = np.max(np.abs(a.astype(np.float64) - b.astype(np.float64)))
            assert d < 5e-4, (name, d)
        print("ok", led0.names())
    """)


def test_sharded_async_run_matches_single_device():
    run_py(PARITY_PRELUDE + """
        from repro.core.async_defta import run_async_defta
        cfg, train, data, task = build(16)
        key = jax.random.PRNGKey(2)
        s0, s1 = {}, {}
        st0, *_ = run_async_defta(key, task, cfg, train, data, ticks=4,
                                  stats=s0)
        st1, *_ = run_async_defta(key, task, cfg, train, data, ticks=4,
                                  stats=s1, shards=4)
        assert s0["dispatches"] == s1["dispatches"], (s0, s1)
        assert err(st0.params, st1.params) < TOL
        assert (np.asarray(st0.epoch) == np.asarray(st1.epoch)).all()
        print("ok", err(st0.params, st1.params))
    """)


def test_sharded_cross_device_matches_single_device():
    """The gather -> dense-k-block -> scatter path composed with the
    sharded worker axis (enrolled rows sharded, k-block replicated),
    telemetry riding both runs."""
    run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.config import DeFTAConfig, TrainConfig
        from repro.core.cross_device import run_cross_device
        from repro.core.tasks import mlp_task
        from repro.data.synthetic import federated_dataset
        from repro.scenarios.cross_device import CrossDeviceSpec
        from repro.telemetry import RunLedger

        def err(a, b):
            la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
            return max(float(jnp.max(jnp.abs(
                x.astype(jnp.float32) - y.astype(jnp.float32))))
                for x, y in zip(la, lb))

        n = 64
        cfg = DeFTAConfig(num_workers=n, avg_peers=4, num_sampled=2,
                          local_epochs=1)
        train = TrainConfig(learning_rate=0.05, batch_size=16)
        data = federated_dataset("vector", n, np.random.default_rng(0),
                                 n_per_worker=16, alpha=0.5)
        task = mlp_task(32, 10)
        spec = CrossDeviceSpec(enrolled=n, sample_k=8, availability=0.8,
                               dropout=0.05, straggle=0.1, seed=0)
        key = jax.random.PRNGKey(0)
        s0, s1 = {}, {}
        led0, led1 = RunLedger(), RunLedger()
        st0, _ = run_cross_device(key, task, cfg, train, data, world=spec,
                                  epochs=4, stats=s0, ledger=led0)
        st1, _ = run_cross_device(key, task, cfg, train, data, world=spec,
                                  epochs=4, stats=s1, ledger=led1,
                                  shards=8)
        assert s0["dispatches"] == s1["dispatches"] == 1, (s0, s1)
        assert err(st0.params, st1.params) < 5e-4
        assert err(st0.conf, st1.conf) < 5e-4
        assert (np.asarray(st0.obs) == np.asarray(st1.obs)).all()
        assert led0.names() == led1.names() and led0.names()
        for name in led0.names():
            a, b = led0.series(name), led1.series(name)
            d = np.max(np.abs(a.astype(np.float64) - b.astype(np.float64)))
            assert d < 5e-4, (name, d)
        print("ok", err(st0.params, st1.params))
    """)


@pytest.mark.skipif(not os.environ.get("RUN_SHARD_SCALE"),
                    reason="10k-worker scale check: shard CI lane only "
                           "(RUN_SHARD_SCALE=1)")
def test_sharded_w10k_superstep_budget():
    """A 10k-worker non-iid world runs end-to-end on 8 shards in
    ceil(epochs / eval_every) dispatches."""
    run_py("""
        import jax, numpy as np
        from repro.config import DeFTAConfig, TrainConfig
        from repro.core.defta import run_defta
        from repro.core.tasks import mlp_task
        from repro.data.synthetic import federated_dataset

        w = 10_000
        cfg = DeFTAConfig(num_workers=w, avg_peers=4, num_sampled=2,
                          local_epochs=1)
        train = TrainConfig(learning_rate=0.05, batch_size=8)
        data = federated_dataset("vector", w, np.random.default_rng(0),
                                 n_per_worker=8, alpha=0.5)
        stats = {}
        st, adj, mal, _ = run_defta(jax.random.PRNGKey(0), mlp_task(32, 10),
                                    cfg, train, data, epochs=2,
                                    eval_every=2, stats=stats, shards=8)
        assert stats["dispatches"] == 1, stats      # ceil(2 / 2)
        ep = np.asarray(st.epoch)
        assert ep.shape == (w,) and (ep == 2).all()
        print("ok", stats)
    """, timeout=560)
