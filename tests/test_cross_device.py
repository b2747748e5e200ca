"""Cross-device participation engine tests (the churn-as-default world).

Covers the participation round program (gather → dense k-block → scatter),
its degradation ladder (isolated workers, k_min identity fallback, absent
users' state bit-unchanged), the sparse-observation trust machinery
(stamped correlation, observation-gated suspicion, lazy confidence decay)
and the ``max_staleness`` cap on both the dense and cross-device paths.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import DeFTAConfig, TrainConfig
from repro.core import dts
from repro.core.cross_device import (probe_indices, resolve_world,
                                     run_cross_device)
from repro.core.defta import run_defta
from repro.core.engine import (build_cross_device_round, build_defta_round,
                               init_cross_device_state, init_state,
                               sketch_shape, stage_names)
from repro.core.gossip import uses_error_feedback
from repro.core.tasks import mlp_task
from repro.data.synthetic import federated_dataset
from repro.scenarios.cross_device import CrossDeviceSpec, compile_world
from repro.scenarios.spec import PartitionSpec, ScenarioSpec


def _leaves_finite(tree):
    return all(bool(jnp.isfinite(x).all()) for x in jax.tree.leaves(tree)
               if jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating))


def _small_world(enrolled, n_per_worker=24):
    rng = np.random.default_rng(3)
    task = mlp_task(8, 4, hidden=16)
    data = federated_dataset("vector", enrolled, rng,
                             n_per_worker=n_per_worker, dim=8,
                             num_classes=4)
    train = TrainConfig(learning_rate=0.05, batch_size=8)
    return task, data, train


# ---------------------------------------------------------------------------
# Peer-selection graceful degradation (satellite: no NaN when alive < k)
# ---------------------------------------------------------------------------

class TestPeerSelectionDegradation:
    def test_sample_weights_isolated_row_is_zeros(self):
        conf = jnp.asarray(np.random.default_rng(0).normal(size=(4, 4)),
                           jnp.float32)
        mask = jnp.zeros((4, 4), bool).at[1].set(
            jnp.array([True, False, True, False]))
        theta = dts.sample_weights(conf, mask)
        assert bool(jnp.isfinite(theta).all())
        # rows with no peers at all: zeros, not softmax's NaN
        assert bool((theta[0] == 0).all())
        assert bool((theta[2] == 0).all())
        assert theta[1].sum() == pytest.approx(1.0, abs=1e-6)

    def test_sample_peers_empty_theta_selects_nobody(self):
        key = jax.random.PRNGKey(0)
        picked = dts.sample_peers(key, jnp.zeros(6), 2)
        assert not bool(picked.any())

    def test_partition_stranding_a_worker_stays_finite(self):
        """A PartitionSpec that isolates worker 0 for the WHOLE run: its
        peer set is empty every round — sampling must select nobody, the
        mixing row must fall back to the identity self-loop, and no NaN
        may reach any state buffer."""
        task, data, train = _small_world(4)
        cfg = DeFTAConfig(num_workers=4, avg_peers=3, num_sampled=2,
                          local_epochs=1, topology="dense", seed=0)
        scen = ScenarioSpec(
            name="strand_w0",
            partitions=(PartitionSpec(groups=((0,), (1, 2, 3)), start=0),))
        state, adj, malicious, _ = run_defta(
            jax.random.PRNGKey(0), task, cfg, train, data, epochs=3,
            scenario=scen)
        assert _leaves_finite(state.params)
        assert bool(jnp.isfinite(state.conf).all())
        assert bool(jnp.isfinite(state.last_loss).all())
        # the stranded worker still self-trained: params moved off init
        init = init_state(jax.random.PRNGKey(0), task, 4)
        moved = any(
            bool(jnp.any(a[0] != b[0]))
            for a, b in zip(jax.tree.leaves(state.params),
                            jax.tree.leaves(init.params)))
        assert moved


# ---------------------------------------------------------------------------
# max_staleness (satellite: threaded as DeFTAConfig.max_staleness)
# ---------------------------------------------------------------------------

class TestMaxStaleness:
    def test_dense_staleness_equals_premasked_adjacency(self):
        """One round under max_staleness=S with epoch gaps must be
        bit-identical to max_staleness=0 with the stale edges removed from
        the adjacency by hand (uniform aggregation: the column weights are
        adjacency-independent, so the ONLY difference is eff_adj)."""
        task, data, train = _small_world(3)
        adj_full = ~np.eye(3, dtype=bool)
        ep = np.array([10, 0, 10])
        s_cap = 5
        fresh = (ep[:, None] - ep[None, :]) <= s_cap
        adj_masked = adj_full & fresh

        sizes = data["sizes"]
        malicious = np.zeros(3, bool)
        jdata = {k: jnp.asarray(v) for k, v in data.items()
                 if k in ("x", "y", "mask")}
        base = dict(local_epochs=1, aggregation="uniform", seed=0)
        cfg_s = DeFTAConfig(num_workers=3, max_staleness=s_cap, **base)
        cfg_0 = DeFTAConfig(num_workers=3, max_staleness=0, **base)

        state = init_state(jax.random.PRNGKey(1), task, 3)
        state = dataclasses.replace(state, epoch=jnp.asarray(ep, jnp.int32))
        rnd_s = build_defta_round(task, cfg_s, train, adj_full, sizes,
                                  malicious)
        rnd_0 = build_defta_round(task, cfg_0, train, adj_masked, sizes,
                                  malicious)
        out_s = jax.jit(rnd_s)(state, jdata)
        out_0 = jax.jit(rnd_0)(state, jdata)
        for a, b in zip(jax.tree.leaves(out_s), jax.tree.leaves(out_0)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_dense_staleness_zero_is_free(self):
        """max_staleness=0 (the default) is build-time gated: the round
        body must trace FEWER equations than the capped build — the cap
        costs ops only when it is on."""
        task, data, train = _small_world(3)
        adj = ~np.eye(3, dtype=bool)
        sizes = data["sizes"]
        mal = np.zeros(3, bool)
        base = dict(num_workers=3, local_epochs=1, aggregation="uniform")
        rnd_0 = build_defta_round(task, DeFTAConfig(**base), train, adj,
                                  sizes, mal)
        rnd_s = build_defta_round(task, DeFTAConfig(max_staleness=5, **base),
                                  train, adj, sizes, mal)
        state = init_state(jax.random.PRNGKey(1), task, 3)
        jdata = {k: jnp.asarray(v) for k, v in data.items()
                 if k in ("x", "y", "mask")}
        n_eqns = lambda r: len(jax.make_jaxpr(r)(state, jdata).eqns)
        assert n_eqns(rnd_0) < n_eqns(rnd_s)

    def test_async_defta_accepts_staleness_cap(self):
        from repro.core.async_defta import run_async_defta
        task, data, train = _small_world(4)
        cfg = DeFTAConfig(num_workers=4, avg_peers=2, num_sampled=2,
                          local_epochs=1, max_staleness=2, seed=0)
        state, adj, malicious, speeds = run_async_defta(
            jax.random.PRNGKey(0), task, cfg, train, data, ticks=5)
        assert _leaves_finite(state.params)
        assert bool(jnp.isfinite(state.conf).all())

    def test_cross_device_staleness_cap_compiles_and_runs(self):
        task, data, train = _small_world(10)
        cfg = DeFTAConfig(num_workers=10, avg_peers=2, num_sampled=2,
                          local_epochs=1, max_staleness=3, seed=0)
        spec = CrossDeviceSpec(enrolled=10, sample_k=4, avg_peers=2,
                               availability=0.6, seed=2)
        state, _ = run_cross_device(
            jax.random.PRNGKey(0), task, cfg, train, data,
            world=spec, epochs=4)
        assert _leaves_finite(state.params)
        assert bool(jnp.isfinite(state.conf).all())


# ---------------------------------------------------------------------------
# Cross-device round program: structure + degradation ladder
# ---------------------------------------------------------------------------

CD_STAGES = ("participation", "split_keys", "peer_sample", "transport",
             "damage_check", "local_train", "attack_inject", "trust_update",
             "scatter_merge")


def _build_cd(enrolled=8, k=3, *, cfg_kw=None, spec_kw=None, epochs=6):
    task, data, train = _small_world(enrolled)
    cfg_args = dict(num_workers=enrolled, avg_peers=2, num_sampled=2,
                    local_epochs=1, seed=0)
    cfg_args.update(cfg_kw or {})
    cfg = DeFTAConfig(**cfg_args)
    spec_args = dict(enrolled=enrolled, sample_k=k, avg_peers=2, seed=1)
    spec_args.update(spec_kw or {})
    spec = CrossDeviceSpec(**spec_args)
    world = compile_world(spec, epochs)
    rnd = build_cross_device_round(task, cfg, train, world, data["sizes"],
                                   num_classes=4)
    jdata = {kk: jnp.asarray(v) for kk, v in data.items()
             if kk in ("x", "y", "mask")}
    state = init_cross_device_state(
        jax.random.PRNGKey(0), task, enrolled,
        wire_error=uses_error_feedback(cfg), sketch=sketch_shape(cfg))
    return task, cfg, world, rnd, state, jdata


def _run_stages_until(rnd, state, jdata, epoch, last_stage):
    """Run the round pipeline stage by stage, stopping AFTER last_stage —
    the per-stage introspection the (name, fn) tuples exist for."""
    c = {"state": state, "data": jdata, "epoch": epoch}
    for name, fn in rnd.stages:
        fn(c)
        if name == last_stage:
            return c
    raise AssertionError(f"stage {last_stage!r} not in pipeline")


class TestCrossDeviceRoundProgram:
    def test_stage_names_and_contract_docs(self):
        _, _, _, rnd, _, _ = _build_cd()
        assert stage_names(rnd) == CD_STAGES
        for name, fn in rnd.stages:
            doc = fn.__doc__ or ""
            assert "reads" in doc, f"stage {name} documents no reads"
            assert "writes" in doc, f"stage {name} documents no writes"

    def test_architecture_doc_covers_cross_device_stages(self):
        import pathlib
        doc = (pathlib.Path(__file__).parents[1] / "docs"
               / "ARCHITECTURE.md").read_text()
        for name in CD_STAGES:
            assert f"`{name}`" in doc, \
                f"docs/ARCHITECTURE.md does not document `{name}`"

    def test_cohort_block_runs_the_dense_round_stages(self):
        """Between ``participation`` and ``scatter_merge`` the cross-device
        round runs the dense round's own stage code, not copies of it."""
        task, cfg, world, rnd, _, _ = _build_cd()
        w = 4
        adj = np.eye(w, k=1, dtype=bool) | np.eye(w, k=-1, dtype=bool)
        dense = build_defta_round(task, cfg, TrainConfig(), adj,
                                  np.full(w, 8), np.zeros(w, bool))
        code = {name: fn.__code__ for name, fn in dense.stages}
        block = rnd.stages[1:-1]
        assert [name for name, _ in block] == list(CD_STAGES[1:-1])
        for name, fn in block:
            assert fn.__code__ is code[name], name

    def test_k_min_shortfall_degrades_to_identity_mixing(self):
        """With k_min = k and a 1-out cohort graph no row can reach k_min
        surviving sampled peers — every mixing row must be the identity
        self-loop (self-training), never a NaN renormalization."""
        _, _, _, rnd, state, jdata = _build_cd(
            8, 3, spec_kw=dict(k_min=3, avg_peers=1, dropout=0.0,
                               straggle=0.0, availability=1.0))
        c = _run_stages_until(rnd, state, jdata, 0, "transport")
        np.testing.assert_array_equal(np.asarray(c["P"]), np.eye(3))

    def test_lazy_confidence_decay_applied_at_gather_only(self):
        """decay**gap scales the GATHERED rows; the raw rows kept for the
        non-fire scatter stay untouched."""
        decay = 0.5
        t = 4
        _, _, world, rnd, state, jdata = _build_cd(
            8, 3, cfg_kw=dict(dts_conf_decay=decay),
            spec_kw=dict(availability=1.0, dropout=0.0, straggle=0.0))
        conf = jnp.ones((8, 8)) * 2.0
        state = dataclasses.replace(state, conf=conf)
        c = _run_stages_until(rnd, state, jdata, t, "participation")
        # last_part is 0 for everyone -> gap = t
        np.testing.assert_allclose(np.asarray(c["g_conf_rows"]),
                                   2.0 * decay ** t, rtol=1e-6)
        np.testing.assert_array_equal(np.asarray(c["g_conf_raw"]),
                                      np.full((3, 8), 2.0))

    def test_decay_one_is_inert(self):
        _, _, _, rnd, state, jdata = _build_cd(8, 3)
        conf = jnp.ones((8, 8)) * 2.0
        state = dataclasses.replace(state, conf=conf)
        c = _run_stages_until(rnd, state, jdata, 3, "participation")
        np.testing.assert_array_equal(np.asarray(c["g_conf_rows"]),
                                      np.full((3, 8), 2.0))

    def test_dispatch_parity_with_eval_windows(self):
        """8 rounds at eval_every=4 must be exactly 2 XLA dispatches —
        the gather/scatter fuses into the scan body, costing zero extra."""
        task, data, train = _small_world(12)
        cfg = DeFTAConfig(num_workers=12, avg_peers=2, num_sampled=2,
                          local_epochs=1, seed=0)
        spec = CrossDeviceSpec(enrolled=12, sample_k=4, avg_peers=2, seed=3)
        stats = {}
        state, hist = run_cross_device(
            jax.random.PRNGKey(0), task, cfg, train, data,
            world=spec, epochs=8, eval_every=4,
            test_x=data["test_x"], test_y=data["test_y"], stats=stats)
        assert stats["dispatches"] == 2
        assert len(hist) == 2
        assert all(np.isfinite(h[1]) for h in hist)

    def test_absent_user_state_rows_are_bit_unchanged(self):
        """Users who never FIRE across the run keep every state row —
        params, backup, trust confidences, losses, EF residuals, sketch
        history and stamps — bit-identical to init. Non-firing cohort
        members scatter back their ORIGINAL (undecayed) rows."""
        enrolled, k, rounds = 12, 3, 4
        task, data, train = _small_world(enrolled)
        cfg = DeFTAConfig(num_workers=enrolled, avg_peers=2, num_sampled=2,
                          local_epochs=1, dts_signal="all",
                          gossip_dtype="int8", dts_conf_decay=0.9, seed=0)
        spec = CrossDeviceSpec(enrolled=enrolled, sample_k=k, avg_peers=2,
                               availability=0.5, dropout=0.2, straggle=0.2,
                               attacks=(("label_flip", 0.25),), seed=5)
        world = compile_world(spec, rounds)
        fire = world.filled & world.survive & world.complete
        fired_users = np.unique(world.part_ix[fire])
        never = np.setdiff1d(np.arange(enrolled), fired_users)
        assert never.size > 0, "world has no never-fired user; reseed"

        key = jax.random.PRNGKey(7)
        init = init_cross_device_state(
            key, task, enrolled, wire_error=uses_error_feedback(cfg),
            sketch=sketch_shape(cfg))
        state, _ = run_cross_device(key, task, cfg, train, data,
                                    world=world, epochs=rounds)

        def rows_equal(a, b):
            np.testing.assert_array_equal(np.asarray(a)[never],
                                          np.asarray(b)[never])

        jax.tree.map(rows_equal, state.params, init.params)
        jax.tree.map(rows_equal, state.backup, init.backup)
        jax.tree.map(rows_equal, state.wire_err, init.wire_err)
        rows_equal(state.conf, init.conf)
        rows_equal(state.sketch, init.sketch)
        rows_equal(state.sketch_round, init.sketch_round)
        rows_equal(state.best_loss, init.best_loss)
        rows_equal(state.last_loss, init.last_loss)
        rows_equal(state.last_part, init.last_part)
        rows_equal(state.obs, init.obs)
        rows_equal(state.epoch, init.epoch)
        # and the fired users really did advance
        assert bool((np.asarray(state.epoch)[fired_users] > 0).any())

    def test_world_validation(self):
        with pytest.raises(ValueError):
            CrossDeviceSpec(enrolled=4, sample_k=8)
        with pytest.raises(ValueError):
            CrossDeviceSpec(attacks=(("nonesuch", 0.1),))
        with pytest.raises(ValueError):
            CrossDeviceSpec(attacks=(("noise", 0.6), ("alie", 0.5)))
        with pytest.raises(TypeError):
            resolve_world(object(), 4)
        world = compile_world(CrossDeviceSpec(enrolled=8, sample_k=3), 2)
        with pytest.raises(ValueError):
            resolve_world(world, 5)

    def test_probe_skips_malicious_users(self):
        spec = CrossDeviceSpec(enrolled=40, sample_k=8,
                               attacks=(("alie", 0.3),), seed=0)
        world = compile_world(spec, 2)
        ix = probe_indices(world, 16, seed=0)
        assert not world.malicious[ix].any()
        assert len(ix) == 16


# ---------------------------------------------------------------------------
# Sparse-observation trust: stamped correlation + gated suspicion
# ---------------------------------------------------------------------------

class TestSparseObservationTrust:
    def _hist(self, stamps, sketch_rows):
        """hist [W, R, S] from per-worker slot sketches; stamps [W, R]."""
        return (jnp.asarray(sketch_rows, jnp.float32),
                jnp.asarray(stamps, jnp.int32))

    def test_matched_stamps_correlate_identical_sketches(self):
        s = np.sign(np.random.default_rng(0).normal(size=(3, 8)))
        hist = np.stack([s, s, -s])                  # w2 anti-correlated
        stamps = np.tile(np.array([4, 5, 6]), (3, 1))
        h, st = self._hist(stamps, hist)
        corr, valid = dts.stamped_correlation(h, st, min_obs=2)
        assert corr[0, 1] == pytest.approx(1.0, abs=1e-5)
        assert corr[0, 2] == pytest.approx(-1.0, abs=1e-5)
        assert bool(valid[0, 1]) and bool(valid[0, 2])
        # self-correlation is never evidence
        assert bool((~np.asarray(valid)[np.eye(3, dtype=bool)]).all())
        assert np.asarray(corr)[np.eye(3, dtype=bool)].sum() == 0.0

    def test_disjoint_stamps_are_invalid_not_zero_evidence(self):
        s = np.sign(np.random.default_rng(1).normal(size=(2, 8)))
        hist = np.stack([s, s])                      # identical payloads...
        stamps = np.array([[0, 1], [2, 3]])          # ...never co-observed
        h, st = self._hist(stamps, hist)
        corr, valid = dts.stamped_correlation(h, st, min_obs=1)
        assert not bool(valid[0, 1])
        assert corr[0, 1] == 0.0

    def test_min_obs_gates_single_lucky_round(self):
        s = np.sign(np.random.default_rng(2).normal(size=(3, 8)))
        hist = np.stack([s, s])
        stamps = np.array([[0, 1, 7], [3, 4, 7]])    # ONE common round
        h, st = self._hist(stamps, hist)
        _, valid1 = dts.stamped_correlation(h, st, min_obs=1)
        _, valid2 = dts.stamped_correlation(h, st, min_obs=2)
        assert bool(valid1[0, 1])
        assert not bool(valid2[0, 1])

    def test_empty_slots_never_match(self):
        hist = np.zeros((2, 2, 4), np.float32)
        stamps = np.full((2, 2), -1)                 # nothing ever filled
        h, st = self._hist(stamps, hist)
        corr, valid = dts.stamped_correlation(h, st, min_obs=1)
        assert not bool(valid.any())
        assert bool((corr == 0).all())

    def test_suspicion_excludes_invalid_pairs_from_baseline(self):
        """A pair never co-observed must contribute NEITHER suspicion nor
        baseline: with only one valid (uncorrelated) pair the excess graph
        is empty and all scores are zero — no phantom suspicion from
        comparing unobserved zeros against a negative median."""
        w = 4
        corr = jnp.zeros((w, w))
        valid = jnp.zeros((w, w), bool).at[0, 1].set(True).at[1, 0].set(True)
        mask = ~jnp.eye(w, dtype=bool)
        s = dts.correlation_suspicion(corr, mask, valid=valid)
        np.testing.assert_allclose(np.asarray(s), 0.0, atol=1e-7)

    def test_suspicion_all_invalid_early_rounds_is_zero(self):
        w = 3
        corr = jnp.full((w, w), 0.9)
        valid = jnp.zeros((w, w), bool)
        mask = ~jnp.eye(w, dtype=bool)
        s = dts.correlation_suspicion(corr, mask, valid=valid)
        assert bool(jnp.isfinite(s).all())
        np.testing.assert_allclose(np.asarray(s), 0.0, atol=1e-7)

    def test_valid_cluster_still_scores_above_honest(self):
        """The gate must not neuter the signal: a fully-observed colluder
        pair with high mutual correlation scores above the honest peers."""
        w = 5
        rng = np.random.default_rng(4)
        corr = np.clip(rng.normal(0.0, 0.05, (w, w)), -1, 1)
        corr[3, 4] = corr[4, 3] = 0.95               # the colluder pair
        np.fill_diagonal(corr, 0.0)
        valid = ~np.eye(w, dtype=bool)
        mask = jnp.asarray(valid)
        s = dts.correlation_suspicion(jnp.asarray(corr, jnp.float32), mask,
                                      valid=jnp.asarray(valid))
        s = np.asarray(s)
        honest_max = s[0, :3].max()
        assert s[0, 3] > honest_max and s[0, 4] > honest_max


# ---------------------------------------------------------------------------
# Golden parity: the cross-device round, bit for bit
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["cross_device_loss", "cross_device_all",
                                  "cross_device_masked"])
def test_golden_cross_device(name, assert_golden):
    """The whole population state after a cross-device run (params,
    backups, the [N, N] confidences, losses, counters, EF residuals,
    sketches and their stamps) equals the state captured on the engine
    whose cross-device round still carried its own copies of the dense
    stages."""
    from capture_engine_goldens import ROUND_GOLDENS
    assert_golden(name, ROUND_GOLDENS[name]())
