"""Optimizers, schedules, data pipeline, checkpoint units."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.optim import make_optimizer
from repro.optim.schedules import cosine_schedule, warmup_linear
from repro.data.partition import dirichlet_partition, shard_partition
from repro.data.synthetic import (federated_dataset, make_classification,
                                  make_lm_stream)


def _quad_problem():
    target = jnp.asarray([1.0, -2.0, 3.0])
    params = {"w": jnp.zeros(3), "m": jnp.zeros((4, 5))}

    def loss(p):
        return jnp.sum((p["w"] - target) ** 2) + jnp.sum(p["m"] ** 2)
    return params, loss, target


@pytest.mark.parametrize("name,lr", [("sgd", 0.1), ("adam", 0.1),
                                     ("adafactor", 0.5)])
def test_optimizers_converge_on_quadratic(name, lr):
    params, loss, target = _quad_problem()
    opt = make_optimizer(name, lr)
    state = opt.init(params)
    steps = 600 if name == "adafactor" else 200
    for step in range(steps):
        g = jax.grad(loss)(params)
        params, state = opt.update(params, g, state, jnp.int32(step))
    # adafactor's update clipping makes the last decimals slow; 0.1 is
    # firmly converged relative to the initial loss (14.0)
    tol = 0.1 if name == "adafactor" else 0.05
    assert float(loss(params)) < tol, (name, float(loss(params)))


def test_sgd_momentum():
    params, loss, _ = _quad_problem()
    opt = make_optimizer("sgd", 0.02, momentum=0.9)
    state = opt.init(params)
    for step in range(150):
        g = jax.grad(loss)(params)
        params, state = opt.update(params, g, state, jnp.int32(step))
    assert float(loss(params)) < 0.05


def test_adam_state_is_fp32_for_bf16_params():
    opt = make_optimizer("adam", 1e-3)
    params = {"w": jnp.zeros((8, 8), jnp.bfloat16)}
    state = opt.init(params)
    assert state["m"]["w"].dtype == jnp.float32
    g = {"w": jnp.ones((8, 8), jnp.bfloat16)}
    p2, _ = opt.update(params, g, state, jnp.int32(0))
    assert p2["w"].dtype == jnp.bfloat16


def test_adafactor_state_is_factored():
    opt = make_optimizer("adafactor", 1e-2)
    params = {"w": jnp.zeros((64, 32))}
    state = opt.init(params)
    assert state["f"]["w"]["vr"].shape == (64,)
    assert state["f"]["w"]["vc"].shape == (32,)


def test_schedules():
    f = warmup_linear(1.0, 10, 100)
    assert float(f(0)) == 0.0
    assert abs(float(f(10)) - 0.9) < 0.01
    g = cosine_schedule(1.0, 10, 100)
    assert float(g(10)) > float(g(90))
    assert float(g(5)) < float(g(10))


def test_dirichlet_partition_covers_everything():
    rng = np.random.default_rng(0)
    labels = rng.integers(0, 10, size=2000)
    parts = dirichlet_partition(labels, 8, alpha=0.5, rng=rng)
    allidx = np.concatenate(parts)
    assert len(allidx) == 2000
    assert len(np.unique(allidx)) == 2000


def test_dirichlet_partition_terminates_at_scale():
    """Many workers x few samples: P(every worker draws >= min_size) is
    ~0, so the old unconditional retry loop never returned. The bounded
    retry + deterministic top-up must terminate, cover every index
    exactly once, and still give each worker min_size."""
    rng = np.random.default_rng(0)
    n_workers, n = 2000, 4000          # 2 samples/worker expected
    labels = rng.integers(0, 10, size=n)
    parts = dirichlet_partition(labels, n_workers, alpha=0.5, rng=rng,
                                min_size=2)
    allidx = np.concatenate(parts)
    assert len(allidx) == n
    assert len(np.unique(allidx)) == n
    assert min(len(ix) for ix in parts) >= 2


def test_dirichlet_more_noniid_with_small_alpha():
    rng = np.random.default_rng(1)
    labels = rng.integers(0, 10, size=4000)

    def skew(alpha):
        parts = dirichlet_partition(labels, 8,
                                    alpha=alpha,
                                    rng=np.random.default_rng(2))
        # mean entropy of per-worker label distribution
        ents = []
        for ix in parts:
            c = np.bincount(labels[ix], minlength=10) + 1e-9
            p = c / c.sum()
            ents.append(-(p * np.log(p)).sum())
        return np.mean(ents)

    assert skew(0.1) < skew(10.0)


def test_shard_partition():
    rng = np.random.default_rng(0)
    labels = rng.integers(0, 10, size=1000)
    parts = shard_partition(labels, 10, 2, rng)
    assert sum(len(p) for p in parts) == 1000


def test_federated_dataset_shapes():
    rng = np.random.default_rng(0)
    d = federated_dataset("vector", 6, rng, n_per_worker=100)
    assert d["x"].shape[0] == 6
    assert (d["sizes"] > 0).all()
    assert d["mask"].sum(1).astype(int).tolist() == d["sizes"].tolist()
    assert len(d["test_x"]) > 100


def test_lm_stream_learnable_structure():
    rng = np.random.default_rng(0)
    seqs = make_lm_stream(200, 32, 16, rng)
    assert seqs.shape == (200, 32)
    assert seqs.min() >= 0 and seqs.max() < 16
    # Markov structure: bigram distribution is far from uniform
    big = np.zeros((16, 16))
    for s in seqs:
        for a, b in zip(s[:-1], s[1:]):
            big[a, b] += 1
    rowp = big / np.maximum(big.sum(1, keepdims=True), 1)
    assert (rowp.max(1) > 0.3).mean() > 0.5


def test_token_batcher_prefetch_gives_the_same_batches():
    """A prefetched batch is the one ``batch_at`` makes directly, made on
    another thread; a step other than the prefetched one is made in place
    and leaves the prefetched one waiting."""
    import threading

    from repro.data import loader

    direct = loader.TokenBatcher(64, 12, 3, seed=5)
    ahead = loader.TokenBatcher(64, 12, 3, seed=5)
    made_on = {}
    make = ahead._make
    ahead._make = lambda s: (made_on.setdefault(s, threading.get_ident()),
                             make(s))[1]
    got = {}
    for step in range(4):
        got[step] = ahead.batch_at(step)
        ahead.prefetch(step + 1)
    got[9] = ahead.batch_at(9)
    got[4] = ahead.batch_at(4)
    for step, batch in got.items():
        want = direct.batch_at(step)
        for k in ("tokens", "labels"):
            np.testing.assert_array_equal(batch[k], want[k])
    main = threading.get_ident()
    assert [made_on[s] == main for s in (0, 1, 2, 3, 4, 9)] == [
        True, False, False, False, False, True]
