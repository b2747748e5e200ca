"""Plain reference of the DeFTA round (arXiv:2204.02632, Algorithms 1-3)
for the ``engine`` driver: straightforward ``jax.numpy``, float32 at
``highest`` matmul precision, no kernels, no scan fusion, nothing
imported from the program. It follows the same seeded PRNG layout as the
program, so at equal precision both draw the same peers, batches and
attack noise:

    round key -> (next key, sample, train, noise)
    peer sample: softmax(cReLU(conf)) over the topology, Gumbel top-k
    mix: P = sampled ∪ self, columns weighted |D_j| / outdeg_j, rows
         normalised; each worker's leaf row sent as int8 (scale max|x|/127,
         round to nearest) on top of its EF21 residual
    time machine: a worker whose aggregate loss explodes (> 10·best + 10)
         restarts from its backup
    local training: ``local_epochs`` epochs of minibatch SGD over a
         permutation of the padded rows (masked mean loss)
    attack: the attacker sends aggregate + scale·N(0, 1) per leaf
    trust: conf -= sampled·P·(loss_agg − last_loss), damage penalty 1e3

``dtype`` runs the same arithmetic in a lower precision: bfloat16 is the
control that the comparison must reject.
"""
from __future__ import annotations

import contextlib

import numpy as np

DAMAGE_PENALTY = 1e3
CRELU_SLOPE = 0.2


def topology(kind: str, n: int, k: int, seed: int) -> np.ndarray:
    """``adj[i, j]``: worker i receives from j (every worker picks k)."""
    if kind != "random_kout":
        raise ValueError(f"reference topology {kind!r} not written")
    rng = np.random.default_rng(seed)
    adj = np.zeros((n, n), bool)
    for i in range(n):
        adj[i, rng.choice([j for j in range(n) if j != i], size=min(k, n - 1),
                          replace=False)] = True
    return adj


def cnn(task: dict):
    import jax
    import jax.numpy as jnp

    hw, ch, width, classes = (task["hw"], task["channels"], task["width"],
                              task["num_classes"])

    def init(key):
        k1, k2, k3 = jax.random.split(key, 3)
        flat = (hw // 4) ** 2 * (2 * width)
        return {"c1": jax.random.normal(k1, (3, 3, ch, width)) * 0.1,
                "c2": jax.random.normal(k2, (3, 3, width, 2 * width)) * 0.1,
                "w": jax.random.normal(k3, (flat, classes)) * flat ** -0.5,
                "b": jnp.zeros(classes)}

    def conv(x, k):
        return jax.lax.conv_general_dilated(
            x, k, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))

    def pool(x):
        return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 2, 2, 1),
                                     (1, 2, 2, 1), "VALID")

    def loss(p, x, y, m):
        h = x.reshape(x.shape[0], hw, hw, ch)
        h = pool(jax.nn.relu(conv(h, p["c1"])))
        h = pool(jax.nn.relu(conv(h, p["c2"])))
        logits = h.reshape(h.shape[0], -1) @ p["w"] + p["b"]
        logp = jax.nn.log_softmax(logits)
        ll = jnp.take_along_axis(logp, y[:, None], axis=-1)[:, 0]
        return -(ll * m).sum() / jnp.maximum(m.sum(), 1.0)

    return init, loss


def run_rounds(config: dict, traffic: dict, seed: int, *, rounds: int,
               data: dict, dtype: str = "float32", fault: str = ""):
    """``rounds`` DeFTA rounds from the seed; returns params, last_loss,
    best_loss and conf after them (numpy). ``fault`` plants one of the
    faults the comparison has to catch: ``half_batch`` (each SGD step
    averages over the first half of its minibatch), ``no_exchange`` (the
    gossip mix returns every worker's own parameters)."""
    import jax
    import jax.numpy as jnp

    import generate

    dt = jnp.dtype(dtype)
    vanilla = config["workers"]
    att = config["attack"]
    w = vanilla + 1
    adj = topology(config["topology"], w, config["avg_peers"],
                   config["topology_seed"])
    sizes, _ = generate.worker_sizes(traffic, vanilla)
    sizes = np.concatenate([sizes, [int(np.mean(sizes))]]).astype(np.float32)
    pad = lambda a: np.concatenate([a[:vanilla], a[vanilla - 1:vanilla]], 0)
    x = jnp.asarray(pad(data["x"][:vanilla]), dt)
    y = jnp.asarray(pad(data["y"][:vanilla]))
    m = jnp.asarray(pad(data["mask"][:vanilla]), dt)
    malicious = np.arange(w) >= vanilla
    init, loss = cnn(config["task"])
    bs, lr = config["batch_size"], config["learning_rate"]
    n = x.shape[1]
    spe = max(n // bs, 1)

    def local_train(key, p, xw, yw, mw):
        def epoch(p, ekey):
            pkey, _ = jax.random.split(ekey)
            perm = jax.random.permutation(pkey, n)[:spe * bs]
            batches = (xw[perm].reshape(spe, bs, -1), yw[perm].reshape(
                spe, bs), mw[perm].reshape(spe, bs))

            def step(p, b):
                if fault == "half_batch":
                    b = (b[0], b[1], b[2] * (jnp.arange(bs) < bs // 2))
                g = jax.grad(loss)(p, *b)
                return jax.tree.map(lambda a, d: (a - lr * d).astype(dt),
                                    p, g), None
            return jax.lax.scan(step, p, batches)[0], None
        return jax.lax.scan(epoch, p, jax.random.split(
            key, config["local_epochs"]))[0]

    def sample_peers(key, conf_row, peers):
        z = jnp.where(conf_row <= 0, conf_row, CRELU_SLOPE * conf_row)
        theta = jax.nn.softmax(jnp.where(peers, z, -jnp.inf))
        g = jax.random.gumbel(key, theta.shape)
        score = jnp.where(theta > 0, jnp.log(theta + 1e-20) + g, -jnp.inf)
        _, idx = jax.lax.top_k(score, config["num_sampled"])
        return jnp.zeros(w, bool).at[idx].set(True) & (theta > 0)

    def pick(flag, a, b):
        return jax.tree.map(lambda u, v: jnp.where(
            flag.reshape((-1,) + (1,) * (u.ndim - 1)), u, v), a, b)

    adj_j = jnp.asarray(adj)
    eye = jnp.eye(w, dtype=bool)
    outdeg = (adj | np.eye(w, dtype=bool)).sum(axis=0).astype(np.float32)
    col_w = jnp.asarray(sizes / outdeg)
    malicious_j = jnp.asarray(malicious)

    @jax.jit
    def one_round(st, x, y, m):
        params, backup, resid, conf, best, last, key = st
        key, k_sample, k_train, k_noise = jax.random.split(key, 4)
        sampled = jax.vmap(sample_peers)(jax.random.split(k_sample, w),
                                         conf, adj_j)
        mask = (sampled & adj_j) | eye
        P = mask * col_w[None, :]
        P = P / jnp.maximum(P.sum(axis=1, keepdims=True), 1e-12)
        agg, new_resid = {}, {}
        for name in params:
            send = (params[name] + resid[name]).reshape(w, -1)
            f32 = send.astype(jnp.float32)
            scale = jnp.maximum(jnp.max(jnp.abs(f32), axis=1), 1e-12) / 127.0
            q = jnp.clip(jnp.round(f32 / scale[:, None]), -127, 127)
            new_resid[name] = (f32 - q * scale[:, None]).astype(dt) \
                .reshape(params[name].shape)
            mixed = (P * scale[None, :]).astype(dt) @ q.astype(dt)
            agg[name] = mixed.astype(dt).reshape(params[name].shape)
            if fault == "no_exchange":
                agg[name] = params[name]
        loss_agg = jax.vmap(loss)(agg, x, y, m).astype(jnp.float32)
        damaged = ~jnp.isfinite(loss_agg) \
            | (loss_agg > 10.0 * jnp.maximum(best, 1e-8) + 10.0)
        start = pick(damaged, backup, agg)
        trained = jax.vmap(local_train)(jax.random.split(k_train, w), start,
                                        x, y, m)
        nkey = jax.random.fold_in(k_noise, att["code"])
        names = sorted(agg)
        noisy = {k: agg[k] + (att["scale"] * jax.random.normal(
            nk, agg[k].shape, jnp.float32)).astype(dt)
            for k, nk in zip(names, jax.random.split(nkey, len(names)))}
        trained = pick(malicious_j, noisy, trained)
        loss_trust = jnp.where(damaged, DAMAGE_PENALTY, loss_agg - last)
        conf = conf - sampled * P * loss_trust[:, None]
        improved = (loss_agg < best) & ~damaged
        backup = pick(improved | damaged, trained, backup)
        best = jnp.where(improved, loss_agg, best)
        last = jnp.where(damaged, last, loss_agg)
        return trained, backup, new_resid, conf, best, last, key

    prec = jax.default_matmul_precision("highest") \
        if dt == jnp.float32 else contextlib.nullcontext()
    with prec:
        keys = jax.random.split(
            jax.random.PRNGKey(generate.prng_seed(seed)), w + 1)
        params = jax.vmap(init)(keys[:w])
        params = jax.tree.map(lambda a: a.astype(dt), params)
        params0 = jax.tree.map(lambda a: np.asarray(a, np.float32), params)
        st = (params, params,
              jax.tree.map(lambda a: jnp.zeros(a.shape, dt), params),
              jnp.zeros((w, w), jnp.float32),
              jnp.full((w,), jnp.inf, jnp.float32),
              jnp.zeros((w,), jnp.float32), keys[-1])
        for _ in range(rounds):
            st = one_round(st, x, y, m)
        params, _, _, conf, best, last, _ = st
    return {"params0": params0,
            "params": jax.tree.map(lambda a: np.asarray(a, np.float32),
                                   params),
            "last_loss": np.asarray(last), "best_loss": np.asarray(best),
            "conf": np.asarray(conf)}
