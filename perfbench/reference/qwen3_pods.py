"""Plain reference of cross-silo DeFTA on Qwen3 (``pods`` driver):
straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision,
nothing imported from the program.

Model (Qwen3, hf:Qwen/Qwen3-0.6B): token embedding (tied to the output
head); per layer RMSNorm → grouped-query attention with per-head RMSNorm
on q and k, split-half rotary embedding and a causal mask → residual →
RMSNorm → SwiGLU MLP → residual; final RMSNorm; next-token cross-entropy
averaged over every target position. Optimizer: Adafactor with factored
second moments over a leaf's last two axes (decay t^-0.8, eps 1e-30,
update RMS clipped to 1). Every ``gossip_every`` steps the pods exchange
their parameters as int8 rows (scale max|x|/127, round to nearest) on top
of their EF21 residuals and average them with DeFTA's weights over the
sampled peers; trust moves by the pods' loss deltas.

The weights follow the same seeded layout as the program's initializer:
a key split per named parameter in declaration order, the layer stack
drawn per layer from ``fold_in(split, layer)``, normal draws scaled by
0.02 (0.01 for the embedding) and stored in bfloat16. The reference
computes on those values in float32 from there on.

``dtype="float8_e4m3fn"`` is the control: every matmul operand (weights
and activations) held in float8 with a per-tensor scale, as an fp8
training path would hold it, the gradient passing straight through; the
precision one step below the configuration's bfloat16.
"""
from __future__ import annotations

import numpy as np

EPS_ADAFACTOR = 1e-30
DECAY = 0.8
CRELU_SLOPE = 0.2


def _split(key):
    import jax
    k, sub = jax.random.split(key)
    return k, sub


def init_params(m: dict, key):
    """The stacked parameter tree, bfloat16, as the program lays it out:
    {embedding, ln_f, prefix: {}, scan: {"0": {per-layer leaves [L, ...]}}}."""
    import jax
    import jax.numpy as jnp

    bf = jnp.bfloat16
    d, nq, nkv, hd, ff = (m["d_model"], m["num_heads"], m["num_kv_heads"],
                          m["head_dim"], m["d_ff"])

    def normal(k, shape, scale=0.02):
        return (scale * jax.random.normal(k, shape, jnp.float32)).astype(bf)

    key, k_emb = _split(key)
    emb = normal(k_emb, (m["vocab_size"], d), 0.01)
    key, _ = _split(key)                       # the (empty) prefix stack
    key, k_scan = _split(key)
    layers = []
    for r in range(m["num_layers"]):
        k_scan, k_layer = _split(k_scan)
        lk = jax.random.fold_in(k_layer, r)
        lk, k_attn = _split(lk)
        k_attn, k_q = _split(k_attn)
        k_attn, k_k = _split(k_attn)
        k_attn, k_v = _split(k_attn)
        k_attn, k_o = _split(k_attn)
        lk, k_mlp = _split(lk)
        k_mlp, k_i = _split(k_mlp)
        k_mlp, k_g = _split(k_mlp)
        k_mlp, k_mo = _split(k_mlp)
        layers.append({
            "ln1": jnp.ones((d,), bf), "ln2": jnp.ones((d,), bf),
            "attn": {"wq": normal(k_q, (d, nq, hd)),
                     "wk": normal(k_k, (d, nkv, hd)),
                     "wv": normal(k_v, (d, nkv, hd)),
                     "wo": normal(k_o, (nq, hd, d)),
                     "q_norm": jnp.ones((hd,), bf),
                     "k_norm": jnp.ones((hd,), bf)},
            "mlp": {"wi": normal(k_i, (d, ff)), "wg": normal(k_g, (d, ff)),
                    "wo": normal(k_mo, (ff, d))}})
    scan = jax.tree.map(lambda *a: jnp.stack(a), *layers)
    return {"embedding": emb, "ln_f": jnp.ones((d,), bf), "prefix": {},
            "scan": {"0": scan}}


def model_loss(m: dict, low=None):
    """loss(params, tokens [S]) for one row, float32 (``low`` rounds every
    matmul operand through that dtype: the control)."""
    import jax
    import jax.numpy as jnp

    eps, theta = m["norm_eps"], m["rope_theta"]
    nq, nkv, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]

    def rd(a):
        """The operand as ``low`` holds it: scaled per tensor into its
        range (max |a| to the format's largest finite value), rounded,
        scaled back; the gradient passes straight through in float32."""
        if low is None:
            return a
        s = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) \
            / float(jnp.finfo(low).max)
        q = (a / s).astype(low).astype(jnp.float32) * s
        return a + jax.lax.stop_gradient(q - a)

    def mm(spec, a, b):
        return jnp.einsum(spec, rd(a), rd(b))

    def rms(x, w):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w

    def rope(x, pos):
        half = hd // 2
        freqs = 1.0 / (theta ** (np.arange(half, dtype=np.float32) / half))
        ang = pos[:, None].astype(jnp.float32) * freqs
        cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)

    def layer(x, p):
        s = x.shape[0]
        pos = jnp.arange(s)
        h = rms(x, p["ln1"])
        a = p["attn"]
        q = rms(mm("sd,dhk->shk", h, a["wq"]), a["q_norm"])
        k = rms(mm("sd,dhk->shk", h, a["wk"]), a["k_norm"])
        v = mm("sd,dhk->shk", h, a["wv"])
        q, k = rope(q, pos), rope(k, pos)
        q = q.reshape(s, nkv, nq // nkv, hd)
        sc = mm("qhgk,shk->hgqs", q, k) / np.sqrt(hd)
        causal = pos[None, :] <= pos[:, None]
        sc = jnp.where(causal[None, None], sc, -1e30)
        o = mm("hgqs,shk->qhgk", jax.nn.softmax(sc, -1), v)
        x = x + mm("shk,hkd->sd", o.reshape(s, nq, hd), a["wo"])
        h = rms(x, p["ln2"])
        f = p["mlp"]
        g = jax.nn.silu(mm("sd,df->sf", h, f["wg"])) * mm("sd,df->sf", h,
                                                           f["wi"])
        return x + mm("sf,fd->sd", g, f["wo"]), None

    layer = jax.checkpoint(layer)

    def loss(params, tokens):
        x = params["embedding"][tokens]
        x, _ = jax.lax.scan(layer, x, params["scan"]["0"])
        x = rms(x, params["ln_f"])
        logits = mm("sd,vd->sv", x, params["embedding"])[:-1]
        logp = jax.nn.log_softmax(logits, -1)
        return -jnp.mean(jnp.take_along_axis(logp, tokens[1:, None], -1))

    return loss


def adafactor(lr: float):
    import jax
    import jax.numpy as jnp

    def init(p):
        if p.ndim >= 2:
            return {"vr": jnp.zeros(p.shape[:-1]),
                    "vc": jnp.zeros(p.shape[:-2] + p.shape[-1:])}
        return {"v": jnp.zeros(p.shape)}

    def update(p, g, s, t):
        beta = 1.0 - t ** (-DECAY)
        g2 = g * g + EPS_ADAFACTOR
        if p.ndim >= 2:
            vr = beta * s["vr"] + (1 - beta) * g2.mean(-1)
            vc = beta * s["vc"] + (1 - beta) * g2.mean(-2)
            denom = vr[..., None] * vc[..., None, :] / jnp.maximum(
                vc.mean(-1)[..., None, None], EPS_ADAFACTOR)
            u, s = g * jax.lax.rsqrt(denom + EPS_ADAFACTOR), {"vr": vr,
                                                             "vc": vc}
        else:
            v = beta * s["v"] + (1 - beta) * g2
            u, s = g * jax.lax.rsqrt(v + EPS_ADAFACTOR), {"v": v}
        u = u / jnp.maximum(1.0, jnp.sqrt(jnp.mean(u * u) + 1e-12))
        return p - lr * u, s

    return init, update


def gossip(params: list, resid, conf, last, losses, key, sizes, *,
           keep_resid: bool):
    """One pod gossip round over a dense topology (every pod samples
    ``min(2, pods − 1)`` peers by DTS): returns (params, resid, conf,
    losses as last, key). ``resid`` None is all zeros; the new residuals
    are kept only with ``keep_resid``, on the host, where they wait for
    the next round (on the chip they would not fit beside the float32
    parameters and gradients). Leaves are replaced one at a time, so the
    round holds one leaf's temporaries beyond the parameters."""
    import jax
    import jax.numpy as jnp

    pods = len(params)
    adj = ~np.eye(pods, dtype=bool)
    key, k_sample, _ = jax.random.split(key, 3)
    z = jnp.where(conf <= 0, conf, CRELU_SLOPE * conf)
    theta = jax.nn.softmax(jnp.where(adj, z, -jnp.inf), -1)
    sampled = []
    for i, k in enumerate(jax.random.split(k_sample, pods)):
        g = jax.random.gumbel(k, (pods,))
        score = jnp.where(theta[i] > 0, jnp.log(theta[i] + 1e-20) + g,
                          -jnp.inf)
        _, idx = jax.lax.top_k(score, min(2, pods - 1))
        sampled.append(jnp.zeros(pods, bool).at[idx].set(True)
                       & (theta[i] > 0))
    sampled = jnp.stack(sampled)
    mask = (sampled & adj) | np.eye(pods, dtype=bool)
    outdeg = (adj | np.eye(pods, dtype=bool)).sum(0).astype(np.float32)
    P = mask * jnp.asarray(sizes / outdeg)[None, :]
    P = P / jnp.maximum(P.sum(1, keepdims=True), 1e-12)
    tdef = jax.tree.structure(params[0])
    leaves = [jax.tree.leaves(p) for p in params]
    params.clear()                  # the caller's trees: free as replaced
    rleaves = [jax.tree.leaves(r) for r in resid] if resid is not None \
        else None
    kept = [[None] * len(leaves[0]) for _ in range(pods)]
    for li in range(len(leaves[0])):
        deq = []
        for j in range(pods):
            send = leaves[j][li]
            if rleaves is not None:
                send = send + rleaves[j][li]
                rleaves[j][li] = None
            scale = jnp.maximum(jnp.max(jnp.abs(send)), 1e-12) / 127.0
            q = jnp.clip(jnp.round(send / scale), -127, 127) * scale
            deq.append(q)
            if keep_resid:
                kept[j][li] = np.asarray(send - q)
        for i in range(pods):
            leaves[i][li] = sum(P[i, j] * deq[j] for j in range(pods))
        del deq
    conf = conf - sampled * P * (losses - last)[:, None]
    new_resid = [jax.tree.unflatten(tdef, r) for r in kept] \
        if keep_resid else None
    return ([jax.tree.unflatten(tdef, p) for p in leaves], new_resid, conf,
            losses, key)


def run_steps(config: dict, traffic: dict, seed: int, *, steps: int,
              dtype: str = "float32", fault: str = ""):
    """The first ``steps`` steps of every pod from the seed, with a
    gossip round after every ``gossip_every`` steps. Returns losses [steps, pods],
    the first step's gradient norm per leaf [leaves][pods], and each
    leaf's change from the initial weights per pod after the steps.
    ``fault`` plants one of the faults the comparison has to catch:
    ``half_batch`` (each pod's step averages over the first half of its
    rows), ``no_exchange`` (the gossip round leaves every pod's
    parameters as they are)."""
    import contextlib

    import jax
    import jax.numpy as jnp

    import generate

    m = config["model"]
    pods, gb, seq = config["pods"], config["global_batch"], config["seq"]
    low = None if dtype == "float32" else jnp.dtype(dtype)
    key = jax.random.PRNGKey(generate.prng_seed(seed))
    prec = jax.default_matmul_precision("highest") if low is None \
        else contextlib.nullcontext()
    loss = model_loss(m, low)
    opt_init, opt_update = adafactor(config["learning_rate"])
    every = traffic["gossip_every"]
    with prec:
        p0 = jax.jit(lambda k: init_params(m, k))(key)        # bfloat16
        grad_fn = jax.jit(jax.value_and_grad(loss))
        upd_fn = jax.jit(lambda p, g, s, t: jax.tree.map(
            lambda a, b, c: opt_update(a, b, c, t), p, g, s))
        start = jax.tree.map(lambda a: a.astype(jnp.float32), p0)
        params = [start for _ in range(pods)]
        states = [jax.tree.map(opt_init, start) for _ in range(pods)]
        del start
        resid = None
        conf = jnp.zeros((pods, pods))
        last = jnp.zeros((pods,))
        pkey = jax.random.fold_in(key, config["pod_key_tag"])
        sizes = np.full(pods, gb, np.float32)
        losses, grad0 = [], None
        for i in range(steps):
            batch = generate.token_batch(traffic, gb, seq, m["vocab_size"],
                                         seed, i).reshape(pods, gb // pods,
                                                          seq)
            row = []
            grads_i = []
            for pi in range(pods):
                tot, g_acc = 0.0, None
                rows = batch[pi]
                if fault == "half_batch":
                    rows = rows[:max(len(rows) // 2, 1)]
                for r in rows:
                    lv, g = grad_fn(params[pi], jnp.asarray(r))
                    tot += float(lv)
                    g_acc = g if g_acc is None else jax.tree.map(
                        jnp.add, g_acc, g)
                g = jax.tree.map(lambda a: a / len(rows), g_acc)
                row.append(tot / len(rows))
                grads_i.append(g)
                params[pi], states[pi] = _split_update(
                    upd_fn(params[pi], g, states[pi], jnp.float32(i + 1)))
            losses.append(row)
            if i == 0:
                grad0 = [[float(jnp.linalg.norm(leaf.ravel())) for leaf in
                          jax.tree.leaves(g)] for g in grads_i]
            del grads_i, g
            if (i + 1) % every == 0 and fault != "no_exchange":
                params, resid, conf, last, pkey = gossip(
                    params, resid, conf, last, jnp.asarray(row), pkey, sizes,
                    keep_resid=i + 1 + every <= steps)
        change = [[float(jnp.linalg.norm((a - b.astype(jnp.float32)).ravel()))
                   for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(p0))]
                  for p in params]
    return {"losses": np.asarray(losses), "grad": np.asarray(grad0).T,
            "change": np.asarray(change).T}


def _split_update(tree_of_pairs):
    import jax

    pairs = jax.tree.leaves(tree_of_pairs, is_leaf=lambda x: isinstance(
        x, tuple) and len(x) == 2)
    tdef = jax.tree.structure(tree_of_pairs, is_leaf=lambda x: isinstance(
        x, tuple) and len(x) == 2)
    return (jax.tree.unflatten(tdef, [a for a, _ in pairs]),
            jax.tree.unflatten(tdef, [b for _, b in pairs]))
