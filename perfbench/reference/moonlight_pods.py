"""Plain reference of cross-silo DeFTA on Moonlight-16B-A3B's block
(``pods_moe`` driver): straightforward ``jax.numpy`` in float32 at
``highest`` matmul precision, nothing imported from the program but the
Qwen3 reference's Adafactor and gossip round.

Model (DeepSeek-V3 block, hf:moonshotai/Moonlight-16B-A3B): token embedding;
per layer RMSNorm → multi-head latent attention → residual → RMSNorm →
SwiGLU MLP (layer 0) or the mixture of experts (the rest) → residual;
final RMSNorm; untied output head; next-token cross-entropy averaged over
every target position, plus alpha times each MoE layer's sequence-wise
balance loss.

* Latent attention, per token x: ``q = x W_q`` split nope | rope;
  ``[c | k_rope] = x W_kva``, ``c ← RMSNorm(c)``; ``[k_nope | v] = c W_kvb``
  per head; rotary embedding on adjacent pairs of q_rope and k_rope (one
  k_rope per token, shared by the heads); scores (q_nope·k_nope +
  q_rope·k_rope) / √(nope + rope), causal softmax, Σ p·v, then ``W_o``.
  Computed one block of queries at a time.
* Experts: router scores ``s = sigmoid(x W_r)`` over all E experts; the
  top-k of ``s + b`` are selected; gates ``scaling · s_i / Σ_sel s_j``.
  This chip holds experts [held_start, held_start + held) and computes each
  of them over every token, weighted by its gate where selected (0
  elsewhere): the held share of the layer. Two shared experts are one
  SwiGLU of twice the width. After each step ``b += gamma · sign(mean
  load − load)``, load counting the silo's tokens that selected each
  expert. b gets no gradient.

The weights follow the program's seeded layout (a key split per named
parameter in declaration order, the repeated layers drawn from
``fold_in(split, layer)``), bfloat16 as drawn, float32 from there on; the
router bias starts at 0 in float32.

``dtype="float8_e4m3fn"`` is the control: every matmul operand rounded
through float8 with a per-tensor scale (the Qwen3 reference's rule).
"""
from __future__ import annotations

import numpy as np

from reference.qwen3_pods import _split, _split_update, adafactor, gossip

FAULTS = ("half_batch", "no_exchange", "capacity_drop", "held_only",
          "biased_gates")


def _dims(m: dict) -> dict:
    """The model's widths from the configuration file's published keys
    (Hugging Face names, at the file's top level)."""
    return {"d": m["hidden_size"], "h": m["num_attention_heads"],
            "nope": m["qk_nope_head_dim"], "rope": m["qk_rope_head_dim"],
            "vd": m["v_head_dim"], "c": m["kv_lora_rank"],
            "ff": m["intermediate_size"], "fe": m["moe_intermediate_size"],
            "shared": m["n_shared_experts"], "held": m["n_routed_experts"],
            "experts": m["n_routed_experts_published"],
            "start": m["held_start"], "k": m["num_experts_per_tok"],
            "vocab": m["vocab_size"], "layers": m["num_hidden_layers"],
            "dense": m["first_k_dense_replace"]}


def init_params(m: dict, key):
    """The parameter tree, bfloat16 (the bias float32), as the program
    lays it out: {embedding, lm_head, ln_f, prefix: {"0": dense layer},
    scan: {"0": MoE layers stacked [L-1, ...]}}."""
    import jax
    import jax.numpy as jnp

    bf = jnp.bfloat16
    z = _dims(m)
    d, h = z["d"], z["h"]

    def normal(k, shape, scale=0.02):
        return (scale * jax.random.normal(k, shape, jnp.float32)).astype(bf)

    def attn(key):
        key, k_q = _split(key)
        key, k_a = _split(key)
        key, k_b = _split(key)
        key, k_o = _split(key)
        return {"wq": normal(k_q, (d, h, z["nope"] + z["rope"])),
                "wkv_a": normal(k_a, (d, z["c"] + z["rope"])),
                "kv_norm": jnp.ones((z["c"],), bf),
                "wkv_b": normal(k_b, (z["c"], h, z["nope"] + z["vd"])),
                "wo": normal(k_o, (h, z["vd"], d))}

    def swiglu(key, names, shapes):
        out = {}
        for name, shape in zip(names, shapes):
            key, k = _split(key)
            out[name] = normal(k, shape)
        return key, out

    def dense_layer(key):
        key, k_attn = _split(key)
        key, k_mlp = _split(key)
        _, mlp = swiglu(k_mlp, ("wi", "wg", "wo"),
                        ((d, z["ff"]), (d, z["ff"]), (z["ff"], d)))
        return {"ln1": jnp.ones((d,), bf), "ln2": jnp.ones((d,), bf),
                "attn": attn(k_attn), "mlp": mlp}

    def moe_layer(key):
        key, k_attn = _split(key)
        key, k_moe = _split(key)
        e, f, fs = z["held"], z["fe"], z["fe"] * z["shared"]
        k_moe, k_r = _split(k_moe)
        k_moe, moe = swiglu(k_moe, ("wi", "wg", "wo", "shared_wi",
                                    "shared_wg", "shared_wo"),
                            ((e, d, f), (e, d, f), (e, f, d), (d, fs),
                             (d, fs), (fs, d)))
        moe["router"] = normal(k_r, (d, z["experts"]))
        moe["router_bias"] = jnp.zeros((z["experts"],), jnp.float32)
        return {"ln1": jnp.ones((d,), bf), "ln2": jnp.ones((d,), bf),
                "attn": attn(k_attn), "moe": moe}

    key, k_emb = _split(key)
    key, k_head = _split(key)
    key, k_prefix = _split(key)
    key, k_scan = _split(key)
    prefix = {}
    for i in range(z["dense"]):
        k_prefix, k_layer = _split(k_prefix)
        prefix[str(i)] = dense_layer(k_layer)
    layers = []
    for r in range(z["layers"] - z["dense"]):
        k_scan, k_layer = _split(k_scan)
        layers.append(moe_layer(jax.random.fold_in(k_layer, r)))
    return {"embedding": normal(k_emb, (z["vocab"], d), 0.01),
            "lm_head": normal(k_head, (d, z["vocab"])),
            "ln_f": jnp.ones((d,), bf), "prefix": prefix,
            "scan": {"0": jax.tree.map(lambda *a: jnp.stack(a), *layers)}}


def model_loss(m: dict, low=None, fault: str = "", block_q: int = 1024):
    """loss(params, tokens [S]) -> (loss, loads [L-1, E]) for one row,
    float32 (``low`` rounds every matmul operand through that dtype: the
    control). ``fault`` plants a routing fault (``capacity_drop``,
    ``held_only``, ``biased_gates``) or ``half_batch`` (the loss over the
    first half of the row's targets)."""
    import jax
    import jax.numpy as jnp

    z = _dims(m)
    eps, theta = m["rms_norm_eps"], m["rope_theta"]
    h, nope, rope, c = z["h"], z["nope"], z["rope"], z["c"]
    e_all, k, held, start = z["experts"], z["k"], z["held"], z["start"]
    alpha, scaling = m["seq_aux_alpha"], m["routed_scaling_factor"]

    def rd(a):
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

    def rotate(x, pos):
        """Adjacent pairs (x[2i], x[2i+1]) turned by pos · theta^(-2i/d)."""
        half = x.shape[-1] // 2
        freqs = 1.0 / (theta ** (np.arange(half, dtype=np.float32) / half))
        ang = pos[:, None].astype(jnp.float32) * freqs
        cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
        x0, x1 = x[..., 0::2], x[..., 1::2]
        return jnp.stack([x0 * cos - x1 * sin, x0 * sin + x1 * cos],
                         -1).reshape(x.shape)

    def attention(x, a):
        s = x.shape[0]
        pos = jnp.arange(s)
        q = mm("sd,dhk->shk", x, a["wq"])
        kva = mm("sd,dk->sk", x, a["wkv_a"])
        lat = rms(kva[:, :c], a["kv_norm"])
        kv = mm("sc,chk->shk", lat, a["wkv_b"])
        k_rope = rotate(kva[:, None, c:], pos)                # [S, 1, rope]
        keys = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_rope, (s, h, rope))], -1)
        v = kv[..., nope:]
        q = jnp.concatenate([q[..., :nope], rotate(q[..., nope:], pos)], -1)
        bq = min(block_q, s)

        @jax.checkpoint
        def one_block(_, i):
            qb = jax.lax.dynamic_slice_in_dim(q, i * bq, bq)
            sc = mm("qhk,shk->hqs", qb, keys) / np.sqrt(nope + rope)
            qpos = i * bq + jnp.arange(bq)
            sc = jnp.where(pos[None, None, :] <= qpos[None, :, None], sc,
                           -1e30)
            return None, mm("hqs,shk->qhk", jax.nn.softmax(sc, -1), v)

        _, out = jax.lax.scan(one_block, None, jnp.arange(s // bq))
        return mm("shk,hkd->sd", out.reshape(s, h, -1), a["wo"])

    def swiglu(x, wi, wg, wo):
        return mm("sf,fd->sd", jax.nn.silu(mm("sd,df->sf", x, wg))
                  * mm("sd,df->sf", x, wi), wo)

    def experts(x, p):
        """The held share of the layer, its balance loss and its loads."""
        t = x.shape[0]
        s = jax.nn.sigmoid(mm("sd,de->se", x, p["router"]))       # [T, E]
        b = jax.lax.stop_gradient(p["router_bias"])
        choose = s + b
        if fault == "held_only":            # top-k among the held alone
            choose = jnp.full_like(choose, -jnp.inf).at[
                :, start:start + held].set(choose[:, start:start + held])
        _, idx = jax.lax.top_k(choose, k)
        picked = jnp.zeros((t, e_all), bool).at[
            jnp.arange(t)[:, None], idx].set(True)
        by = s + b if fault == "biased_gates" else s
        gate = jnp.where(picked, by, 0.0)
        gate = scaling * gate / gate.sum(-1, keepdims=True)
        load = picked.sum(0)
        if fault == "capacity_drop":        # capacity-1.25 dispatch
            cap = (int(1.25 * k * t / e_all) + 7) // 8 * 8
            rank = jnp.cumsum(picked, 0)
            gate = jnp.where(rank <= cap, gate, 0.0)
        f = load * e_all / (k * t)
        aux = jnp.sum(f * (s / s.sum(-1, keepdims=True)).mean(0))
        y = swiglu(x, p["shared_wi"], p["shared_wg"], p["shared_wo"])
        for j in range(held):
            y = y + gate[:, start + j, None] * swiglu(x, p["wi"][j],
                                                      p["wg"][j],
                                                      p["wo"][j])
        return y, aux, load

    def dense_layer(x, p):
        x = x + attention(rms(x, p["ln1"]), p["attn"])
        f = p["mlp"]
        return x + swiglu(rms(x, p["ln2"]), f["wi"], f["wg"], f["wo"])

    def moe_layer(carry, p):
        x, aux = carry
        x = x + attention(rms(x, p["ln1"]), p["attn"])
        y, a, load = experts(rms(x, p["ln2"]), p["moe"])
        return (x + y, aux + a), load

    dense_layer = jax.checkpoint(dense_layer)
    moe_layer = jax.checkpoint(moe_layer)

    def loss(params, tokens):
        x = params["embedding"][tokens]
        for i in sorted(params["prefix"], key=int):
            x = dense_layer(x, params["prefix"][i])
        (x, aux), loads = jax.lax.scan(moe_layer, (x, 0.0),
                                       params["scan"]["0"])
        x = rms(x, params["ln_f"])
        logits = mm("sd,dv->sv", x, params["lm_head"])[:-1]
        logp = jax.nn.log_softmax(logits, -1)
        ll = jnp.take_along_axis(logp, tokens[1:, None], -1)[:, 0]
        if fault == "half_batch":
            ll = ll[:ll.shape[0] // 2]
        return -jnp.mean(ll) + alpha * aux, loads

    return loss


def run_steps(config: dict, traffic: dict, seed: int, *, steps: int,
              dtype: str = "float32", fault: str = ""):
    """The first ``steps`` steps of every pod from the seed, a gossip round
    after every ``gossip_every`` steps. Returns losses [steps, pods], the
    first step's gradient norm per leaf [leaves, pods], each leaf's change
    from the initial weights per pod [leaves, pods], and the loads of the
    held experts [steps, pods, layers, held]. ``fault``: one of
    ``FAULTS`` (``no_exchange``: the gossip round leaves every pod as it
    is; the rest see ``model_loss``)."""
    import contextlib

    import jax
    import jax.numpy as jnp

    import generate

    m = config                      # the published keys sit at the top level
    z = _dims(m)
    pods, gb, seq = config["pods"], config["global_batch"], config["seq"]
    low = None if dtype == "float32" else jnp.dtype(dtype)
    key = jax.random.PRNGKey(generate.prng_seed(seed))
    prec = jax.default_matmul_precision("highest") if low is None \
        else contextlib.nullcontext()
    loss = model_loss(m, low, fault)
    opt_init, opt_update = adafactor(config["learning_rate"])
    every, gamma = traffic["gossip_every"], m["bias_update_gamma"]
    with prec:
        p0 = jax.jit(lambda k: init_params(m, k))(key)
        grad_fn = jax.jit(jax.value_and_grad(loss, has_aux=True))
        upd_fn = jax.jit(lambda p, g, s, t: jax.tree.map(
            lambda a, b, c: opt_update(a, b, c, t), p, g, s))

        def bias_step(p, load):
            load = load.astype(jnp.float32)
            moe = dict(p["scan"]["0"]["moe"])
            moe["router_bias"] = moe["router_bias"] + gamma * jnp.sign(
                load.mean(-1, keepdims=True) - load)
            return {**p, "scan": {"0": {**p["scan"]["0"], "moe": moe}}}

        bias_step = jax.jit(bias_step)
        start = jax.tree.map(lambda a: a.astype(jnp.float32), p0)
        params = [start for _ in range(pods)]
        states = [jax.tree.map(opt_init, start) for _ in range(pods)]
        del start
        resid = None
        conf = jnp.zeros((pods, pods))
        last = jnp.zeros((pods,))
        pkey = jax.random.fold_in(key, config["pod_key_tag"])
        sizes = np.full(pods, gb, np.float32)
        losses, grad0, loads = [], None, []
        for i in range(steps):
            batch = generate.token_batch(traffic, gb, seq, z["vocab"], seed,
                                         i).reshape(pods, gb // pods, seq)
            row, grads_i, load_i = [], [], []
            for pi in range(pods):
                tot, g_acc, l_acc = 0.0, None, 0
                for r in batch[pi]:
                    (lv, ld), g = grad_fn(params[pi], jnp.asarray(r))
                    tot += float(lv)
                    l_acc = l_acc + ld
                    g_acc = g if g_acc is None else jax.tree.map(
                        jnp.add, g_acc, g)
                g = jax.tree.map(lambda a: a / len(batch[pi]), g_acc)
                row.append(tot / len(batch[pi]))
                grads_i.append(g)
                params[pi], states[pi] = _split_update(
                    upd_fn(params[pi], g, states[pi], jnp.float32(i + 1)))
                params[pi] = bias_step(params[pi], l_acc)
                load_i.append(np.asarray(l_acc)[:, z["start"]:z["start"]
                                                + z["held"]])
            losses.append(row)
            loads.append(load_i)
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
            "change": np.asarray(change).T, "loads": np.asarray(loads)}
