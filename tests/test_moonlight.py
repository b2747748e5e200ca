"""Moonlight-16B-A3B's block (DeepSeek-V3) against plain jnp at toy widths
on seeded random weights: latent attention (including the blocked path
above BLOCKED_ATTN_THRESHOLD), the sigmoid router and its bias update, the
expert share (the shares of a layer add up to the uncut layer), dropless
dispatch under all-to-one routing, the routed experts' backward, the
folded grouped matmul under the pods vmap, and the pod trainer's step on
the model."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import reduced
from repro.configs import get_config
from repro.models import blocks, mla, moe
from repro.models import model as mm
from repro.models.attention import BLOCKED_ATTN_THRESHOLD
from repro.models.layers import Builder


def toy(**moe_kw):
    cfg = reduced(get_config("moonlight-16b-a3b"), num_layers=3, d_model=64)
    return dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, **moe_kw))


def build(init, cfg, seed=0):
    b = Builder(jax.random.PRNGKey(seed), jnp.float32)
    init(b, cfg)
    return jax.tree.map(lambda a: a * 20.0 if a.ndim > 1 else a, b.params)


# -- plain jnp twins ---------------------------------------------------------

def plain_mla(p, cfg, x):
    """One sequence x [S, D]: the MLA equations (see models/mla.py), full S x S
    scores."""
    m = cfg.mla
    s = x.shape[0]
    pos = np.arange(s)

    def rot(v):
        d = v.shape[-1]
        f = 1.0 / cfg.rope_theta ** (np.arange(d // 2) * 2.0 / d)
        ang = pos[:, None, None] * f
        out = np.empty_like(v)
        out[..., 0::2] = v[..., 0::2] * np.cos(ang) - v[..., 1::2] * np.sin(ang)
        out[..., 1::2] = v[..., 0::2] * np.sin(ang) + v[..., 1::2] * np.cos(ang)
        return out

    p = jax.tree.map(np.asarray, p)
    q = np.einsum("sd,dhk->shk", x, p["wq"])
    kva = x @ p["wkv_a"]
    c = kva[:, :m.kv_lora_rank]
    c = c / np.sqrt((c * c).mean(-1, keepdims=True) + cfg.norm_eps) \
        * p["kv_norm"]
    kv = np.einsum("sc,chk->shk", c, p["wkv_b"])
    nope = m.qk_nope_head_dim
    k_rope = rot(kva[:, None, m.kv_lora_rank:])
    q_rope = rot(q[..., nope:])
    sc = (np.einsum("qhk,shk->hqs", q[..., :nope], kv[..., :nope])
          + np.einsum("qhk,sk->hqs", q_rope, k_rope[:, 0])) \
        / np.sqrt(m.qk_head_dim)
    sc = np.where(pos[None, None] <= pos[None, :, None], sc, -np.inf)
    pr = np.exp(sc - sc.max(-1, keepdims=True))
    pr /= pr.sum(-1, keepdims=True)
    out = np.einsum("hqs,shk->qhk", pr, kv[..., nope:])
    return np.einsum("shk,hkd->sd", out, p["wo"])


def plain_route(p, cfg, x):
    m = cfg.moe
    s = 1 / (1 + np.exp(-(x @ np.asarray(p["router"]))))
    sel = np.argsort(-(s + np.asarray(p["router_bias"])), -1,
                     kind="stable")[:, :m.top_k]
    g = np.take_along_axis(s, sel, -1)
    return sel, m.routed_scaling * g / g.sum(-1, keepdims=True), s


def plain_share(p, cfg, x, start, count):
    """Experts [start, start + count) of the uncut layer's routed part, for
    x [T, D]; p holds all experts."""
    sel, gates, _ = plain_route(p, cfg, x)
    p = jax.tree.map(np.asarray, p)
    y = np.zeros_like(x)
    for t in range(x.shape[0]):
        for e, g in zip(sel[t], gates[t]):
            if start <= e < start + count:
                hi, hg = x[t] @ p["wi"][e], x[t] @ p["wg"][e]
                y[t] += g * ((hg / (1 + np.exp(-hg)) * hi) @ p["wo"][e])
    return y


def plain_shared(p, x):
    p = jax.tree.map(np.asarray, p)
    hg = x @ p["shared_wg"]
    return (hg / (1 + np.exp(-hg)) * (x @ p["shared_wi"])) @ p["shared_wo"]


# -- tests -------------------------------------------------------------------

@pytest.mark.parametrize("seq", [48, BLOCKED_ATTN_THRESHOLD + 256])
def test_mla_matches_plain(seq):
    """The expanded MLA form, at a short sequence and on the blocked
    online-softmax path above the threshold (its causal tile skip too)."""
    cfg = toy()
    p = build(mla.init_mla, cfg)
    x = np.random.default_rng(1).normal(size=(seq, cfg.d_model)).astype(
        np.float32)
    pos = jnp.arange(seq)[None]
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda p, x: mla.mla(p, cfg, x, pos))(p, x[None])[0]
    want = plain_mla(p, cfg, x.astype(np.float64))
    np.testing.assert_allclose(got, want, rtol=2e-4,
                               atol=2e-4 * np.abs(want).max())


def test_router_selects_on_biased_scores_and_gates_on_plain_ones():
    cfg = toy(num_experts=16, top_k=6)
    p = build(moe.init_moe, cfg)
    p["router_bias"] = jnp.asarray(
        np.random.default_rng(2).normal(size=16) * 0.5, jnp.float32)
    x = np.random.default_rng(3).normal(size=(40, cfg.d_model)).astype(
        np.float32)
    with jax.default_matmul_precision("highest"):
        idx, gates, s = moe.sigmoid_route(p, cfg, x)
    sel, want_g, want_s = plain_route(p, cfg, x.astype(np.float64))
    np.testing.assert_array_equal(np.asarray(idx), sel)
    np.testing.assert_allclose(gates, want_g, rtol=1e-5)
    np.testing.assert_allclose(s, want_s, rtol=1e-5)
    unbiased = np.sort(np.argsort(-want_s, -1)[:, :6], -1)
    assert (np.sort(sel, -1) != unbiased).any()     # the bias mattered


def test_router_bias_moves_by_the_sign_rule():
    """b += rate · sign(mean load − load): over-loaded experts down,
    under-loaded up, at-mean unchanged; only the bias moves."""
    cfg = dataclasses.replace(toy(num_experts=4, top_k=2), scan_layers=True)
    params = mm.init_params(jax.random.PRNGKey(0), cfg)
    load = jnp.asarray([[3, 1, 2, 2], [0, 4, 2, 2]], jnp.int32)   # mean 2
    stats = {"scan": {"0": {"load": load, "dropped": jnp.zeros(2)}}}
    new = blocks.update_router_biases(params, cfg, stats)
    rate = cfg.moe.bias_update_rate
    np.testing.assert_allclose(new["scan"]["0"]["moe"]["router_bias"],
                               rate * np.array([[-1, 1, 0, 0], [1, -1, 0, 0]]))
    rest = lambda t: [a for path, a in jax.tree_util.tree_leaves_with_path(t)
                      if "router_bias" not in jax.tree_util.keystr(path)]
    for a, b in zip(rest(params), rest(new)):
        assert a is b


def test_expert_shares_add_up_to_the_uncut_layer():
    """Eight shares of a 64-expert layer, each holding 8 experts and
    routing over all 64, add up to the uncut layer, the shared experts
    counted once."""
    full = toy(num_experts=64, top_k=6)
    p = build(moe.init_moe, full)
    p["router_bias"] = jnp.asarray(
        np.random.default_rng(4).normal(size=64) * 0.1, jnp.float32)
    x = np.random.default_rng(5).normal(size=(1, 24, full.d_model)).astype(
        np.float32)
    shared = plain_shared(p, x[0].astype(np.float64))
    total = np.zeros_like(shared)
    with jax.default_matmul_precision("highest"):
        for j in range(8):
            cfg = toy(num_experts=64, top_k=6, num_held=8, held_start=8 * j)
            q = dict(p, **{w: p[w][8 * j:8 * j + 8]
                           for w in ("wi", "wg", "wo")})
            y, _, st = moe.moe_share(q, cfg, x)
            total += np.asarray(y[0], np.float64) - shared
            assert int(st["dropped"]) == 0
    want = plain_share(p, full, x[0].astype(np.float64), 0, 64)
    np.testing.assert_allclose(total, want, rtol=1e-4,
                               atol=1e-4 * np.abs(want).max())


def test_all_to_one_routing_drops_nothing():
    """Every token picks the same six held experts (a bias far above the
    scores): all T·k pairs land on this chip and all are computed."""
    cfg = toy(num_experts=16, top_k=6, num_held=8, held_start=8)
    p = build(moe.init_moe, toy(num_experts=16, top_k=6))
    p["router_bias"] = jnp.zeros(16).at[9:15].set(100.0)
    q = dict(p, **{w: p[w][8:] for w in ("wi", "wg", "wo")})
    x = np.random.default_rng(6).normal(size=(2, 20, cfg.d_model)).astype(
        np.float32)
    with jax.default_matmul_precision("highest"):
        y, _, st = moe.moe_share(q, cfg, x)
    assert int(st["dropped"]) == 0
    np.testing.assert_array_equal(st["load"], np.r_[np.zeros(9), np.full(
        6, 40), 0])
    flat = x.reshape(-1, cfg.d_model).astype(np.float64)
    want = plain_share(p, cfg, flat, 8, 8) + plain_shared(p, flat)
    np.testing.assert_allclose(y.reshape(flat.shape), want, rtol=1e-4,
                               atol=1e-4 * np.abs(want).max())


def test_routed_experts_gradients_match_plain():
    """The routed experts' own backward (from the forward's saved SwiGLU
    inputs, gate gradients as <silu(a)·h, dy·wo^T>) against autodiff of
    the plain per-pair sum, with pairs this chip does not hold."""
    rng = np.random.default_rng(9)
    t, k, g, d, f = 40, 6, 4, 16, 8
    x = jnp.asarray(rng.normal(size=(t, d)), jnp.float32)
    eid = jnp.asarray(rng.integers(0, g + 1, size=(t, k)), jnp.int32)
    gates = jnp.asarray(rng.random(size=(t, k)), jnp.float32)
    w = [jnp.asarray(rng.normal(size=(g,) + s) * 0.3, jnp.float32)
         for s in ((d, f), (d, f), (f, d))]
    dy = jnp.asarray(rng.normal(size=(t, d)), jnp.float32)

    def plain(x, gates, wi, wg, wo):
        held = (eid < g)[..., None]
        e = jnp.minimum(eid, g - 1)
        hi = jnp.einsum("td,tkdf->tkf", x, wi[e])
        hg = jnp.einsum("td,tkdf->tkf", x, wg[e])
        ys = jnp.einsum("tkf,tkfd->tkd", jax.nn.silu(hg) * hi, wo[e])
        return jnp.sum(jnp.where(held, ys, 0) * gates[..., None], 1)

    def program(x, gates, wi, wg, wo):
        return moe.routed_experts(x, eid, gates, wi, wg, wo)

    with jax.default_matmul_precision("highest"):
        want_y, pull = jax.vjp(plain, x, gates, *w)
        got_y, pull_got = jax.vjp(program, x, gates, *w)
        want, got = pull(dy), pull_got(dy)
    np.testing.assert_allclose(got_y, want_y, rtol=1e-5, atol=1e-5)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-4,
                                   atol=1e-5 * np.abs(b).max())


def test_folded_grouped_matmul_under_vmap_matches_each_pod():
    """Batched over pods, the routed experts fold the pod axis into expert
    groups; values and gradients equal each pod's own call."""
    cfg = toy(num_experts=16, top_k=6, num_held=4, held_start=4)
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(size=(2, 30, cfg.d_model)), jnp.float32)
    eid = jnp.asarray(rng.integers(0, 5, size=(2, 30, 6)), jnp.int32)
    gates = jnp.asarray(rng.random(size=(2, 30, 6)), jnp.float32)
    w = [jnp.asarray(rng.normal(size=(2, 4) + s) * 0.1, jnp.float32)
         for s in ((64, 32), (64, 32), (32, 64))]

    def f(x, e, g, *w):
        return jnp.sum(jnp.sin(moe.routed_experts(x, e, g, *w)))

    grad = jax.grad(f, argnums=(0, 2, 3, 4, 5))
    batched = jax.jit(jax.vmap(grad))(x, eid, gates, *w)
    for i in range(2):
        one = grad(x[i], eid[i], gates[i], *[a[i] for a in w])
        for b, o in zip(batched, one):
            np.testing.assert_allclose(b[i], o, rtol=1e-5, atol=1e-6)


def test_pod_step_trains_moonlight_and_moves_the_bias():
    """build_fl_train_step on the model, two pods: finite losses, each
    pod's loads count T·k selections per layer, nothing dropped, the
    router bias moved by the rate."""
    from repro.launch.steps import build_fl_train_step
    from repro.optim import make_optimizer

    cfg = dataclasses.replace(toy(num_experts=16, top_k=6, num_held=4),
                              scan_layers=True)
    params = mm.init_params(jax.random.PRNGKey(0), cfg)
    opt = make_optimizer("adafactor", 1e-2)
    stack = lambda t: jax.tree.map(lambda a: jnp.stack([a, a]), t)
    toks = jnp.asarray(np.random.default_rng(8).integers(
        0, 200, size=(2, 1, 32)), jnp.int32)
    step = jax.jit(build_fl_train_step(cfg, opt, with_stats=True))
    p2, _, _, losses, st = step(stack(params), stack(opt.init(params)),
                                jnp.zeros((), jnp.int32),
                                {"tokens": toks, "labels": toks})
    assert np.isfinite(np.asarray(losses)).all()
    load = np.asarray(st["scan"]["0"]["load"])          # [pods, layers, E]
    assert (load.sum(-1) == 32 * 6).all()
    assert int(np.asarray(st["scan"]["0"]["dropped"]).sum()) == 0
    bias = np.asarray(p2["scan"]["0"]["moe"]["router_bias"])
    np.testing.assert_allclose(np.abs(bias)[load != 32 * 6 / 16],
                               cfg.moe.bias_update_rate)
