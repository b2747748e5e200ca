"""Mixture-of-Experts FFN: shared + routed experts, top-k router with
load-balance auxiliary loss.

Two dispatch strategies:

* ``dense``   — every token through every expert (exact; oracle + tiny smoke).
* ``grouped`` — GShard-style capacity dispatch WITHOUT the [T,E,C] one-hot:
                tokens are scatter-packed into an [E, C, D] buffer by
                (expert, rank-within-expert), batch-matmul'd against the
                expert stack, and gathered back. FLOPs scale with
                k·T·capacity_factor instead of E·T, and the buffer shards
                cleanly (E over the expert-parallel axis, D/F over model).
                Overflow tokens are dropped (standard), underflow slots are
                zero. Fully differentiable (scatter/gather transpose).

Layers with the sigmoid (DeepSeek-V3 noaux_tc) router take a third path,
``moe_share``: dropless dispatch of the experts this chip holds
(``MoEConfig.held_start``, ``num_held``) with routing over all of them —
see its docstring.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.custom_batching import custom_vmap

from repro.config import ModelConfig
from repro.models.layers import Builder
from repro.sharding import constrain


def init_moe(b: Builder, cfg: ModelConfig):
    m = cfg.moe
    d, f, e = cfg.d_model, m.d_expert, m.num_experts
    b.normal("router", (d, e), ("embed", "experts_r"))
    if m.router == "sigmoid":        # selection-only bias, no gradient
        b.zeros("router_bias", (e,), ("experts_r",), dtype=jnp.float32)
    e = m.held                       # the experts this chip holds
    b.normal("wi", (e, d, f), ("experts", "embed", "expert_mlp"))
    b.normal("wg", (e, d, f), ("experts", "embed", "expert_mlp"))
    b.normal("wo", (e, f, d), ("experts", "expert_mlp", "embed"))
    if m.num_shared_experts:
        fs = f * m.num_shared_experts
        b.normal("shared_wi", (d, fs), ("embed", "mlp"))
        b.normal("shared_wg", (d, fs), ("embed", "mlp"))
        b.normal("shared_wo", (fs, d), ("mlp", "embed"))


def router_probs(params, x):
    """x: [T, D] -> router probabilities [T, E] (fp32)."""
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32),
                        params["router"].astype(jnp.float32))
    return jax.nn.softmax(logits, axis=-1), logits


def load_balance_loss(probs, expert_index, num_experts):
    """Switch-transformer aux loss: E * sum_e f_e * P_e."""
    t = probs.shape[0]
    onehot = jax.nn.one_hot(expert_index, num_experts, dtype=jnp.float32)
    f = onehot.sum(axis=(0, 1)) / t            # fraction routed per expert
    p = probs.mean(axis=0)                     # mean router prob per expert
    return num_experts * jnp.sum(f * p)


def _shared(params, x):
    h = jnp.einsum("td,df->tf", x, params["shared_wi"])
    g = jnp.einsum("td,df->tf", x, params["shared_wg"])
    return jnp.einsum("tf,fd->td", jax.nn.silu(g) * h, params["shared_wo"])


def moe_dense(params, cfg: ModelConfig, x):
    """Exact all-experts formulation. x: [T, D] -> ([T, D], aux_loss)."""
    m = cfg.moe
    probs, _ = router_probs(params, x)
    gate_vals, gate_idx = jax.lax.top_k(probs, m.top_k)          # [T,k]
    gate_vals = gate_vals / (gate_vals.sum(-1, keepdims=True) + 1e-9)
    h = jnp.einsum("td,edf->tef", x, params["wi"])
    g = jnp.einsum("td,edf->tef", x, params["wg"])
    y_all = jnp.einsum("tef,efd->ted", jax.nn.silu(g) * h, params["wo"])
    combine = jnp.zeros(probs.shape, x.dtype)
    combine = combine.at[jnp.arange(x.shape[0])[:, None], gate_idx].set(
        gate_vals.astype(x.dtype))
    y = jnp.einsum("te,ted->td", combine, y_all)
    if m.num_shared_experts:
        y = y + _shared(params, x)
    return y, load_balance_loss(probs, gate_idx, m.num_experts)


def moe_grouped(params, cfg: ModelConfig, x, capacity_factor: float = 1.25):
    """Capacity-packed dispatch. x: [T, D] -> ([T, D], aux_loss)."""
    m = cfg.moe
    t, d = x.shape
    e, k = m.num_experts, m.top_k
    cap = max(int(capacity_factor * k * t / e), 1)
    # round capacity to a lane-friendly multiple of 8
    cap = (cap + 7) // 8 * 8

    probs, _ = router_probs(params, x)
    gate_vals, gate_idx = jax.lax.top_k(probs, k)                # [T,k]
    gate_vals = gate_vals / (gate_vals.sum(-1, keepdims=True) + 1e-9)
    aux = load_balance_loss(probs, gate_idx, e)

    # rank of each (token, k) within its expert, via one-hot-free cumsum:
    flat_e = gate_idx.reshape(-1)                                # [T*k]
    onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)          # [T*k, E]
    rank = jnp.cumsum(onehot, axis=0) - 1                        # pos in expert
    rank = jnp.take_along_axis(rank, flat_e[:, None], axis=1)[:, 0]
    keep = rank < cap
    slot = jnp.where(keep, rank, cap)                            # drop -> pad

    # scatter-pack tokens into [E, cap+1, D] (last slot is the trash bin)
    tok = jnp.repeat(jnp.arange(t), k)
    buf = jnp.zeros((e, cap + 1, d), x.dtype)
    buf = buf.at[flat_e, slot].add(x[tok])
    buf = buf[:, :cap]
    buf = constrain(buf, "experts", None, None)

    h = jnp.einsum("ecd,edf->ecf", buf, params["wi"])
    g = jnp.einsum("ecd,edf->ecf", buf, params["wg"])
    h = jax.nn.silu(g) * h
    h = constrain(h, "experts", None, "expert_mlp")
    y_buf = jnp.einsum("ecf,efd->ecd", h, params["wo"])
    y_buf = constrain(y_buf, "experts", None, None)

    # gather back and combine with gate weights (dropped tokens get 0)
    y_tok = y_buf[flat_e, jnp.minimum(slot, cap - 1)]            # [T*k, D]
    w = (gate_vals.reshape(-1) * keep).astype(x.dtype)
    y = jnp.zeros((t, d), x.dtype).at[tok].add(y_tok * w[:, None])
    if m.num_shared_experts:
        y = y + _shared(params, x)
    return y, aux


# ---------------------------------------------------------------------------
# DeepSeek-V3 router + dropless dispatch of this chip's share of the experts
# ---------------------------------------------------------------------------

def sigmoid_route(params, cfg: ModelConfig, x):
    """DeepSeek-V3's noaux_tc router over all ``num_experts``.
    x: [T, D] -> (idx [T, k] selected experts, gates [T, k] f32, scores
    [T, E] f32): ``s = sigmoid(x W_r)``, top-k of ``s + b`` (b gets no
    gradient), gates ``routed_scaling · s_i / Σ_selected s_j``."""
    m = cfg.moe
    s = jax.nn.sigmoid(jnp.einsum("td,de->te", x.astype(jnp.float32),
                                  params["router"].astype(jnp.float32)))
    bias = jax.lax.stop_gradient(params["router_bias"])
    _, idx = jax.lax.top_k(s + bias, m.top_k)
    sel = jnp.take_along_axis(s, idx, axis=-1)
    gates = m.routed_scaling * sel / sel.sum(-1, keepdims=True)
    return idx, gates, s


def seq_balance_loss(scores, idx, num_experts: int):
    """DeepSeek-V3's sequence-wise balance loss (arXiv:2412.19437 eq. 17-20)
    before its weight: per sequence, Σ_i f_i · P_i with f_i = E/(k·S) ·
    (tokens that selected i) and P_i the mean of the normalised scores
    s_i / Σ_j s_j; averaged over sequences. scores [B,S,E], idx [B,S,k]."""
    _, seq, k = idx.shape
    counts = jax.nn.one_hot(idx, num_experts, dtype=jnp.float32).sum((1, 2))
    f = jax.lax.stop_gradient(counts) * num_experts / (k * seq)
    p = (scores / scores.sum(-1, keepdims=True)).mean(1)
    return jnp.mean(jnp.sum(f * p, axis=-1))


def _folded(fn):
    """``fn`` with a batching rule that folds the batch axis into tokens
    and expert groups: args (x [T,D], eid [T,k], then per-expert leaves
    [G, ...]); eid = G marks a pair this chip does not hold. Batched over
    P (the pods vmap), batch p's experts become groups p·G ... p·G + G − 1
    of one call, so the grouped matmul is never batched (the TPU's grouped
    matmuls take no batch dimension) and no pad rows sit between batches.
    Every output's leading axis (tokens, experts, or the folded call's
    sorted pairs) is split evenly over the batch; sorted-pair outputs are
    only handed back to a batched call, which folds them again in the
    same order."""
    wrapped = custom_vmap(fn)

    @wrapped.def_vmap
    def rule(axis_size, in_batched, x, eid, *rest):
        x, eid, *rest = [a if b else jnp.broadcast_to(a, (axis_size,)
                                                      + a.shape)
                         for a, b in zip((x, eid, *rest), in_batched)]
        g = rest[-1].shape[1]                      # experts per batch
        off = (jnp.arange(axis_size) * g).reshape((-1,) + (1,) * (eid.ndim
                                                                  - 1))
        eid = jnp.where(eid < g, eid + off, axis_size * g)
        flat = lambda a: a.reshape((-1,) + a.shape[2:])
        outs = wrapped(flat(x), flat(eid), *map(flat, rest))
        outs = [o.reshape((axis_size, -1) + o.shape[1:]) for o in outs]
        return outs, [True] * len(outs)
    return wrapped


def _dispatch(eid, groups: int):
    """Pairs sorted by held expert: (order [N], its inverse [N], group
    sizes [G], valid [N]: the sorted pair is held; pairs with eid == G
    sort last)."""
    flat = eid.reshape(-1)
    order = jnp.argsort(flat, stable=True)
    sizes = jnp.bincount(flat, length=groups + 1)[:groups].astype(jnp.int32)
    valid = jnp.arange(flat.shape[0]) < sizes.sum()
    return order, jnp.argsort(order), sizes, valid


# Grouped matmuls over the sorted pairs. On the TPU they are the Pallas
# megablox kernels (``gmm`` / ``tgmm``), which visit only the tiles that
# hold pairs; elsewhere ``jax.lax.ragged_dot``. Rows past the groups are
# not computed: they hold anything on the TPU (zeros elsewhere).

def _gmm_tiling(m: int, k: int, n: int):
    """megablox tile sizes (tm, tk, tn) for [m, k] rows against [k, n]
    weights: 512 rows a tile, the contraction in tiles of 512 unless it
    is one expert width (1,408 = 11 x 128), and the output in one tile up
    to that width, else 512 columns."""
    tm = next(t for t in (512, 256, 128) if m % t == 0)
    tk = k if k <= 1408 else 512
    tn = n if n <= 1408 else 512
    return tm, tk, tn


def _tiles_fit(rows: int) -> bool:
    return rows % 128 == 0


def _gmm(a, w, sizes, transpose: bool = False):
    """Each row of a [N, K] times its group's w [G, K, F] ([G, F, K] with
    ``transpose``, times its transpose) -> [N, F]."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

    def other(a, w, sizes):
        w = w.swapaxes(1, 2) if transpose else w
        return jax.lax.ragged_dot(a, w, sizes, preferred_element_type=a.dtype)

    def tpu(a, w, sizes):
        return gmm(a, w, sizes, a.dtype, _gmm_tiling,
                   transpose_rhs=transpose)

    if not _tiles_fit(a.shape[0]):
        return other(a, w, sizes)
    return jax.lax.platform_dependent(a, w, sizes, tpu=tpu, default=other)


def _tgmm(a, b, sizes, groups: int):
    """Per group, its rows of a [N, K] transposed times its rows of
    b [N, F] -> [G, K, F] (the weight gradient of ``_gmm``)."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import tgmm

    def other(a, b, sizes):
        w = jnp.zeros((groups, a.shape[1], b.shape[1]), a.dtype)
        _, pull = jax.vjp(lambda w: jax.lax.ragged_dot(
            a, w, sizes, preferred_element_type=b.dtype), w)
        return pull(b)[0]

    def tpu(a, b, sizes):
        return tgmm(a.swapaxes(0, 1), b, sizes, a.dtype, _gmm_tiling,
                    num_actual_groups=groups)

    if not _tiles_fit(a.shape[0]):
        return other(a, b, sizes)
    return jax.lax.platform_dependent(a, b, sizes, tpu=tpu, default=other)


def _slot(rows, inv, valid, j: int, k: int):
    """Selection slot j of every token, from the sorted pair rows: [T, ...],
    zero where that pair is not held (rows past the groups are not
    computed and may hold anything)."""
    at = inv.reshape(-1, k)[:, j]
    keep = valid[at].reshape((-1,) + (1,) * (rows.ndim - 1))
    return jnp.where(keep, rows[at], 0)


def _combine(rows, inv, valid, weights):
    """Σ_j weights[t, j] · rows[pair (t, j)] in f32, one selection slot at
    a time (no [T, k, D] buffer). rows [N, D] sorted; weights [T, k]."""
    k = weights.shape[1]
    out = jnp.zeros((weights.shape[0], rows.shape[1]), jnp.float32)
    for j in range(k):
        out = out + (_slot(rows, inv, valid, j, k).astype(jnp.float32)
                     * weights[:, j, None])
    return out


@_folded
def _routed_fwd(x, eid, gates, wi, wg, wo):
    """The layer's routed part and the SwiGLU's two grouped-matmul
    outputs (a = xs·wg, h = xs·wi, per sorted pair), which the backward
    reads instead of computing them again."""
    k = eid.shape[1]
    order, inv, sizes, valid = _dispatch(eid, wi.shape[0])
    xs = x[order // k]
    a, h = _gmm(xs, wg, sizes), _gmm(xs, wi, sizes)
    ys = _gmm(jax.nn.silu(a) * h, wo, sizes)
    return [_combine(ys, inv, valid, gates).astype(x.dtype), a, h]


@_folded
def _routed_bwd(x, eid, gates, dy, a, h, wi, wg, wo):
    """Gradients of ``routed_experts`` from the forward's a and h: six
    grouped matmuls (three row gradients, three weight gradients). A
    gate's gradient is <ys, dy> = <silu(a)·h, dy·wo^T>, so the expert
    outputs ys are not needed."""
    k = eid.shape[1]
    groups = wi.shape[0]
    order, inv, sizes, valid = _dispatch(eid, groups)
    xs = x[order // k]
    dyr = jnp.where(valid[:, None], dy[order // k], 0).astype(x.dtype)
    du0 = _gmm(dyr, wo, sizes, transpose=True).astype(jnp.float32)
    a32, h32 = a.astype(jnp.float32), h.astype(jnp.float32)
    sig = jax.nn.sigmoid(a32)
    u = a32 * sig * h32
    dpair = jnp.sum(u * du0, -1)[:, None]
    dgates = jnp.concatenate([_slot(dpair, inv, valid, j, k)
                              for j in range(k)], axis=1)
    gs = gates.reshape(-1)[order][:, None]
    dwo = _tgmm((u * gs).astype(x.dtype), dyr, sizes, groups)
    du = du0 * gs
    dh = (du * a32 * sig).astype(x.dtype)
    da = (du * h32 * sig * (1 + a32 * (1 - sig))).astype(x.dtype)
    dxs = (_gmm(da, wg, sizes, transpose=True).astype(jnp.float32)
           + _gmm(dh, wi, sizes, transpose=True).astype(jnp.float32)
           ).astype(x.dtype)
    dwg, dwi = _tgmm(xs, da, sizes, groups), _tgmm(xs, dh, sizes, groups)
    dx = _combine(dxs, inv, valid, jnp.ones_like(gates)).astype(x.dtype)
    return [dx, dgates, dwi.astype(wi.dtype), dwg.astype(wg.dtype),
            dwo.astype(wo.dtype)]


@jax.custom_vjp
def routed_experts(x, eid, gates, wi, wg, wo):
    """Σ over each token's selected, held experts of ``gate · SwiGLU_e(x)``.
    x [T,D]; eid [T,k] held-expert index per selection (G = not held);
    gates [T,k] f32; wi/wg [G,D,F], wo [G,F,D]. Dropless: every (token,
    held expert) pair is computed, in one grouped matmul per weight."""
    return _routed_fwd(x, eid, gates, wi, wg, wo)[0]


def _routed_vjp_fwd(x, eid, gates, wi, wg, wo):
    y, a, h = _routed_fwd(x, eid, gates, wi, wg, wo)
    return y, (x, eid, gates, a, h, wi, wg, wo)


def _routed_vjp_bwd(res, dy):
    x, eid, gates, a, h, wi, wg, wo = res
    dx, dgates, dwi, dwg, dwo = _routed_bwd(x, eid, gates, dy, a, h, wi, wg,
                                            wo)
    return dx, None, dgates, dwi, dwg, dwo


routed_experts.defvjp(_routed_vjp_fwd, _routed_vjp_bwd)


def moe_share(params, cfg: ModelConfig, x):
    """DeepSeek-V3 MoE layer, this chip's share. x: [B, S, D] ->
    ([B, S, D], balance loss, stats).

    The router scores all ``num_experts`` and selects ``top_k`` of them;
    the layer computes, dropless, the selected experts among the ones it
    holds (``held_start`` ... + ``num_held``) and adds the shared experts.
    What absent experts would add belongs to the chips holding them; on
    one chip there is no exchange. ``stats``: ``load`` [E] int32, the
    tokens that selected each expert (the bias update's input), and
    ``dropped``, held pairs left uncomputed (0: nothing has a capacity)."""
    m = cfg.moe
    b_, s, d = x.shape
    flat = x.reshape(b_ * s, d)
    with jax.named_scope("moe_route"):
        idx, gates, scores = sigmoid_route(params, cfg, flat)
        aux = seq_balance_loss(scores.reshape(b_, s, -1),
                               idx.reshape(b_, s, -1), m.num_experts)
        load = jnp.bincount(idx.reshape(-1), length=m.num_experts)
        local = idx - m.held_start
        eid = jnp.where((local >= 0) & (local < m.held), local, m.held)
        eid = eid.astype(jnp.int32)
    with jax.named_scope("moe_experts"):
        y = routed_experts(flat, eid, gates, params["wi"], params["wg"],
                           params["wo"])
    if m.num_shared_experts:
        with jax.named_scope("moe_shared"):
            y = y + _shared(params, flat)
    held = jnp.sum(eid < m.held)
    stats = {"load": load.astype(jnp.int32),
             "dropped": (held - jnp.minimum(held, eid.size)).astype(
                 jnp.int32)}
    return y.reshape(b_, s, d), aux, stats


def moe_ffn_stats(params, cfg: ModelConfig, x, strategy: str = "grouped"):
    """``moe_ffn`` and the layer's stats (None but for the sigmoid
    router's share, ``moe_share``)."""
    if cfg.moe.router == "sigmoid":
        return moe_share(params, cfg, x)
    return moe_ffn(params, cfg, x, strategy) + (None,)


def moe_ffn(params, cfg: ModelConfig, x, strategy: str = "grouped"):
    """x: [B, S, D] -> ([B, S, D], aux_loss).

    strategies: dense (exact oracle) | grouped (single-device capacity
    dispatch) | eplocal (shard_map expert parallelism — production).
    The sigmoid router always takes ``moe_share``."""
    if cfg.moe.router == "sigmoid":
        return moe_share(params, cfg, x)[:2]
    if strategy.startswith("eplocal"):
        from repro.models.moe_eplocal import moe_eplocal
        return moe_eplocal(params, cfg, x,
                           a2a_fp8=strategy.endswith("fp8"))
    b_, s, d = x.shape
    flat = x.reshape(b_ * s, d)
    if strategy == "dense":
        y, aux = moe_dense(params, cfg, flat)
    else:
        y, aux = moe_grouped(params, cfg, flat)
    return y.reshape(b_, s, d), aux
