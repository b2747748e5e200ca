"""Decentralized Trust System (paper §3.3, Algorithm 3).

Every worker i keeps a confidence score c_{i→j} per peer j (init 0 =
neutral). After each round it observes loss_trust = loss^t − loss_last
(its OWN training-loss delta after aggregating the sampled peers' models)
and updates

    c_i ← c_i − m_i ∘ p_i · loss_trust          (Algorithm 3, line 12)

where m_i is the 0-1 sampled mask and p_i the aggregation weights: peers
whose inclusion made the loss go up lose confidence proportionally to how
much of the aggregate they contributed. Sampling weights are

    θ_i = softmax(cRELU(c_i))   with  cRELU(x) = x (x≤0), 0.2x (x>0)

so bad peers are penalized steeply (constraint 1), good peers climb slowly
together (constraint 2) and reliable peers stay near-equiprobable
(constraint 3).

The **time machine** (lines 1–4): back up the best-loss model; if a round
yields a damaged model (non-finite loss or an explosion), restore the
backup, run one compensation training step, and push loss_trust = +inf so
every sampled peer of that round is maximally penalized (we clamp to a
large finite value for numerics).

**Geometric trust (DTS v2).** The loss-delta signal is a scalar per
receiver: every sampled peer of a bad round is penalized alike, and under
non-iid heterogeneity a label-flip attacker's contribution is
indistinguishable from an honest peer's (the PR-3 finding: "a defense
needs update geometry, not just loss deltas"; cf. the DFL security surveys
and served-trust designs like DeTrust-FL). ``geom_scores`` supplies the
missing per-(receiver, peer) resolution from deltas the round already
materializes: each peer j's UPDATE delta u_j — the local step it applied
on top of its adopted aggregate (``trained − start`` in the simulation
engines; the round displacement on the pod path). NOT the raw model
difference ``x_j − x_i``: under non-iid spread attackers cluster while
honest workers scatter, so model differences make the poison look
central (see ``geom_scores``). Each u_j is scored by

* cosine distance to the trust-weighted coordinate-wise **median
  direction** of i's peer set (robust reference — a colluding majority
  shifts a mean, not a weighted median until it owns half the trust mass),
* the |log| **norm ratio** against the weighted-median peer norm
  (scaling / boosted-update outliers), and
* the **sign-disagreement rate** vs that median direction (sign-flip and
  label-flip updates push coordinates the wrong way even when their
  magnitude hides in the crowd).

Each signal is scale-invariant; their sum is centered over the peer set so
conforming peers sit at ≲0 and outliers >0, and the fused confidence
update becomes ``c_i ← c_i − m_i ∘ p_i · (loss_trust + λ·geom_trust)``
(``DeFTAConfig.dts_signal = "loss" | "geom" | "both"``, λ =
``dts_geom_weight``; "loss" is bit-identical to the paper's update).

**Collusion-aware correlation trust (DTS v3).** ALIE-style colluders
defeat both signals above BY CONSTRUCTION: they hide inside the honest
variance envelope, so no single-round, single-peer statistic separates
them. But collusion has a cross-round signature no honest cohort shows —
the colluders' updates correlate with *each other*, round after round,
far more than non-iid honest workers do (the sybil/collusion threat model
of the DFL security surveys; DeTrust-FL's argument that decentralized
trust must live at the aggregation layer). ``update_sketch`` keeps a
device-side ring buffer of SIGN-SKETCHES (count-sketch projection →
sign) of the per-peer update deltas over the last R rounds;
``colluder_scores`` computes the pairwise peer×peer correlation matrix
via a sign-matmul over the flattened sketch history, calibrates a
median+MAD baseline of the off-diagonal correlations, and clusters the
high-mutual-correlation group with one power-iteration step on the
excess-correlation graph. The resulting cluster-membership suspicion is
folded into the confidence update as a third channel

    c_i ← c_i − m_i ∘ p_i · (loss_trust + λg·geom + λc·corr)

(``dts_signal = "corr"`` for the correlation channel alone, ``"all"`` for
the full fusion; λc = ``dts_corr_weight``). The sketch hash/sign plan is
drawn with numpy at trace time (``_sketch_plan``) — the sketches consume
ZERO jax PRNG keys, so the frozen key-split layout (and the ``"loss"``
golden) is untouched, and the whole pipeline rides the existing scan
supersteps with zero extra dispatches.

In the unified round-program engine (``core.engine``) these primitives are
the ``peer_sample`` (sample_weights/sample_peers), ``damage_check``
(is_damaged + backup select) and ``trust_update`` (confidence update,
loss / geometric / correlation signal) stages — shared verbatim by the
sync, async and multi-pod selections.
"""
from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np

DAMAGE_PENALTY = 1e3       # finite stand-in for the paper's +inf loss_trust
EXPLOSION_FACTOR = 10.0    # loss > factor * best  => damaged


def crelu(x, slope: float = 0.2):
    """Paper Eq. 13 (piecewise: identity for x<=0, gentle slope above)."""
    return jnp.where(x <= 0, x, slope * x)


def sample_weights(conf, peer_mask, slope: float = 0.2):
    """θ_i = softmax(cRELU(c_i)) over actual peers. conf: [...,W]; mask:
    [...,W] bool. Non-peers get 0. A row with NO peers at all (an isolated
    worker — partitioned away, all neighbors dead, or a cross-device
    cohort where everyone else dropped) returns the all-zero row instead
    of softmax's NaN over all-(−inf) logits: downstream, zero θ means
    ``sample_peers`` selects nobody and the mixing matrix falls back to
    the identity self-loop, so the worker self-trains for the round."""
    z = crelu(conf, slope)
    z = jnp.where(peer_mask, z, -jnp.inf)
    t = jax.nn.softmax(z, axis=-1)
    return jnp.where(peer_mask.any(axis=-1, keepdims=True), t, 0.0)


def topk_mask(score, k: int):
    """Boolean mask of the (≤ k) largest FINITE entries of ``score`` along
    the last axis. Index-based rather than threshold-based: the old
    ``score >= top_k(score)[0][..., -1]`` comparison admits MORE than k
    entries on exact ties, and on degenerate rows (fewer than k finite
    scores) the threshold collapses to −inf, where ``-inf >= -inf`` is
    True and only a caller-side guard kept the mask sane. Scattering the
    top-k indices guarantees ≤ k True entries unconditionally; −inf
    padding slots are dropped via the finiteness gate."""
    vals, idx = jax.lax.top_k(score, k)
    hit = (jnp.arange(score.shape[-1]) == idx[..., None]) \
        & jnp.isfinite(vals)[..., None]
    return hit.any(axis=-2)


def sample_peers(key, theta, num_sampled: int):
    """Gumbel top-k sample without replacement by weights θ. theta: [W];
    returns boolean mask [W] with ≤ num_sampled True entries (fewer only if
    the peer set itself is smaller — isolated workers and all-dead
    neighborhoods yield the empty mask, never a full row)."""
    g = jax.random.gumbel(key, theta.shape)
    score = jnp.where(theta > 0, jnp.log(theta + 1e-20) + g, -jnp.inf)
    k = min(num_sampled, theta.shape[-1])
    return topk_mask(score, k) & (theta > 0)


def is_damaged(loss, best_loss):
    return ~jnp.isfinite(loss) | (loss > EXPLOSION_FACTOR *
                                  jnp.maximum(best_loss, 1e-8) + 10.0)


def update_confidence(conf, sampled_mask, agg_weights, loss_trust):
    """Algorithm 3 line 12: c ← c − m ∘ p · loss_trust."""
    return conf - sampled_mask * agg_weights * loss_trust


def dts_step(state, loss, sampled_mask, agg_weights, slope: float = 0.2):
    """One φ(·) evaluation for a single worker.

    state: dict(conf [W], best_loss [], last_loss [])
    Returns (new_state, theta [W], damaged bool, loss_trust).
    """
    damaged = is_damaged(loss, state["best_loss"])
    loss_trust = jnp.where(damaged, DAMAGE_PENALTY, loss - state["last_loss"])
    conf = update_confidence(state["conf"], sampled_mask, agg_weights,
                             loss_trust)
    new_state = {
        "conf": conf,
        "best_loss": jnp.where(damaged, state["best_loss"],
                               jnp.minimum(state["best_loss"], loss)),
        "last_loss": jnp.where(damaged, state["last_loss"], loss),
    }
    return new_state, damaged, loss_trust


def init_dts_state(num_workers: int):
    return {
        "conf": jnp.zeros((num_workers,)),
        "best_loss": jnp.asarray(jnp.inf),
        "last_loss": jnp.asarray(0.0),
    }


# ---------------------------------------------------------------------------
# Geometric trust signals (DTS v2)
# ---------------------------------------------------------------------------

GEOM_NORM_CLIP = 4.0       # |log norm-ratio| saturation (e^4 ≈ 55x outlier)


def flatten_stacked(stacked):
    """Flatten a stacked [W, ...] pytree to one [W, D] fp32 matrix (the
    per-worker model vectors the geometric signals score)."""
    leaves = jax.tree.leaves(stacked)
    return jnp.concatenate(
        [x.reshape(x.shape[0], -1).astype(jnp.float32) for x in leaves],
        axis=1)


def weighted_median(vals, wts):
    """Per-receiver coordinate-wise weighted median of a SHARED stack.

    vals: [P, D] — one stack of peer values, shared by every receiver;
    wts: [R, P] per-receiver weights (>= 0, zero = excluded). Returns
    [R, D]: per (receiver, coordinate) the smallest value whose
    cumulative weight reaches half the receiver's total.

    Because the stack is shared, the per-coordinate sort order does not
    depend on the receiver — only the weights do — so the values are
    sorted ONCE and each receiver contributes just a weight gather +
    cumsum (this is what keeps the geometric trust_update inside the
    superstep overhead gate). Zero-weight entries can never be the
    crossing index (the cumsum does not move on them), so no value
    masking is needed; an all-zero weight row returns 0.
    """
    order = jnp.argsort(vals, axis=0)                  # one shared sort
    sv = jnp.take_along_axis(vals, order, axis=0)      # [P, D]
    sw = jnp.take(wts, order, axis=1)                  # [R, P, D]
    cw = jnp.cumsum(sw, axis=1)
    total = wts.sum(axis=1)
    pick = jnp.argmax(cw >= total[:, None, None] * 0.5, axis=1)  # [R, D]
    med = jnp.take_along_axis(
        jnp.broadcast_to(sv[None], (wts.shape[0],) + sv.shape),
        pick[:, None, :], axis=1)[:, 0, :]
    return jnp.where(total[:, None] > 0, med, 0.0)


def geom_scores(deltas, mask, weights=None, *,
                norm_clip: float = GEOM_NORM_CLIP, eps: float = 1e-12):
    """Update-geometry suspicion scores per (receiver i, peer j).

    deltas: [W, D] per-peer UPDATE deltas (``flatten_stacked`` of two
    stacks the round already materializes — zero extra dispatches). The
    simulation engines pass each worker's local-update delta
    ``trained − start`` (the step it applied on top of its adopted
    aggregate — what an update-shipping wire format exposes directly,
    post attack injection so the poison is exactly what gets scored);
    the pod round passes the round displacement ``out − params``. The
    TRAINING component is where label-flip/sign-flip poisoning lives
    (ascent instead of descent on the shared structure) — raw model
    DIFFERENCES ``x_j − x_i`` hide it under non-iid spread (attackers
    cluster, honest workers scatter; see the ROADMAP DTS v2 findings).

    mask: [W, W] bool, i listens to j (the sampled ∧ live set; the
    diagonal is ignored for scoring); weights: [W, W] trust weights for
    the reference statistics (θ from ``sample_weights``; defaults to
    uniform over the mask).

    The reference direction r_i is the trust-weighted coordinate-wise
    median over i's peer set ∪ SELF, with the receiver's own displacement
    carrying half the total mass: the receiver's own data is clean by
    definition, so the median is anchored on it (FLTrust-style trust
    root) and a colluding majority cannot capture the reference — the
    failure mode of purely peer-relative geometry at ≥50% malicious.
    (At exactly half the mass the lower weighted median collapses to the
    closed form ``min(self, max over positive-weight peers)`` per
    coordinate — computed that way below, so the direction reference
    depends on ``weights`` only through their support; the weights still
    shape the norm median and the centering.)

    Each peer is scored by three scale-invariant signals — cosine
    distance to r_i, clipped |log| norm ratio vs the (self-anchored)
    weighted-median displacement norm, and sign-disagreement rate vs r_i —
    summed and centered over the receiver's peer set. Returns [W, W]:
    ~0-sum per row under ``weights``; conforming peers ≲ 0, geometric
    outliers > 0. Rows with no peers are all-zero. Permutation-
    equivariant in the worker axis and invariant to a global positive
    rescaling of ``deltas``.
    """
    w = deltas.shape[0]
    eye = jnp.eye(w, dtype=bool)
    mask = mask & ~eye
    wts = jnp.where(mask, weights if weights is not None else 1.0, 0.0)
    wts = jnp.maximum(wts, 0.0)
    # self-anchor: the receiver's own displacement joins the reference
    # statistics with weight == the whole peer mass (half the total)
    wts_ref = wts + eye * wts.sum(1, keepdims=True)

    # The (lower) weighted median with the self anchor at exactly half
    # the mass has a closed form: the cumulative weight can only reach
    # half BEFORE self if the ENTIRE peer mass lies below self's value,
    # in which case the median is the largest peer value — otherwise it
    # is self. Per coordinate: ref = min(self, max over positive-weight
    # peers). Same result as weighted_median(deltas, wts_ref), without
    # the [R, P, D] sort/gather/cumsum — what keeps this stage inside
    # the superstep overhead gate.
    peer_max = jnp.max(
        jnp.where(wts[:, :, None] > 0, deltas[None, :, :], -jnp.inf),
        axis=1)                                        # [R, D]
    ref = jnp.minimum(deltas, peer_max)    # row r's self IS deltas[r]
    ref = jnp.where(jnp.isfinite(ref), ref, 0.0)       # no-peer rows
    dn = jnp.sqrt((deltas * deltas).sum(-1))           # [P]
    rn = jnp.sqrt((ref * ref).sum(-1))                 # [R]

    cos = (ref @ deltas.T) / (dn[None, :] * rn[:, None] + eps)
    cos_score = 1.0 - cos                              # [0, 2]

    med_n = weighted_median(dn[:, None], wts_ref)[:, 0]  # [R]
    norm_score = jnp.abs(jnp.log((dn[None, :] + eps)
                                 / (med_n[:, None] + eps)))
    norm_score = jnp.clip(norm_score, 0.0, norm_clip) / norm_clip

    # sign-agreement via a sign matmul: S_ref @ S.T counts same-sign
    # minus differing-sign coordinates (exact zeros count as half-agree)
    agree = 0.5 * (1.0 + (jnp.sign(ref) @ jnp.sign(deltas).T)
                   / deltas.shape[1])
    sign_score = 1.0 - agree                           # [0, 1]

    score = cos_score + norm_score + sign_score
    tot = wts.sum(1, keepdims=True)
    mean_s = (wts * score).sum(1, keepdims=True) / jnp.maximum(tot, eps)
    return jnp.where(mask, score - mean_s, 0.0)


# ---------------------------------------------------------------------------
# Cross-round correlation trust (DTS v3)
# ---------------------------------------------------------------------------

SKETCH_ROUNDS = 8          # default ring-buffer depth R (rounds of history)
SKETCH_DIM = 64            # default count-sketch width S per round


@lru_cache(maxsize=32)
def _sketch_plan(seed: int, dim: int, sketch_dim: int):
    """Count-sketch hash plan: bucket assignment h [D] and Rademacher
    signs s [D], drawn with NUMPY at trace time and embedded as
    constants — the sketches consume zero jax PRNG keys, keeping the
    engines' frozen key-split layout (and the "loss" golden) untouched.
    Cached per (seed, D, S): every engine tracing the same config shares
    one plan, so sim and pod sketches of the same delta agree."""
    rng = np.random.default_rng(seed * 1_000_003 + 0xC0DE)
    bucket = rng.integers(0, sketch_dim, size=dim)
    sign = rng.integers(0, 2, size=dim) * 2 - 1
    return (np.asarray(bucket, np.int32), np.asarray(sign, np.float32))


def sketch_deltas(deltas, sketch_dim: int, *, seed: int = 0):
    """Sign-sketch of per-worker update deltas: count-sketch projection
    [W, D] → [W, S] (signed bucket sums — an AMS/count-sketch linear map,
    so inner products of sketches estimate inner products of deltas) then
    ``sign`` — the {−1, 0, +1} codes whose cross-round sign-matmul is the
    correlation estimator in ``colluder_scores``. D is static at trace
    time, so the hash plan is a host-side constant."""
    bucket, sign = _sketch_plan(seed, deltas.shape[1], sketch_dim)
    proj = jax.ops.segment_sum(
        (deltas * jnp.asarray(sign)).T, jnp.asarray(bucket),
        num_segments=sketch_dim)                        # [S, W]
    return jnp.sign(proj.T)                             # [W, S]


def update_sketch(hist, deltas, *, seed: int = 0):
    """Rotate the sketch ring buffer: drop the oldest round, append this
    round's sign-sketch. hist: [W, R, S]; deltas: [W, D]. Shift-based
    (no pointer) so per-worker freeze/fire merging is a plain
    ``where`` over rows — a frozen worker's whole history stays put."""
    new = sketch_deltas(deltas, hist.shape[2], seed=seed)
    return jnp.concatenate([hist[:, 1:, :], new[:, None, :]], axis=1)


def correlation_matrix(hist, *, eps: float = 1e-12):
    """Pairwise peer×peer cross-round correlation: cosine similarity of
    the flattened [W, R·S] sign-sketch histories via one sign-matmul.
    Zero rows (unfilled history) correlate 0 with everything; the
    diagonal is zeroed (self-correlation is not evidence)."""
    w = hist.shape[0]
    flat = hist.reshape(w, -1)
    n = jnp.sqrt((flat * flat).sum(-1))
    corr = (flat @ flat.T) / (n[:, None] * n[None, :] + eps)
    return jnp.where(jnp.eye(w, dtype=bool), 0.0, corr)


def colluder_scores(hist, mask, weights=None, *, eps: float = 1e-12):
    """Cluster-membership suspicion per (receiver i, peer j) from the
    cross-round correlation structure of the sketch history.

    hist: [W, R, S] sign-sketch ring buffer (``update_sketch``); mask /
    weights as in ``geom_scores``. Colluders (ALIE et al.) must emit
    near-identical payloads to coordinate their shift, so their pairwise
    correlation sits far above the honest baseline — which non-iid
    heterogeneity keeps LOW (honest workers' local steps scatter).

    Calibration is self-normalizing, not max-normalized: the baseline is
    the median off-diagonal correlation and the spread its MAD, so in a
    clean run (no cluster) the excess graph is ~empty and the scores ~0 —
    clean-run accuracy is unharmed by construction. The high-mutual-
    correlation CLUSTER is extracted with one power-iteration step on the
    excess graph (v = row-mean, s = E·v): a peer scores high only when
    its excess correlations point at peers that themselves have excess
    correlations — one stray correlated pair does not an attacker make.

    Returns [W, W]: the per-peer suspicion s_j centered over each
    receiver's peer set under ``weights`` (same contract as
    ``geom_scores`` — conforming peers ≲ 0, cluster members > 0, rows
    with no peers all-zero)."""
    corr = correlation_matrix(hist, eps=eps)
    return correlation_suspicion(corr, mask, weights=weights, eps=eps)


def correlation_suspicion(corr, mask, weights=None, *, valid=None,
                          eps: float = 1e-12):
    """The median+MAD calibration + power-iteration clustering tail of
    ``colluder_scores``, factored out so the dense path (``corr`` from
    ``correlation_matrix``) and the cross-device sparse path (``corr``
    from ``stamped_correlation``) share one scoring rule.

    ``valid`` (optional [W, W] bool) marks correlation entries backed by
    enough common observations to be evidence: invalid entries contribute
    NEITHER to the median/MAD baseline NOR to the excess graph — under
    sparse cross-device sampling a pair never co-observed reads as "no
    evidence", not "zero correlation" (a zero would sit below a negative
    baseline and manufacture phantom excess). When every entry is invalid
    (early rounds) the baseline falls back to 0 and all scores are 0.
    ``valid=None`` is the dense path and traces the exact pre-refactor
    op sequence — the committed corr_trust bench numbers are unchanged.
    """
    w = corr.shape[0]
    eye = jnp.eye(w, dtype=bool)
    offd = jnp.where(eye, jnp.nan, corr)
    if valid is not None:
        offd = jnp.where(valid, offd, jnp.nan)
    base = jnp.nanmedian(offd)
    spread = jnp.nanmedian(jnp.abs(offd - base))
    if valid is not None:
        base = jnp.where(jnp.isnan(base), 0.0, base)
        spread = jnp.where(jnp.isnan(spread), 0.0, spread)
    excess = jnp.where(eye, 0.0, jax.nn.relu(corr - base - spread))
    if valid is not None:
        excess = jnp.where(valid & ~eye, excess, 0.0)
    v = excess.mean(axis=1)                             # [W] first pass
    s = excess @ v                                      # [W] cluster mass

    mask = mask & ~eye
    wts = jnp.where(mask, weights if weights is not None else 1.0, 0.0)
    wts = jnp.maximum(wts, 0.0)
    tot = wts.sum(1, keepdims=True)
    score = jnp.broadcast_to(s[None, :], (w, w))
    mean_s = (wts * score).sum(1, keepdims=True) / jnp.maximum(tot, eps)
    return jnp.where(mask, score - mean_s, 0.0)


def stamped_correlation(hist, stamps, *, min_obs: int = 2,
                        eps: float = 1e-12):
    """Observation-aligned cross-round correlation for SPARSELY observed
    peers (the cross-device path).

    Under partial participation each worker's ring buffer rotates only on
    the rounds IT fired, so slot r of worker i and slot r of worker j
    generally hold sketches from DIFFERENT global rounds — the dense
    flattened-cosine of ``correlation_matrix`` would compare unrelated
    rounds and wash out exactly the colluder signature it exists to find.
    Each slot therefore carries a global-round STAMP (−1 = never filled),
    and the correlation is the mean per-slot-pair cosine over stamp-
    MATCHED pairs only: rounds both peers actually participated in.

    hist: [W, R, S] sign-sketch ring buffer; stamps: [W, R] int32.
    Returns ``(corr [W, W], valid [W, W])`` where ``valid[i, j]`` is True
    iff i and j share ≥ ``min_obs`` stamped common rounds — below that,
    a high correlation is sampling noise, not collusion evidence (one
    common round ALWAYS correlates alie colluders at 1.0, but so does one
    lucky honest pair; the gate is the per-peer observation count the
    sparse threat model requires). Pairs never co-observed get corr 0 and
    valid False; feed both into ``correlation_suspicion``.
    """
    filled = stamps >= 0                                # [W, R]
    match = (stamps[:, None, :, None] == stamps[None, :, None, :]) \
        & filled[:, None, :, None] & filled[None, :, None, :]  # [W,W,R,R]
    # per-slot-pair cosine of sign-sketches
    dots = jnp.einsum("irs,jps->ijrp", hist, hist)      # [W, W, R, R]
    n = jnp.sqrt((hist * hist).sum(-1))                 # [W, R] slot norms
    denom = n[:, None, :, None] * n[None, :, None, :] + eps
    cos = dots / denom
    m = match.astype(hist.dtype)
    nmatch = m.sum((2, 3))                              # [W, W]
    corr = (m * cos).sum((2, 3)) / jnp.maximum(nmatch, 1.0)
    valid = nmatch >= min_obs
    w = hist.shape[0]
    eye = jnp.eye(w, dtype=bool)
    return jnp.where(eye, 0.0, corr), valid & ~eye


def fused_trust_signal(dts_signal: str, loss_trust, geom, damaged,
                       lam: float, corr=None, lam_corr: float = 0.0):
    """The trust_update stage's fused per-(receiver, peer) signal.

    ``loss_trust``: [W] (already carries DAMAGE_PENALTY on damaged rows);
    ``geom``: [W, W] from ``geom_scores`` (or None); ``damaged``: [W] bool;
    ``corr``: [W, W] from ``colluder_scores`` (or None).
    Returns [W, W]. ``"loss"`` reproduces Algorithm 3 line 12 bit-exactly
    (a pure broadcast, no geometry ops traced); ``"geom"`` / ``"corr"``
    keep only the damage penalty from the loss channel plus their own
    score; ``"both"`` fuses loss + geometry; ``"all"`` fuses all three:
    loss_trust + λg·geom + λc·corr.
    """
    if dts_signal == "loss":
        return loss_trust[:, None]
    if dts_signal == "geom":
        damage_only = jnp.where(damaged, DAMAGE_PENALTY, 0.0)
        return damage_only[:, None] + lam * geom
    if dts_signal == "both":
        return loss_trust[:, None] + lam * geom
    if dts_signal == "corr":
        damage_only = jnp.where(damaged, DAMAGE_PENALTY, 0.0)
        return damage_only[:, None] + lam_corr * corr
    if dts_signal == "all":
        return loss_trust[:, None] + lam * geom + lam_corr * corr
    raise ValueError(f"unknown dts_signal {dts_signal!r} "
                     f"(one of: loss, geom, both, corr, all)")


def geom_confidence_update(dts_signal: str, lam: float, conf, sampled, P,
                           loss_trust, damaged, deltas, mask, weights,
                           sketch=None, lam_corr: float = 0.0, corr=None):
    """The confidence update of every round (dense, cross-device and pod):
    score the deltas (geometry) and/or the sketch history (cross-round
    correlation) over the live ``mask``, fuse with the loss channel per
    ``dts_signal``, and apply Algorithm 3's masked update
    ``c ← c − m ∘ p · signal``. ``"loss"`` scores nothing and is line 12
    bit-exactly. ``sketch`` is the ALREADY-ROTATED [W, R, S] ring buffer
    (this round's sketch included) that ``colluder_scores`` reads for the
    "corr"/"all" variants, unless the caller passes the [W, W]
    correlation scores it computed itself as ``corr`` (the cross-device
    round's stamp-aligned ``correlation_suspicion``)."""
    gs = (geom_scores(deltas, mask, weights=weights)
          if dts_signal in ("geom", "both", "all") else None)
    if corr is None and dts_signal in ("corr", "all"):
        corr = colluder_scores(sketch, mask, weights=weights)
    signal = fused_trust_signal(dts_signal, loss_trust, gs, damaged, lam,
                                corr=corr, lam_corr=lam_corr)
    return conf - sampled * P * signal


def masked_geom_trust(deltas, P, mask=None, *, eps: float = 1e-12):
    """The aggregate-only trust signal under ``secagg_mode="masked_geom"``.

    Secure aggregation in its strong (sender-side group-sum) form hides
    every individual update: the receiver only ever observes its own
    UNMASKED AGGREGATE. The one geometric observable it can still derive
    is the aggregate minus its own contribution, renormalized —
    ``pooled_i = Σ_{j≠i} P_ij δ_j / Σ_{j≠i} P_ij`` — against its own
    local-update direction. Returns the per-RECEIVER signal [W]:
    ``−cos(pooled_i, δ_i)`` — negative (trust-raising) when the pooled
    neighborhood moves with the receiver, positive when it moves against
    it. The receiver cannot attribute the pool to a specific peer, so
    the engine broadcasts this uniformly over its sampled row (the
    confidence row rises/falls together) — which is exactly the fidelity
    DTS loses under aggregate-only secagg, and what the bench's
    masked_geom attacked-accuracy rows quantify.

    ``mask``: [W, W] bool live-peer gate (non-firing peers' deltas were
    never in the aggregate). Rows with no off-diagonal mass return 0.
    """
    w = P.shape[0]
    off = P.astype(jnp.float32) * (1.0 - jnp.eye(w, dtype=jnp.float32))
    if mask is not None:
        off = off * mask.astype(jnp.float32)
    tot = off.sum(axis=1, keepdims=True)
    pooled = (off / jnp.maximum(tot, eps)) @ deltas          # [W, D]
    num = (pooled * deltas).sum(axis=1)
    den = jnp.linalg.norm(pooled, axis=1) \
        * jnp.linalg.norm(deltas, axis=1) + eps
    return jnp.where(tot[:, 0] > 0, -num / den, 0.0)
