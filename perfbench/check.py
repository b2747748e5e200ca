"""The comparison that decides ``correct``: numbers read from what the
timed path produced, each against the plain reference's, each with its
limit. A check is ``(name, value, limit)``; the run is correct when every
value is at or under its limit."""
from __future__ import annotations

import numpy as np


def _norm(a) -> float:
    return float(np.linalg.norm(np.asarray(a, np.float64).ravel()))


def update_gap(got0: dict, got: dict, want0: dict, want: dict,
               rows=None) -> float:
    """Worst leaf of | ‖Δ_program‖ − ‖Δ_reference‖ |, against the larger of
    that leaf's ‖Δ_reference‖ and the median leaf's. Δ is the parameters'
    change from each side's own start. Leaves whose reference change is
    under a thousandth of the median leaf's are nought to rounding and
    left out. ``rows`` selects the leading-axis rows compared."""
    sel = (lambda a: np.asarray(a)[rows]) if rows is not None \
        else np.asarray
    names = sorted(want)
    d_ref = {k: _norm(sel(want[k]) - sel(want0[k])) for k in names}
    d_got = {k: _norm(sel(got[k]) - sel(got0[k])) for k in names}
    med = float(np.median(list(d_ref.values())))
    gaps = [abs(d_got[k] - d_ref[k]) / max(d_ref[k], med, 1e-30)
            for k in names if d_ref[k] >= 1e-3 * med]
    return max(gaps)


def relative_gap(got, want) -> float:
    """max |got − want| / max |want| (finite entries of ``want``; a
    non-finite entry of ``got`` where ``want`` is finite reads inf)."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    fin = np.isfinite(want)
    if not np.isfinite(got[fin]).all():
        return float("inf")
    scale = np.max(np.abs(want[fin])) if fin.any() else 1.0
    return float(np.max(np.abs(got[fin] - want[fin])) / max(scale, 1e-30))


def trust_gap(got, want, loss_scale: float) -> float:
    """max |got − want| over the trust entries, in units of
    ``loss_scale``: the trust moves by loss deltas, or by the damage
    penalty (1e3) where a sampled peer wrecked the aggregate, so the
    largest entry is no steady unit (it is a penalty on some seeds, a loss
    delta on others); the largest loss is."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if not np.isfinite(got).all():
        return float("inf")
    return float(np.max(np.abs(got - want)) / max(loss_scale, 1e-30))


def engine_checks(got0: dict, got: dict, want: dict, malicious,
                  limits: dict):
    """DeFTA engine after the first chunk of rounds: the vanilla workers'
    losses (this round's and their best), their parameters' change, and
    their trust rows."""
    v = ~np.asarray(malicious, bool)
    loss = max(relative_gap(got["last_loss"][v], want["last_loss"][v]),
               relative_gap(got["best_loss"][v], want["best_loss"][v]))
    upd = update_gap(got0, got["params"], want["params0"], want["params"],
                     rows=v)
    trust = trust_gap(got["conf"][v], want["conf"][v],
                      float(np.max(np.abs(want["last_loss"][v]))))
    return [("loss_gap", loss, limits["loss_gap"]),
            ("update_gap", upd, limits["update_gap"]),
            ("trust_gap", trust, limits["trust_gap"])]


def leaf_gaps(got, want):
    """| got − want | per leaf and pod against the larger of that leaf's
    ``want`` and the pod's median leaf's (``got``, ``want``: per-leaf
    norms, [leaves, pods]); inf throughout if ``got`` is not finite."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if not np.isfinite(got).all():
        return np.full(want.shape, np.inf)
    med = np.median(want, axis=0, keepdims=True)
    return np.abs(got - want) / np.maximum(np.maximum(want, med), 1e-30)


def pod_checks(got: dict, want: dict, limits: dict):
    """Cross-silo trainer after its first gossip cycle: every pod's first
    gradient's norm per leaf (read from the optimizer's state after that
    step; the worst leaf), and each leaf's change from the initial weights
    after the cycle's gossip round (the median leaf, worst pod: the worst
    leaf swings from seed to seed, among the norm scales and the attention
    projections most). Leaves whose reference gradient is under a
    thousandth of the median leaf's are left out. The losses are not
    compared: neither the control nor a fault separates from the program's
    own spread on them."""
    g_ref = np.asarray(want["grad"])
    moved = g_ref >= 1e-3 * np.median(g_ref, axis=0, keepdims=True)
    grad = float(leaf_gaps(np.stack(got["grad"]), g_ref)[moved].max())
    change = leaf_gaps(np.stack(got["change"]), want["change"])
    upd = float(max(np.median(change[moved[:, p], p])
                    for p in range(change.shape[1])))
    return [("grad_gap", grad, limits["grad_gap"]),
            ("update_gap", upd, limits["update_gap"])]
