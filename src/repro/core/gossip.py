"""The gossip aggregation op: ``out = P @ stacked_params`` applied leaf-wise.

Backends:
* ``einsum`` — jnp reference (always available, differentiable).
* ``pallas`` — the dense TPU ``gossip_mix`` kernel (repro.kernels), tiled
  over the flattened parameter axis; the oracle the sparse kernel is
  validated against.
* ``sparse`` — the padded-CSR ``gossip_mix_sparse`` kernel: HBM+compute
  scale O(nnz·F) instead of O(W²·F). Requires the static ``adjacency``
  support (the topology); the traced P supplies the per-round weights.
* ``auto``  — picks ``sparse`` when an adjacency is given and its density
  (self-loops included) is below ``SPARSE_DENSITY_THRESHOLD``, else the
  dense pallas kernel. DeFTA topologies (avg_peers ≪ W) land on sparse.

Wire format + error feedback contract
-------------------------------------
In DeFTA every worker serializes and ships its model to its outbound peers
each round, so WIRE BYTES dominate the decentralized hot path at scale.
``wire`` selects what actually crosses the wire:

* ``None``   — fp32 payload (4 B/param, lossless).
* ``"bf16"`` — bf16 cast (2 B/param); kernels accumulate in fp32.
* ``"int8"`` — per-row symmetric quantization (1 B/param + one fp32 scale
  per worker row): ``scale_i = max|row_i| / 127``, ``q_i = round(row_i /
  scale_i)``. The ``sparse`` backend mixes the int8 payload directly with
  the fused ``gossip_mix_quant`` kernel (dequant folded into the CSR
  weights — no materialized fp32 stack); ``einsum``/``pallas`` fold the
  scales into P's columns (``P·diag(scale)``) so they never materialize a
  dequantized stack either.

Lossy wires compose with EF21-style error feedback: pass ``residual`` (a
pytree like ``stacked``, zeros at round 0) and the mix returns
``(mixed, new_residual)`` where each worker encoded ``row + residual`` and
``new_residual = (row + residual) - dequant(payload)`` — the quantization
error is compensated NEXT round instead of compounding, which keeps
decentralized averaging convergent under lossy exchange (DeceFL). Without
``residual`` the cast is fire-and-forget (simulation-only, PR 1 behavior).

Backend auto-selection: ``auto`` + sparse topology → fused quant kernel on
the int8 wire; ``auto`` + dense/absent adjacency → dense kernel with the
scales folded into P. Byte-savings scope: the in-jit backends reproduce
the wire's NUMERICS (encode→mix fuses into one XLA program, so any GSPMD
collectives they emit still move fp32); the realized cross-pod byte cut
is the ``mix_pytree_ppermute`` path, which permutes the int8 payload +
per-row scale instead of fp32 leaves — ~4× fewer bytes on the same ring
schedule. Wire bytes per payload are accounted by ``WIRE_BYTES`` /
``launch.roofline.gossip_wire_bytes``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


SPARSE_DENSITY_THRESHOLD = 0.25

# Every gossip contraction runs at full f32 precision. TPU's default
# rounds f32 matmul operands to bf16, which perturbs each row-stochastic
# mixing row by ~2^-9 and lets the transports (kernels, einsum, ring
# matmuls) drift apart; the CPU computes f32 either way.
MIX_PRECISION = jax.lax.Precision.HIGHEST

# How far a worker-sharded run (``run_defta(shards=N)``) may drift from the
# one-device run of the same seed, per state leaf, by wire. The layouts
# differ only in f32 summation order; the int8 wire can turn such an ulp
# into one quantization step on a few rows.
SHARD_PARITY_TOL = {None: 5e-4, "int8": 5e-3}

# bytes per parameter on the wire, by format (int8 adds 4 B/row of scales,
# accounted in launch.roofline.gossip_wire_bytes)
WIRE_BYTES = {None: 4, "fp32": 4, "bf16": 2, "int8": 1}

_WIRE_ALIASES = {
    None: None, "fp32": None, "float32": None,
    "bf16": "bf16", "bfloat16": "bf16",
    "int8": "int8",
}


def normalize_wire(wire):
    """Canonicalize a wire-format name to None | "bf16" | "int8"."""
    key = wire
    if not isinstance(key, str) and key is not None:
        key = jnp.dtype(key).name                 # accept dtype-likes
    if key not in _WIRE_ALIASES:
        raise ValueError(f"unknown gossip wire format {wire!r} "
                         f"(expected one of {sorted(_WIRE_ALIASES, key=str)})")
    return _WIRE_ALIASES[key]


def uses_error_feedback(cfg) -> bool:
    """Single place the engines decide whether a DeFTAConfig runs EF21
    error feedback: a lossy wire format with feedback enabled."""
    return bool(cfg.gossip_error_feedback) \
        and normalize_wire(cfg.gossip_dtype) is not None


def quantize_rows_int8(flat, *, rounding: str = "nearest", key=None):
    """Per-row symmetric int8 quantization of a [W, F] stack.
    Returns (q [W, F] int8, scale [W] f32) with q = round(flat / scale)
    clipped to ±127 and scale = max|row| / 127 (never zero).

    ``rounding="stochastic"`` rounds ``x`` up with probability equal to its
    fractional part (needs ``key``): E[dequant(q)] == x exactly, so the
    per-round quantization is UNBIASED — noise instead of bias, which
    composes with (or substitutes for) the EF21 residual for workers that
    drop out mid-stream and never get to replay their residual. On TPU the
    same draw maps to ``pltpu.prng_random_bits`` inside the encode; the
    encode is row-local jnp here (it runs outside the mix kernels), so the
    lowering is already fused into the superstep either way."""
    flat = flat.astype(jnp.float32)
    amax = jnp.max(jnp.abs(flat), axis=1)
    scale = jnp.maximum(amax, 1e-12) / 127.0
    scaled = flat / scale[:, None]
    if rounding == "stochastic":
        if key is None:
            raise ValueError("stochastic rounding needs a PRNG key")
        lo = jnp.floor(scaled)
        u = jax.random.uniform(key, scaled.shape, jnp.float32)
        q = lo + (u < (scaled - lo)).astype(jnp.float32)
    elif rounding == "nearest":
        q = jnp.round(scaled)
    else:
        raise ValueError(f"unknown wire rounding {rounding!r} "
                         f"(expected 'nearest' | 'stochastic')")
    q = jnp.clip(q, -127, 127)
    return q.astype(jnp.int8), scale


def dequantize_rows_int8(q, scale):
    """Inverse of ``quantize_rows_int8`` (fp32)."""
    return q.astype(jnp.float32) * scale.reshape(-1, 1)


# sparse_support is memoized on the adjacency bytes: the O(W²) Python loop
# otherwise re-runs on every mix_pytree trace (per leaf, per jit). Bounded
# LRU — a long-lived topology sweep must not grow it without limit.
_SUPPORT_CACHE: dict = {}
_SUPPORT_CACHE_MAX = 64
SUPPORT_CACHE_STATS = {"hits": 0, "misses": 0}


def sparse_support(adjacency) -> tuple[np.ndarray, np.ndarray]:
    """Padded-CSR support of a topology: ``adjacency[i, j]`` = i receives
    from j. Self-loops are always added (worker i keeps its own model).
    Returns (idx [W, K] int32, valid [W, K] bool) with K = max row degree;
    padding slots repeat the row's own index and are masked by ``valid``.
    Memoized on the adjacency bytes — callers must not mutate the result."""
    a0 = np.asarray(adjacency, bool)
    key = (a0.shape, a0.tobytes())
    cached = _SUPPORT_CACHE.get(key)
    if cached is not None:
        SUPPORT_CACHE_STATS["hits"] += 1
        _SUPPORT_CACHE[key] = _SUPPORT_CACHE.pop(key)   # LRU refresh
        return cached
    SUPPORT_CACHE_STATS["misses"] += 1
    while len(_SUPPORT_CACHE) >= _SUPPORT_CACHE_MAX:
        _SUPPORT_CACHE.pop(next(iter(_SUPPORT_CACHE)))
    a = a0 | np.eye(a0.shape[0], dtype=bool)
    w = a.shape[0]
    k = int(a.sum(axis=1).max())
    idx = np.tile(np.arange(w, dtype=np.int32)[:, None], (1, k))
    valid = np.zeros((w, k), bool)
    for i in range(w):
        peers = np.flatnonzero(a[i]).astype(np.int32)
        idx[i, :peers.size] = peers
        valid[i, :peers.size] = True
    idx.setflags(write=False)
    valid.setflags(write=False)
    _SUPPORT_CACHE[key] = (idx, valid)
    return idx, valid


def sparse_weights(P, adjacency):
    """Padded-CSR form of a (possibly traced) mixing matrix P over a static
    topology: returns (idx [W, K] int32 jnp, val [W, K] f32 jnp) with
    padding slots zero-weighted. The single place the padding convention
    lives — kernels, benchmarks, and tests all go through it."""
    idx, valid = sparse_support(adjacency)
    idx_j = jnp.asarray(idx)
    val = jnp.take_along_axis(P.astype(jnp.float32), idx_j, axis=1)
    return idx_j, val * jnp.asarray(valid, jnp.float32)


def dynamic_mixing_matrix(sampled, eff_adj, sizes, scheme: str = "defta"):
    """Per-epoch mixing matrix under a DYNAMIC (traced) adjacency.

    The scenario engine changes who is reachable every epoch (churn, link
    failures, partitions), so the aggregation weights cannot be baked at
    build time: outdegrees — the |D_j|/d_j correction of Theorem 3.3 —
    must be recomputed from the epoch's effective adjacency, otherwise a
    worker whose receivers died keeps its stale (under-)weighting.

    sampled:  [W, W] bool, this round's sampled peers.
    eff_adj:  [W, W] bool, the epoch's effective topology (static adj ∧
              link_ok ∧ alive-row ∧ alive-col). May be traced.
    sizes:    [W] f32 dataset sizes.
    Returns row-stochastic P [W, W]; every row keeps its self-loop, so an
    isolated (or dead) worker degrades to the identity row — its params
    pass through the mix unchanged.

    P's support is ⊆ static adjacency ∪ self-loops by construction, so the
    sparse backend reuses the STATIC padded-CSR support (masked entries
    are zero-weighted slots) and the ``sparse_support`` memo is untouched
    by per-epoch masks.
    """
    w = eff_adj.shape[0]
    eye = jnp.eye(w, dtype=bool)
    outdeg = (eff_adj | eye).sum(axis=0).astype(jnp.float32)
    sizes = jnp.asarray(sizes, jnp.float32)
    if scheme == "defta":
        col_w = sizes / outdeg
    elif scheme == "defl":
        col_w = sizes
    else:                                   # uniform gossip
        col_w = jnp.ones_like(sizes)
    mask = (sampled & eff_adj) | eye
    P = mask * col_w[None, :]
    return P / jnp.maximum(P.sum(axis=1, keepdims=True), 1e-12)


def _resolve_backend(backend, adjacency, w):
    if backend != "auto":
        return backend
    if adjacency is None:
        return "pallas"
    a = np.asarray(adjacency, bool) | np.eye(w, dtype=bool)
    return "sparse" if a.mean() <= SPARSE_DENSITY_THRESHOLD else "pallas"


def _encode_rows(flat, r_flat, wire, *, rounding: str = "nearest",
                 key=None):
    """Encode one worker-stacked [W, F] leaf for the wire. Returns
    (payload, scale_or_None, new_residual_or_None): with ``r_flat`` (EF21)
    the encoded row is ``flat + r_flat`` and the residual is what the
    decode loses; without it the cast is fire-and-forget."""
    send = flat.astype(jnp.float32)
    if r_flat is not None:
        send = send + r_flat.astype(jnp.float32)
    if wire == "bf16":
        payload, scale = send.astype(jnp.bfloat16), None
        deq = payload.astype(jnp.float32)
    else:                                         # int8
        payload, scale = quantize_rows_int8(send, rounding=rounding,
                                            key=key)
        deq = dequantize_rows_int8(payload, scale)
    new_r = (send - deq) if r_flat is not None else None
    return payload, scale, new_r


def mix_pytree(P, stacked, backend: str = "einsum", *, adjacency=None,
               wire=None, wire_dtype=None, residual=None,
               wire_round: str = "nearest", wire_key=None,
               secagg=None, secagg_round=None):
    """P: [W, W] row-stochastic; stacked: pytree with leading axis W.

    ``adjacency``: static bool [W, W] support of P (required for the
    ``sparse`` backend, enables it under ``auto``). P's nonzeros must lie
    within adjacency ∪ self-loops — DeFTA's sampled mixing matrices do by
    construction (sampled ⊆ topology edges). A per-epoch dynamic mask
    (churn, link failures) rides in P's VALUES: masked entries are zero,
    which the padded-CSR backends express as zero-weighted slots of the
    SAME static support — the ``sparse_support`` memo never churns.

    ``wire``: None | "bf16" | "int8" — what crosses the wire (module
    docstring). ``wire_dtype`` is the PR-1 spelling, kept as an alias.
    ``residual``: EF21 error-feedback buffers (pytree like ``stacked``);
    when given the return value is ``(mixed, new_residual)``.
    ``wire_round``: "nearest" | "stochastic" rounding on the int8 wire
    ("stochastic" needs ``wire_key`` and makes the encode unbiased; see
    ``quantize_rows_int8``).
    ``secagg``: pad-PRG base key (``core.secagg.secagg_base_key``) — the
    payload crosses the wire one-time-padded per directed edge and the
    receiver unmasks before the weighted sum (``_mix_pytree_secagg``).
    ``secagg_round`` is the round counter the pads are keyed on (may be
    traced; defaults to 0).
    """
    w = P.shape[0]
    backend = _resolve_backend(backend, adjacency, w)
    wire = normalize_wire(wire if wire is not None else wire_dtype)
    if residual is not None and wire is None:
        raise ValueError("error-feedback residual needs a lossy wire "
                         "(wire='bf16'|'int8')")
    if wire_round == "stochastic" and wire != "int8":
        raise ValueError("wire_round='stochastic' is an int8-wire option "
                         f"(wire={wire!r})")
    if secagg is not None:
        if adjacency is None:
            raise ValueError(
                "secagg needs the static topology: pass "
                "adjacency=<bool [W, W]> (the pads are per wire edge)")
        return _mix_pytree_secagg(
            P, stacked, adjacency, wire=wire, residual=residual,
            wire_round=wire_round, wire_key=wire_key, base=secagg,
            round_=secagg_round)

    if backend == "sparse":
        if adjacency is None:
            raise ValueError(
                "gossip backend 'sparse' needs the static topology: pass "
                "adjacency=<bool [W, W]> (or use backend='pallas')")
        idx_j, val = sparse_weights(P, adjacency)
    Pf = P.astype(jnp.float32)

    def mix_flat(payload, scale):
        """[W, F] mixed rows in fp32 (dequant fused, no fp32 stack)."""
        if backend == "einsum":
            Pw = Pf * scale[None, :] if scale is not None else Pf
            return jnp.einsum("ij,jf->if", Pw,
                              payload.astype(jnp.float32),
                              precision=MIX_PRECISION)
        if backend == "pallas":
            from repro.kernels.ops import gossip_mix
            Pw = Pf * scale[None, :] if scale is not None else Pf
            return gossip_mix(Pw, payload, out_dtype=jnp.float32)
        if backend == "sparse":
            if scale is not None:
                from repro.kernels.ops import gossip_mix_quant
                return gossip_mix_quant(idx_j, val, scale, payload,
                                        out_dtype=jnp.float32)
            from repro.kernels.ops import gossip_mix_sparse
            return gossip_mix_sparse(idx_j, val, payload,
                                     out_dtype=jnp.float32)
        raise ValueError(f"unknown gossip backend {backend!r}")

    leaves, treedef = jax.tree.flatten(stacked)
    r_leaves = jax.tree.flatten(residual)[0] if residual is not None \
        else [None] * len(leaves)
    wire_keys = jax.random.split(wire_key, len(leaves)) \
        if (wire_key is not None and wire_round == "stochastic") \
        else [None] * len(leaves)
    outs, new_rs = [], []
    for x, r, wk in zip(leaves, r_leaves, wire_keys):
        flat = x.reshape(w, -1)
        if wire is None:
            out = mix_flat(flat, None)
            new_r = r
        else:
            r_flat = r.reshape(w, -1) if r is not None else None
            payload, scale, nr = _encode_rows(flat, r_flat, wire,
                                              rounding=wire_round, key=wk)
            out = mix_flat(payload, scale)
            new_r = nr.reshape(x.shape) if nr is not None else None
        outs.append(out.reshape(x.shape).astype(x.dtype))
        new_rs.append(new_r)
    mixed = jax.tree.unflatten(treedef, outs)
    if residual is not None:
        return mixed, jax.tree.unflatten(treedef, new_rs)
    return mixed


def _mix_pytree_secagg(P, stacked, adjacency, *, wire, residual,
                       wire_round, wire_key, base, round_):
    """Receiver-side pairwise-masked gather mix — the in-jit secagg wire.

    Each receiver gathers the encoded payload rows of its padded-CSR
    support; a gathered row models the WIRE: ``ring(q_j) + pad(round,
    j→i)`` in the wire format's integer ring (``core.secagg``), and the
    receiver subtracts the shared directed-edge pad before the trust-
    weighted sum. The OTP is exact word for word, so the recovered rows
    equal the encoded rows bit for bit and the masked mix is BITWISE
    identical to the same gather-sum without masks — at fp32 wire exactly
    the no-secagg gather aggregate, at int8 within the plain quantization
    error (tests/test_secagg.py pins both).

    Dropout/churn recovery is structural: a dead or unsampled edge rides
    P's zero weight, so its (perfectly recovered) row is annihilated and
    its pad is simply never consumed — survivor-renormalized rows,
    vacancy pads and the cross-device k_min fallback all compose with no
    extra protocol. Note the summation ORDER differs from the dense
    einsum backend (gather-sum over K slots vs dense over W), so engine-
    level secagg-on vs -off parity is allclose, not bitwise; the bitwise
    contract lives at this gossip level.
    """
    from repro.core import secagg as sa

    w = P.shape[0]
    round_ = 0 if round_ is None else round_
    idx_np, valid_np = sparse_support(adjacency)
    idx_j = jnp.asarray(idx_np)
    recv = jnp.arange(w, dtype=jnp.int32)[:, None]
    val = jnp.take_along_axis(P.astype(jnp.float32), idx_j, axis=1) \
        * jnp.asarray(valid_np, jnp.float32)
    ebase = sa.domain_key(base, sa.DOMAIN_EDGE)

    leaves, treedef = jax.tree.flatten(stacked)
    r_leaves = jax.tree.flatten(residual)[0] if residual is not None \
        else [None] * len(leaves)
    wire_keys = jax.random.split(wire_key, len(leaves)) \
        if (wire_key is not None and wire_round == "stochastic") \
        else [None] * len(leaves)
    outs, new_rs = [], []
    for li, (x, r, wk) in enumerate(zip(leaves, r_leaves, wire_keys)):
        flat = x.reshape(w, -1)
        if wire is None:
            payload, scale, nr = flat.astype(jnp.float32), None, r
        else:
            r_flat = r.reshape(w, -1) if r is not None else None
            payload, scale, nr = _encode_rows(flat, r_flat, wire,
                                              rounding=wire_round, key=wk)
            nr = nr.reshape(x.shape) if nr is not None else None
        f = payload.shape[1]
        pads = sa.edge_pads(ebase, round_, idx_j, recv, f, wire,
                            tag=2 * li)
        gathered = jnp.take(payload, idx_j, axis=0)       # [W, K, F]
        wire_words = sa.mask_payload(gathered, pads, wire)
        rec = sa.unmask_payload(wire_words, pads, wire)   # == gathered
        if scale is not None:
            spads = sa.edge_pads(ebase, round_, idx_j, recv, 1, None,
                                 tag=2 * li + 1)[..., 0]
            s_g = jnp.take(scale, idx_j, axis=0)          # [W, K]
            s_rec = sa.unmask_payload(
                sa.mask_payload(s_g, spads, None), spads, None)
            weights = val * s_rec                # dequant into the weights
        else:
            weights = val
        out = jnp.einsum("wk,wkf->wf", weights,
                         rec.astype(jnp.float32), precision=MIX_PRECISION)
        outs.append(out.reshape(x.shape).astype(x.dtype))
        new_rs.append(nr)
    mixed = jax.tree.unflatten(treedef, outs)
    if residual is not None:
        return mixed, jax.tree.unflatten(treedef, new_rs)
    return mixed


def mix_pytree_ppermute(P, stacked, mesh, axis: str = "pod",
                        adjacency=None, wire=None, residual=None,
                        secagg=None, secagg_round=None):
    """Sparse-topology gossip via collective_permute ring schedules.

    For a sparse mixing matrix P, the dense all-gather backend moves every
    worker's params to every worker; ``ppermute`` moves only the edges that
    exist. The schedule rotates the worker axis |offsets| times; offset o
    carries edge (i-o -> i) and is skipped entirely when no worker uses it
    (column of nonzero P at that circular offset is empty).

    The schedule is static, so sparsity must come from the static
    ``adjacency`` (bool [W, W], i receives from j — self-loops implied).
    Without it the full dense rotation runs: all W offsets, correct for any
    P, but wire traffic no longer shrinks with topology sparsity. Pass the
    topology whenever you have it.

    stacked: pytree with leading worker axis sharded on ``axis``.
    The padded-CSR nnz selection is FUSED into the ring schedule: offset
    o's ppermute names only the (src, dst) pairs with a real edge
    ``adjacency[dst, src]``, so a pod ships its rows ONLY to the pods
    whose row of P actually uses them (unnamed destinations receive
    zeros, which the zero P weight annihilates — bit-identical output).
    Total gossip wire bytes therefore equal the algorithmic contract —
    nnz(adjacency) payloads per round — instead of (#used offsets × W):
    the paper's sparse-peers economy holds per EDGE, not just per offset.

    ``wire``/``residual``: same contract as ``mix_pytree``. With
    ``wire="int8"`` the ring permutes the int8 payload + one fp32 scale per
    worker instead of fp32 leaves — per-offset bytes drop ~4× on top of the
    offset-skipping economy (with "bf16", ~2×). Encoding and the EF21
    residual are computed OUTSIDE the shard_map: quantization is row-local,
    so it shards trivially and adds no cross-pod traffic.

    ``secagg``/``secagg_round``: same contract as ``mix_pytree`` — the
    sender one-time-pads the payload for offset o's destination INSIDE the
    ring body (edge j → (j+o)%w), the receiver subtracts the shared
    directed-edge pad after the ppermute, so what the collective actually
    moves is the masked wire. Ring slots without a real edge receive
    zeros, whose unmask decodes to garbage — they are gated off by the
    static edge mask before the (zero-weight) accumulate, which a bitcast
    NaN would otherwise poison.
    """
    from jax.sharding import PartitionSpec as Ps

    w = P.shape[0]
    wire = normalize_wire(wire)
    if residual is not None and wire is None:
        raise ValueError("error-feedback residual needs a lossy wire "
                         "(wire='bf16'|'int8')")
    if adjacency is not None:               # static sparsity
        a = np.asarray(adjacency) | np.eye(w, dtype=bool)
        used_offsets = [o for o in range(w)
                        if np.any(a[np.arange(w), (np.arange(w) - o) % w])]
        # nnz row selection per offset: src j -> dst (j+o)%w only where
        # the edge exists
        offset_perm = {
            o: [(j, (j + o) % w) for j in range(w) if a[(j + o) % w, j]]
            for o in used_offsets}
    else:                                   # documented dense fallback
        used_offsets = list(range(w))
        offset_perm = {o: [(j, (j + o) % w) for j in range(w)]
                       for o in used_offsets}

    if secagg is not None:
        from repro.core import secagg as sa
        sa_base = sa.domain_key(secagg, sa.DOMAIN_EDGE)
        sa_round = 0 if secagg_round is None else secagg_round
        a_ok = (np.asarray(adjacency) | np.eye(w, dtype=bool)) \
            if adjacency is not None else np.ones((w, w), bool)
        # ok_vecs[o][i]: does worker i really receive at offset o?
        ok_vecs = {o: jnp.asarray(a_ok[np.arange(w),
                                       (np.arange(w) - o) % w])
                   for o in used_offsets}
    else:
        sa = None

    leaves, treedef = jax.tree.flatten(stacked)
    r_leaves = jax.tree.flatten(residual)[0] if residual is not None \
        else [None] * len(leaves)

    # encode each leaf for the wire (row-local, shards with the worker axis)
    payloads, scales, new_rs = [], [], []
    for x, r in zip(leaves, r_leaves):
        if wire is None:
            payloads.append(x)
            scales.append(None)
            new_rs.append(r)
            continue
        flat = x.reshape(w, -1)
        r_flat = r.reshape(w, -1) if r is not None else None
        payload, scale, nr = _encode_rows(flat, r_flat, wire)
        payloads.append(payload.reshape(x.shape))
        scales.append(scale)
        new_rs.append(nr.reshape(x.shape) if nr is not None else None)
    has_scale = wire == "int8"

    def body(p_local, *args):
        # p_local: [1, W] this worker's mixing row; payload leaves [1, ...]
        # local; int8 wire appends one [1] scale per leaf.
        n = len(leaves)
        qs, scs = args[:n], args[n:] if has_scale else (None,) * n
        idx = jax.lax.axis_index(axis)
        outs = []
        for li, (q, s) in enumerate(zip(qs, scs)):
            acc = jnp.zeros(q.shape, jnp.float32)
            for o in used_offsets:
                src = (idx - o) % w
                weight = p_local[0, src].astype(jnp.float32)
                if o == 0:
                    qq, ss = q, s
                    qqf = qq.astype(jnp.float32)
                    if ss is not None:       # dequant: scale into weight
                        weight = weight * ss[0]
                elif secagg is None:
                    perm = offset_perm[o]
                    qq = jax.lax.ppermute(q, axis, perm)
                    ss = jax.lax.ppermute(s, axis, perm) \
                        if s is not None else None
                    qqf = qq.astype(jnp.float32)
                    if ss is not None:
                        weight = weight * ss[0]
                else:
                    # masked wire: pad for the destination, ship, unmask
                    # the pad of the symmetric inbound edge (src -> idx)
                    perm = offset_perm[o]
                    dst = (idx + o) % w
                    pad_out = sa.edge_pad(sa_base, sa_round, idx, dst,
                                          q.shape, wire, tag=2 * li)
                    qw = jax.lax.ppermute(
                        sa.mask_payload(q, pad_out, wire), axis, perm)
                    pad_in = sa.edge_pad(sa_base, sa_round, src, idx,
                                         q.shape, wire, tag=2 * li)
                    qq = sa.unmask_payload(qw, pad_in, wire)
                    ok = ok_vecs[o][idx]
                    qqf = jnp.where(ok, qq.astype(jnp.float32), 0.0)
                    if s is not None:
                        sp_out = sa.edge_pad(sa_base, sa_round, idx, dst,
                                             s.shape, None, tag=2 * li + 1)
                        sw = jax.lax.ppermute(
                            sa.mask_payload(s, sp_out, None), axis, perm)
                        sp_in = sa.edge_pad(sa_base, sa_round, src, idx,
                                            s.shape, None, tag=2 * li + 1)
                        ss = sa.unmask_payload(sw, sp_in, None)
                        weight = weight * jnp.where(ok, ss[0], 0.0)
                acc = acc + weight * qqf
            outs.append(acc)
        return tuple(outs)

    specs = tuple(Ps(axis) for _ in leaves)
    in_specs = (Ps(axis, None),) + specs
    operands = list(payloads)
    if has_scale:
        in_specs = in_specs + specs
        operands += scales
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=in_specs,
        out_specs=specs, check_vma=False)
    out_leaves = fn(P.astype(jnp.float32), *operands)
    out_leaves = [o.astype(x.dtype) for o, x in zip(out_leaves, leaves)]
    mixed = jax.tree.unflatten(treedef, out_leaves)
    if residual is not None:
        return mixed, jax.tree.unflatten(treedef, new_rs)
    return mixed


# ---------------------------------------------------------------------------
# Worker-axis sharding: local-block CSR + block-granular cross-shard ring
# ---------------------------------------------------------------------------

class WorkerShardPlan:
    """The static schedule of one sharded gossip round.

    The W worker rows are padded to ``wp = shards × block`` and split into
    per-shard blocks of ``block`` consecutive workers. The adjacency
    support then splits into:

    * the DIAGONAL blocks — intra-shard edges, compiled to one padded-CSR
      support per shard (``idx``/``valid`` [S, B, K], local coordinates,
      K = the max local row degree across shards) so the existing
      ``gossip_mix_sparse``/``gossip_mix_quant`` kernels run unchanged on
      the local block;
    * the OFF-DIAGONAL blocks — cross-shard edges, compiled to a
      block-granular ppermute ring: shard-offset ``d`` is used iff some
      shard receives from the shard ``d`` ring positions behind it, and
      its permutation names only the (src, dst) shard pairs with at least
      one real edge. A shard therefore ships its whole block once per
      DISTINCT destination shard — ring bytes scale with the number of
      used shard PAIRS × block, not with W².

    Padded worker rows get a self-loop only (weight supplied by the
    identity padding of P), so the schedule never depends on W being
    divisible by the shard count.
    """

    def __init__(self, adjacency, shards: int):
        a0 = np.asarray(adjacency, bool)
        w = a0.shape[0]
        s = int(shards)
        b = -(-w // s)                       # ceil(w / shards)
        wp = s * b
        a = np.zeros((wp, wp), bool)
        a[:w, :w] = a0
        np.fill_diagonal(a, True)            # self-loops (incl. padding)

        # diagonal blocks -> per-shard padded-CSR support, local coords
        k = 1
        for si in range(s):
            blk = a[si * b:(si + 1) * b, si * b:(si + 1) * b]
            k = max(k, int(blk.sum(axis=1).max()))
        idx = np.tile(np.arange(b, dtype=np.int32)[None, :, None],
                      (s, 1, k))
        valid = np.zeros((s, b, k), bool)
        for si in range(s):
            blk = a[si * b:(si + 1) * b, si * b:(si + 1) * b]
            for i in range(b):
                peers = np.flatnonzero(blk[i]).astype(np.int32)
                idx[si, i, :peers.size] = peers
                valid[si, i, :peers.size] = True

        # off-diagonal blocks -> block-granular ring schedule
        pairs = []
        for src in range(s):
            for dst in range(s):
                if src == dst:
                    continue
                if a[dst * b:(dst + 1) * b, src * b:(src + 1) * b].any():
                    pairs.append((src, dst))
        perms = {}
        for src, dst in pairs:
            perms.setdefault((dst - src) % s, []).append((src, dst))

        at = a0 | np.eye(w, dtype=bool)      # true-W support, self-loops in
        intra = 0
        for si in range(s):
            intra += int(at[si * b:min((si + 1) * b, w),
                            si * b:min((si + 1) * b, w)].sum())

        idx.setflags(write=False)
        valid.setflags(write=False)
        self.w, self.shards, self.block, self.wp = w, s, b, wp
        self.idx, self.valid = idx, valid
        self.pairs = tuple(pairs)
        self.used_offsets = tuple(sorted(perms))
        self.perms = {d: tuple(p) for d, p in perms.items()}
        self.intra_edges = intra
        self.cross_edges = int(at.sum()) - intra

    def ring_bytes(self, n_params: int, wire=None, *, rows: int = 1) -> int:
        """Cross-shard wire bytes of ONE sharded round: every used shard
        pair ships one block of ``block`` worker payloads (int8 payloads
        carry their per-row scales). This is the contract
        ``launch.roofline.sharded_ring_bytes`` must reproduce."""
        from repro.launch.roofline import gossip_wire_bytes
        payload = gossip_wire_bytes(n_params, wire, rows=rows)
        return len(self.pairs) * self.block * payload


_PLAN_CACHE: dict = {}
_PLAN_CACHE_MAX = 16


def worker_shard_plan(adjacency, shards: int) -> WorkerShardPlan:
    """Memoized ``WorkerShardPlan`` (same LRU discipline as
    ``sparse_support`` — the plan re-derives on every trace otherwise)."""
    a = np.asarray(adjacency, bool)
    key = (a.shape, a.tobytes(), int(shards))
    cached = _PLAN_CACHE.get(key)
    if cached is not None:
        _PLAN_CACHE[key] = _PLAN_CACHE.pop(key)
        return cached
    while len(_PLAN_CACHE) >= _PLAN_CACHE_MAX:
        _PLAN_CACHE.pop(next(iter(_PLAN_CACHE)))
    plan = WorkerShardPlan(a, shards)
    _PLAN_CACHE[key] = plan
    return plan


def mix_pytree_sharded(P, stacked, mesh, axis: str = "worker",
                       adjacency=None, wire=None, residual=None,
                       secagg=None, secagg_round=None):
    """Worker-axis-sharded gossip: intra-shard edges run the padded-CSR
    sparse/quant kernels on the LOCAL block, cross-shard edges ride a
    block-granular ppermute ring (``WorkerShardPlan``). Same contract as
    ``mix_pytree``/``mix_pytree_ppermute``: P [W, W] row-stochastic with
    support ⊆ adjacency ∪ self-loops, ``stacked`` a pytree with leading
    axis W, optional lossy ``wire`` + EF21 ``residual``.

    ``secagg``/``secagg_round``: pads ride the ring CHANNELS this
    transport actually has — one OTP per used (src_shard, dst_shard)
    block pair per round (``DOMAIN_SHARD``), masking the whole shipped
    block. The intra-shard diagonal never crosses the wire (it runs
    on-device through the local CSR kernels) and is deliberately NOT
    masked — the privacy boundary is the device, same as every secagg
    deployment that batches co-located users.

    W need not divide the shard count: rows pad to ``shards × block``
    with identity mixing rows and zero payloads, and the padding is
    sliced away before returning. Encoding (and the EF residual) is
    row-local and computed at true W outside the shard_map, so the wire
    numerics match the other transports row for row.
    """
    from jax.sharding import PartitionSpec as Ps

    from repro.kernels.ops import gossip_mix_quant, gossip_mix_sparse

    w = P.shape[0]
    wire = normalize_wire(wire)
    if residual is not None and wire is None:
        raise ValueError("error-feedback residual needs a lossy wire "
                         "(wire='bf16'|'int8')")
    if adjacency is None:                    # documented dense fallback
        adjacency = np.ones((w, w), bool)
    shards = int(mesh.shape[axis])
    plan = worker_shard_plan(adjacency, shards)
    b, wp = plan.block, plan.wp

    if secagg is not None:
        from repro.core import secagg as sa
        sa_base = sa.domain_key(secagg, sa.DOMAIN_SHARD)
        sa_round = 0 if secagg_round is None else secagg_round
        # ok_vecs[d][si]: does shard si really receive a block at ring
        # offset d? (unnamed destinations get zeros — gate the garbage
        # their unmask decodes to before the zero-weight matmul)
        ok_vecs = {}
        for d in plan.used_offsets:
            okv = np.zeros((shards,), bool)
            for _, dst in plan.perms[d]:
                okv[dst] = True
            ok_vecs[d] = jnp.asarray(okv)
    else:
        sa = None

    leaves, treedef = jax.tree.flatten(stacked)
    r_leaves = jax.tree.flatten(residual)[0] if residual is not None \
        else [None] * len(leaves)

    # encode at true W (row-local; identical numerics to the other
    # transports), then pad rows to the sharded extent
    payloads, scales, new_rs = [], [], []
    for x, r in zip(leaves, r_leaves):
        flat = x.reshape(w, -1)
        if wire is None:
            payload, scale, nr = flat, None, r
        else:
            r_flat = r.reshape(w, -1) if r is not None else None
            payload, scale, nr = _encode_rows(flat, r_flat, wire)
            nr = nr.reshape(x.shape) if nr is not None else None
        payloads.append(jnp.pad(payload, ((0, wp - w), (0, 0))))
        scales.append(None if scale is None
                      else jnp.pad(scale, (0, wp - w), constant_values=1.0))
        new_rs.append(nr)
    has_scale = wire == "int8"

    Pp = jnp.pad(P.astype(jnp.float32), ((0, wp - w), (0, wp - w)))
    if wp > w:                               # identity rows for the padding
        pad_eye = np.zeros((wp, wp), np.float32)
        pad_eye[np.arange(w, wp), np.arange(w, wp)] = 1.0
        Pp = Pp + jnp.asarray(pad_eye)
    idx_j = jnp.asarray(plan.idx)
    valid_j = jnp.asarray(plan.valid, jnp.float32)

    def body(p_local, idxb, validb, *args):
        # p_local [B, Wp]: this shard's mixing rows; idxb/validb [1, B, K]
        # the shard's local-block CSR; payload leaves [B, F] local rows
        # (int8 wire appends one [B] scale vector per leaf).
        idx_l, valid_l = idxb[0], validb[0]
        si = jax.lax.axis_index(axis)
        n = len(leaves)
        qs = args[:n]
        scs = args[n:] if has_scale else (None,) * n
        p_diag = jax.lax.dynamic_slice(p_local, (0, si * b), (b, b))
        val = jnp.take_along_axis(p_diag, idx_l, axis=1) * valid_l
        outs = []
        for li, (q, s_) in enumerate(zip(qs, scs)):
            if s_ is not None:               # fused dequant CSR kernel
                acc = gossip_mix_quant(idx_l, val, s_, q,
                                       out_dtype=jnp.float32)
            else:
                acc = gossip_mix_sparse(idx_l, val, q,
                                        out_dtype=jnp.float32)
            for d in plan.used_offsets:
                perm = plan.perms[d]
                src = (si - d) % shards
                if secagg is None:
                    qq = jax.lax.ppermute(q, axis, perm)
                    ss = jax.lax.ppermute(s_, axis, perm) \
                        if s_ is not None else None
                else:
                    # block-channel OTP: mask for the destination shard,
                    # ship, unmask the inbound (src_shard -> si) pad
                    dstb = (si + d) % shards
                    pad_out = sa.edge_pad(sa_base, sa_round, si, dstb,
                                          q.shape, wire, tag=2 * li)
                    qw = jax.lax.ppermute(
                        sa.mask_payload(q, pad_out, wire), axis, perm)
                    pad_in = sa.edge_pad(sa_base, sa_round, src, si,
                                         q.shape, wire, tag=2 * li)
                    ok = ok_vecs[d][si]
                    qq = jnp.where(
                        ok, sa.unmask_payload(qw, pad_in, wire)
                        .astype(jnp.float32), 0.0)
                    ss = None
                    if s_ is not None:
                        sp_out = sa.edge_pad(sa_base, sa_round, si, dstb,
                                             s_.shape, None, tag=2 * li + 1)
                        sw = jax.lax.ppermute(
                            sa.mask_payload(s_, sp_out, None), axis, perm)
                        sp_in = sa.edge_pad(sa_base, sa_round, src, si,
                                            s_.shape, None, tag=2 * li + 1)
                        ss = jnp.where(
                            ok, sa.unmask_payload(sw, sp_in, None), 1.0)
                blk = jax.lax.dynamic_slice(
                    p_local, (0, src * b), (b, b)).astype(jnp.float32)
                if ss is not None:           # dequant: scale into columns
                    blk = blk * ss[None, :]
                acc = acc + jnp.matmul(blk, qq.astype(jnp.float32),
                                       precision=MIX_PRECISION)
            outs.append(acc)
        return tuple(outs)

    specs = tuple(Ps(axis, None) for _ in leaves)
    in_specs = (Ps(axis, None), Ps(axis, None, None),
                Ps(axis, None, None)) + specs
    operands = list(payloads)
    if has_scale:
        in_specs = in_specs + tuple(Ps(axis) for _ in leaves)
        operands += scales
    fn = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                       out_specs=specs, check_vma=False)
    out_leaves = fn(Pp, idx_j, valid_j, *operands)
    out_leaves = [o[:w].reshape(x.shape).astype(x.dtype)
                  for o, x in zip(out_leaves, leaves)]
    mixed = jax.tree.unflatten(treedef, out_leaves)
    if residual is not None:
        return mixed, jax.tree.unflatten(treedef, new_rs)
    return mixed
