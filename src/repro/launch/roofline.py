"""Roofline analysis from compiled dry-run artifacts (no hardware).

Three terms per (arch × shape × mesh), per training/serving step:

    compute    = HLO_FLOPs_total   / (chips × 197e12 FLOP/s bf16)
    memory     = HLO_bytes_total   / (chips × 819e9  B/s HBM)
    collective = collective_bytes  / (chips × 50e9   B/s ICI link)

``cost_analysis()`` supplies flops / bytes of the SPMD-partitioned
per-device module (multiplied back to cluster totals); collective bytes are
parsed from the partitioned HLO text — the sum of result sizes of every
all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, asdict
from typing import Dict

# Published per-chip peaks, keyed by ``jax.Device.device_kind``.
# Source: Google Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16,
# 393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip
# interconnect (4 ICI links of 50 GB/s).
V5E = "TPU v5 lite"
PEAKS = {
    V5E: {"bf16_flops": 197e12, "int8_ops": 393e12, "hbm_bytes": 16e9,
          "hbm_bw": 819e9, "ici_link_bw": 50e9, "ici_links": 4},
}


def chip_peaks(device_kind: str) -> dict:
    """The published peaks of one chip; a kind not in ``PEAKS`` is an
    error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r} (known: {sorted(PEAKS)})") from None


# the dry-run prices the v5e production mesh
PEAK_FLOPS = chip_peaks(V5E)["bf16_flops"]   # FLOP/s per chip
HBM_BW = chip_peaks(V5E)["hbm_bw"]           # bytes/s per chip
ICI_BW = chip_peaks(V5E)["ici_link_bw"]      # bytes/s per link

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([\d,]*)\]")
_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

# gossip wire formats: bytes per parameter on the wire. Single source of
# truth is core/gossip.py (the module that encodes the payloads); aliases
# cover the config spellings.
from repro.core.gossip import WIRE_BYTES as _WIRE_BYTES  # noqa: E402

WIRE_BYTES = {**_WIRE_BYTES, "float32": 4, "bfloat16": 2}


def gossip_wire_bytes(n_params: int, wire=None, *, rows: int = 1) -> int:
    """Bytes of ONE serialized model payload under a gossip wire format:
    payload + the fp32 per-row quantization scales int8 ships alongside
    (``rows`` = number of quantization rows, one per worker×leaf)."""
    b = n_params * WIRE_BYTES[wire]
    if WIRE_BYTES[wire] == 1:
        b += 4 * rows
    return b


def gossip_round_wire_bytes(n_params: int, w: int, out_degree: float,
                            wire=None, *, rows: int = 1) -> float:
    """Cluster-total gossip wire bytes for one DeFTA round: every worker
    ships its payload to ``out_degree`` outbound peers. The sparse-topology
    economy (bytes ∝ nnz edges = w·out_degree, not w²) and the wire-format
    economy (1/2/4 B per param) compose."""
    return w * out_degree * gossip_wire_bytes(n_params, wire, rows=rows)


def ppermute_ring_bytes(n_params: int, adjacency, wire=None, *,
                        rows: int = 1):
    """Cluster-total wire bytes of ONE ``mix_pytree_ppermute`` round over
    a static topology, as ``(nnz_bytes, dense_rotation_bytes)``:

    * ``nnz_bytes`` — with the padded-CSR nnz row selection fused into the
      ring schedule (each offset's ppermute names only real edges), a pod
      ships one payload per out-edge: total = nnz(adjacency) × payload —
      the algorithmic wire contract of ``gossip_round_wire_bytes``.
    * ``dense_rotation_bytes`` — the pre-selection schedule (every used
      offset rotates every pod's whole local stack): |used offsets| × W ×
      payload. The ratio is the row-selection win.
    """
    import numpy as np
    a = np.asarray(adjacency, bool).copy()
    np.fill_diagonal(a, False)              # offset 0 never crosses a link
    w = a.shape[0]
    payload = gossip_wire_bytes(n_params, wire, rows=rows)
    used = [o for o in range(1, w)
            if np.any(a[np.arange(w), (np.arange(w) - o) % w])]
    return int(a.sum()) * payload, len(used) * w * payload


def sharded_ring_bytes(n_params: int, adjacency, shards: int, wire=None, *,
                       rows: int = 1) -> Dict[str, float]:
    """Cross-shard wire contract of ONE worker-axis-sharded gossip round
    (``core.gossip.mix_pytree_sharded`` — the independent re-derivation
    ``WorkerShardPlan.ring_bytes`` is tested against).

    The W×W support pads to ``shards × block`` and splits at shard-block
    granularity: DIAGONAL blocks stay on-device (``intra_edges``, priced
    at zero wire bytes), OFF-DIAGONAL blocks ride a block-granular
    ppermute ring where a (src, dst) shard pair is on the schedule iff its
    block has ≥ 1 real edge — and then ships the WHOLE src block once
    (``bytes_per_boundary`` = block × payload). Total ring bytes scale
    with used shard pairs × block, not with the cross-edge count: dense
    cross-shard coupling amortizes, a single stray edge costs a full
    boundary.
    """
    import numpy as np
    a0 = np.asarray(adjacency, bool)
    w = a0.shape[0]
    s = int(shards)
    b = -(-w // s)                            # ceil(w / shards)
    wp = s * b
    a = np.zeros((wp, wp), bool)
    a[:w, :w] = a0
    np.fill_diagonal(a, True)
    pairs = sum(1 for src in range(s) for dst in range(s)
                if src != dst and
                a[dst * b:(dst + 1) * b, src * b:(src + 1) * b].any())
    at = a0 | np.eye(w, dtype=bool)           # true-W support, self-loops
    intra = sum(int(at[si * b:min((si + 1) * b, w),
                       si * b:min((si + 1) * b, w)].sum())
                for si in range(s))
    payload = gossip_wire_bytes(n_params, wire, rows=rows)
    boundary = b * payload
    return {
        "shards": s,
        "block": b,
        "intra_edges": intra,
        "cross_edges": int(at.sum()) - intra,
        "used_pairs": pairs,
        "bytes_per_boundary": float(boundary),
        "ring_bytes": float(pairs * boundary),
    }


def secagg_pad_bytes(adjacency, n_params: int, wire=None, *,
                     rows: int = 1) -> Dict[str, float]:
    """Privacy-wire roofline of ONE secure-aggregation gossip round.

    The OTP masks ride IN PLACE in the wire format's integer ring
    (``core.secagg``): the wire bytes of a masked round equal the
    plaintext round exactly — privacy costs pad GENERATION, not
    bandwidth. Per directed edge the PRG emits one payload-sized pad
    (int8 adds one uint32 pad per quantization row for the scale
    channel), so ``pad_bytes = nnz(adjacency) × payload``. This is the
    independent re-derivation the bench's mask-accounting gate checks
    ``core.secagg.secagg_mask_bytes`` against.
    """
    import numpy as np
    a = np.asarray(adjacency, bool).copy()
    np.fill_diagonal(a, False)          # self-loop never crosses the wire
    edges = int(a.sum())
    per_edge = n_params * WIRE_BYTES[wire]
    if WIRE_BYTES[wire] == 1:
        per_edge += 4 * rows
    return {
        "directed_edges": edges,
        "pad_bytes_per_edge": float(per_edge),
        "pad_bytes": float(edges * per_edge),
        "wire_overhead_bytes": 0.0,     # in-place OTP: wire unchanged
    }


def dp_epsilon(sigma: float, rounds: int, *, delta: float = 1e-5) -> float:
    """Naive per-round Gaussian-mechanism accountant: each round of the
    clipped-update noise stage (sensitivity = the L2 clip, noise
    N(0,(σ·clip)²)) is (ε₀, δ)-DP with ε₀ = √(2 ln(1.25/δ))/σ, and T
    rounds basic-compose to ε = T·ε₀. Deliberately the LOOSE bound — no
    moments accountant, no subsampling amplification — so the costing
    column is an upper bound a reader can check by hand."""
    import math
    if sigma <= 0:
        return float("inf")
    return rounds * math.sqrt(2.0 * math.log(1.25 / delta)) / sigma


def compiled_bytes(compiled) -> Dict[str, int]:
    """A compiled program's device footprint from XLA's
    ``memory_analysis``: argument, output, temporary and aliased (donated)
    bytes, and ``peak`` = argument + output + temp - alias."""
    mem = compiled.memory_analysis()
    out = {k: int(getattr(mem, f"{k}_size_in_bytes", 0))
           for k in ("argument", "output", "temp", "alias")}
    out["peak"] = (out["argument"] + out["output"] + out["temp"]
                   - out["alias"])
    return out


def shape_bytes(shape_str: str) -> int:
    """Bytes of one HLO shape literal like ``bf16[16,512,128]``."""
    m = _SHAPE_RE.match(shape_str)
    if not m:
        return 0
    dt, dims = m.groups()
    if dt not in _DTYPE_BYTES:
        return 0
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES[dt]


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Per-collective-kind result bytes (per device) summed over the module.

    Matches lines like
      ``%ag = bf16[8,128]{1,0} all-gather(...)``
      ``%ar = (f32[8], f32[8]) all-reduce(...)``
    """
    out = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        m = re.match(r"(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(.+)$", stripped)
        if not m:
            continue
        rhs = m.group(1)
        kind = None
        for c in _COLLECTIVES:
            if re.search(rf"\b{c}(?:-start|-done)?\(", rhs):
                kind = c
                break
        if kind is None or f"{kind}-done(" in rhs:
            continue  # count starts once, not their dones
        # result type is everything before the op name
        type_part = rhs.split(kind)[0]
        bytes_ = sum(shape_bytes(s) for s in
                     re.findall(r"[a-z0-9]+\[[\d,]*\]", type_part))
        out[kind] += bytes_
    return out


@dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    chips: int
    hlo_gflops_total: float          # cluster-total
    hlo_gbytes_total: float
    collective_gbytes_per_chip: float
    collective_breakdown: Dict[str, float]
    t_compute: float                 # seconds
    t_memory: float
    t_collective: float
    bottleneck: str
    model_gflops: float              # 6·N·D (or 2·N·D serving)
    useful_ratio: float              # model_flops / hlo_flops
    bytes_per_device: float          # peak memory from memory_analysis

    def to_dict(self):
        return asdict(self)


def analyze(arch, shape, mesh_name, chips, cost, hlo_text, model_flops,
            peak_bytes, coll_override=None):
    """cost: compiled.cost_analysis() dict (per-device module)."""
    flops_dev = float(cost.get("flops", 0.0))
    bytes_dev = float(cost.get("bytes accessed", 0.0))
    flops_total = flops_dev * chips
    bytes_total = bytes_dev * chips
    coll = coll_override if coll_override is not None \
        else collective_bytes(hlo_text)
    coll_dev = float(sum(coll.values()))

    t_comp = flops_total / (chips * PEAK_FLOPS)
    t_mem = bytes_total / (chips * HBM_BW)
    t_coll = coll_dev / ICI_BW          # per-chip link bytes / link bw
    terms = {"compute": t_comp, "memory": t_mem, "collective": t_coll}
    bottleneck = max(terms, key=terms.get)
    return Roofline(
        arch=arch, shape=shape, mesh=mesh_name, chips=chips,
        hlo_gflops_total=flops_total / 1e9,
        hlo_gbytes_total=bytes_total / 1e9,
        collective_gbytes_per_chip=coll_dev / 1e9,
        collective_breakdown={k: v / 1e9 for k, v in coll.items()},
        t_compute=t_comp, t_memory=t_mem, t_collective=t_coll,
        bottleneck=bottleneck,
        model_gflops=model_flops / 1e9,
        useful_ratio=(model_flops / flops_total) if flops_total else 0.0,
        bytes_per_device=peak_bytes,
    )


def model_flops_estimate(cfg, shape) -> float:
    """6·N·D for training, 2·N·D for prefill, 2·N_active·B for decode
    (N_active = top-k expert params for MoE; attention cache reads are
    captured by the memory term, not counted as useful FLOPs here)."""
    n_active = cfg.param_count(active_only=True)
    if shape.mode == "train":
        return 6.0 * n_active * shape.global_batch * shape.seq_len
    if shape.mode == "prefill":
        return 2.0 * n_active * shape.global_batch * shape.seq_len
    return 2.0 * n_active * shape.global_batch   # decode: one token
