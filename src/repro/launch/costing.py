"""Scan-aware cost accounting.

XLA's HLO cost analysis counts a while-loop body ONCE, so a 61-layer model
lowered as ``scan(pattern_block)`` reports ~1 layer of FLOPs/bytes, and the
text-parsed collective bytes likewise under-count loop-carried collectives.

Correction: compile the scan body (one pattern of blocks, same shardings,
same remat policy, with fwd+bwd for training) as a standalone executable and
add ``(repeats - 1) × body_cost`` to the main program's cost. The body is
exactly what the scan iterates, so the corrected totals match an unrolled
lowering (validated in tests against small unrolled configs).
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig, ShapeConfig
from repro.launch.roofline import collective_bytes
from repro.launch.sharding_rules import sharding_tree, with_sharding
from repro.models import blocks as blocks_mod
from repro.models import model as model_mod
from repro.sharding import logical_rules, resolve_spec
from jax.sharding import NamedSharding, PartitionSpec as P


def gossip_cost(cfg: ModelConfig, fl_pods: int, *, wire=None,
                out_degree: float = 0.0,
                adjacency=None) -> Dict[str, float]:
    """Per-round DeFTA gossip WIRE cost, accounted by wire dtype.

    Unlike the HLO-parsed collective bytes (which see whatever one backend
    lowering emits), this is the algorithmic wire contract: every pod ships
    one serialized model payload to each of its ``out_degree`` outbound
    peers (default: fully connected, pods-1), with the payload priced by
    the gossip wire format — 4 B/param fp32, 2 B bf16, 1 B int8 (+ one
    fp32 scale per worker×leaf quantization row). See core/gossip.py.

    The ``ppermute`` transport realizes this contract on the wire:
    ``ring_bytes`` is its per-round total with the nnz row selection fused
    into the ring schedule (== the algorithmic contract over
    ``adjacency``; default fully-connected pods), and
    ``ring_bytes_dense_rotation`` is the pre-selection schedule that
    rotated every pod's whole stack per used offset — the ratio is the
    row-selection win.
    """
    import numpy as np

    from repro.core.topology import make_topology
    from repro.launch.roofline import ICI_BW, gossip_round_wire_bytes, \
        gossip_wire_bytes, ppermute_ring_bytes
    from repro.models import model as model_mod

    sds = model_mod.abstract_params(cfg)
    leaves = jax.tree.leaves(sds)
    n_params = sum(int(np.prod(s.shape)) for s in leaves)
    deg = out_degree or max(fl_pods - 1, 0)
    payload = gossip_wire_bytes(n_params, wire, rows=len(leaves))
    if adjacency is None:
        adjacency = make_topology("dense", fl_pods, fl_pods - 1)
    ring, ring_dense = ppermute_ring_bytes(n_params, adjacency, wire,
                                           rows=len(leaves))
    return {
        "wire": wire or "fp32",
        "payload_bytes": float(payload),
        "round_bytes": gossip_round_wire_bytes(
            n_params, fl_pods, deg, wire, rows=len(leaves)),
        "ring_bytes": float(ring),
        "ring_bytes_dense_rotation": float(ring_dense),
        "t_ici_s": payload * deg / ICI_BW,   # per-pod egress / link bw
    }


def participation_cost(cfg: ModelConfig, enrolled: int, sample_k: int, *,
                       wire=None, avg_peers: int = 4,
                       dropout: float = 0.0,
                       straggle: float = 0.0) -> Dict[str, float]:
    """Cross-device participation wire cost: enrolled vs sampled.

    In the cross-device world (``scenarios.cross_device``) only the
    ``sample_k``-user cohort is on the wire each round — the other
    ``enrolled - sample_k`` users hold state but ship nothing. Per round
    each cohort member sends one serialized payload to each of its
    ``avg_peers`` outbound cohort peers (priced by the gossip wire format,
    as in ``gossip_cost``); full participation would put every enrolled
    user on the wire at the same degree. ``expected_round_bytes``
    additionally discounts mid-round dropout (a departed slot's partial
    payload is masked out of the mix; we price the expectation at half a
    payload) — straggler timeouts do NOT cut wire bytes, the slot is
    consumed by peers and only its own merge is skipped.
    """
    import numpy as np

    from repro.launch.roofline import gossip_wire_bytes

    sds = model_mod.abstract_params(cfg)
    leaves = jax.tree.leaves(sds)
    n_params = sum(int(np.prod(s.shape)) for s in leaves)
    deg = min(avg_peers, sample_k - 1)
    payload = float(gossip_wire_bytes(n_params, wire, rows=len(leaves)))
    cohort_bytes = sample_k * deg * payload
    full_bytes = enrolled * min(avg_peers, enrolled - 1) * payload
    rate = sample_k / enrolled
    return {
        "wire": wire or "fp32",
        "enrolled": enrolled,
        "sample_k": sample_k,
        "sampling_rate": rate,
        "payload_bytes": payload,
        "round_bytes": cohort_bytes,
        "round_bytes_full_participation": full_bytes,
        "wire_reduction": full_bytes / max(cohort_bytes, 1.0),
        "expected_round_bytes": cohort_bytes * (1.0 - 0.5 * dropout),
        # how sparsely DTS observes any one peer: expected rounds between
        # a user's appearances in the cohort
        "rounds_between_participations": 1.0 / max(rate, 1e-12),
    }


def privacy_cost(cfg: ModelConfig, w: int, rounds: int, *, wire=None,
                 adjacency=None, secagg: bool = True,
                 dp_sigma: float = 0.0,
                 dp_delta: float = 1e-5) -> Dict[str, float]:
    """Privacy column for a dry-run: what the secagg wire and the DP
    noise stage cost per round, in the same algorithmic-contract terms as
    ``gossip_cost``.

    * ``pad_bytes`` — PRG pad material per round (one payload-sized pad
      per directed edge; ``roofline.secagg_pad_bytes``). The WIRE bytes
      are zero extra: the OTP masks in place in the wire format's
      integer ring, so a masked round ships exactly the plaintext
      round's bytes — that invariant is the bench_guard accounting gate.
    * ``epsilon`` — the naive basic-composition Gaussian accountant over
      ``rounds`` (``roofline.dp_epsilon``; inf when dp_sigma == 0).
    """
    import numpy as np

    from repro.core.topology import make_topology
    from repro.launch.roofline import dp_epsilon, secagg_pad_bytes

    sds = model_mod.abstract_params(cfg)
    leaves = jax.tree.leaves(sds)
    n_params = sum(int(np.prod(s.shape)) for s in leaves)
    if adjacency is None:
        adjacency = make_topology("dense", w, w - 1)
    pads = (secagg_pad_bytes(adjacency, n_params, wire, rows=len(leaves))
            if secagg else {"directed_edges": 0, "pad_bytes_per_edge": 0.0,
                            "pad_bytes": 0.0, "wire_overhead_bytes": 0.0})
    return {
        **pads,
        "wire": wire or "fp32",
        "secagg": bool(secagg),
        "dp_sigma": float(dp_sigma),
        "dp_delta": float(dp_delta),
        "rounds": int(rounds),
        "epsilon": dp_epsilon(dp_sigma, rounds, delta=dp_delta),
    }


def worker_shard_cost(cfg: ModelConfig, w: int, shards: int, *, wire=None,
                      adjacency=None) -> Dict[str, float]:
    """Cross-shard cost column for a worker-axis-sharded round program.

    Three things a dry-run wants to see before committing a 10k–100k
    worker world to a mesh:

    * ``per_shard_hbm_bytes`` — the per-device slice of the carried
      worker state (params + best-eval backup + the EF21 residual on
      lossy wires, fp32, plus the W-wide confidence row), ``block``
      workers per shard. This is THE number the sharded layout buys:
      it shrinks 1/shards while the replicated layout pins the whole
      [W, ...] stack on every device.
    * ``intra_edges`` / ``cross_edges`` — how the topology's support
      splits at shard-block granularity (intra runs the padded-CSR
      kernels on-device, cross rides the ring).
    * ``ring_bytes`` / ``bytes_per_boundary`` — the cross-shard ppermute
      contract of ``roofline.sharded_ring_bytes``: used shard pairs ×
      block × payload.
    """
    import numpy as np

    from repro.core.gossip import WIRE_BYTES as _WB
    from repro.core.topology import make_topology
    from repro.launch.roofline import ICI_BW, sharded_ring_bytes

    sds = model_mod.abstract_params(cfg)
    leaves = jax.tree.leaves(sds)
    n_params = sum(int(np.prod(s.shape)) for s in leaves)
    if adjacency is None:
        adjacency = make_topology("dense", w, w - 1)
    info = sharded_ring_bytes(n_params, adjacency, shards, wire,
                              rows=len(leaves))
    lossy = _WB.get(wire, 4) < 4
    copies = 3 if lossy else 2               # params + backup (+ residual)
    per_worker = n_params * 4 * copies + w * 4
    return {
        **info,
        "wire": wire or "fp32",
        "n_params": float(n_params),
        "state_bytes_per_worker": float(per_worker),
        "per_shard_hbm_bytes": float(info["block"] * per_worker),
        "replicated_hbm_bytes": float(w * per_worker),
        "t_ici_s": info["ring_bytes"] / (shards * ICI_BW),
    }


def telemetry_cost(num_workers: int, window: int, *, kind: str = "defta",
                   scenario: bool = True, use_ef: bool = False,
                   tick: bool = False) -> Dict[str, float]:
    """Telemetry-plane buffer cost: what the in-scan metrics probes add to
    the carried state per round and per scan window.

    ``kind``: "defta" (per-worker probes over ``num_workers``), "fedavg"
    (star-topology probes), or "cross_device" (cohort probes over a
    ``num_workers``-sized sample-k block). ``window`` is the scan chunk
    length the stacked ys buffer covers (= eval_every rounds, or the
    while-loop padding for async). ``tick`` adds the fire-gated tick's
    ``fired`` mask (async mode). These are DEVICE buffer bytes, not wire
    bytes — telemetry never leaves the chip until the eval-boundary flush.
    """
    from repro.telemetry.spec import (cross_device_specs, defta_specs,
                                      fedavg_specs, frame_bytes, tick_specs)

    if kind == "defta":
        specs = defta_specs(num_workers, scenario=scenario, use_ef=use_ef)
    elif kind == "fedavg":
        specs = fedavg_specs(num_workers)
    elif kind == "cross_device":
        specs = cross_device_specs(num_workers, use_ef=use_ef)
    else:
        raise ValueError(f"unknown telemetry kind {kind!r}")
    if tick:
        specs = specs + tick_specs(num_workers)
    per_round = frame_bytes(specs)
    return {
        "kind": kind,
        "probes": len(specs),
        "bytes_per_round": float(per_round),
        "window_rounds": int(window),
        "buffer_bytes": float(per_round * window),
    }


def scenario_gossip_cost(cfg: ModelConfig, fl_pods: int, compiled_scn, *,
                         wire=None, out_degree: float = 0.0) -> Dict:
    """Scenario-adjusted gossip wire cost: the static per-round bytes of
    ``gossip_cost`` scaled by the scenario's live-edge fraction (each live
    edge ships one payload, so churn/partitions cut wire bytes
    proportionally). Reports the per-segment trajectory and the timeline
    mean — the "cost delta" a dry-run prints next to the static number.

    ``ring_bytes_scenario`` is the same delta applied to the ppermute ring
    transport (nnz row selection fused into the schedule) — what a
    ``train.py --fl --scenario`` run actually ships per round; with the
    selection the ring achieves the algorithmic contract, so a dead edge's
    payload really does come off the wire."""
    import numpy as np

    from repro.core.topology import make_topology

    base = gossip_cost(cfg, fl_pods, wire=wire, out_degree=out_degree)
    w = compiled_scn.num_workers
    adj = make_topology("dense", w, w - 1)
    s = compiled_scn.summary(adj)
    frac = s["mean_edge_fraction"]
    return {
        **base,
        "scenario": s["name"],
        "mean_edge_fraction": frac,
        "round_bytes_scenario": base["round_bytes"] * frac,
        "ring_bytes_scenario": base["ring_bytes"] * frac,
        "segments": s["segments"],
        "summary": s,           # the full digest — callers must not
                                # recompute it (the per-segment loop is
                                # O(S·W²))
    }


def _cost_of(compiled) -> Tuple[float, float, Dict[str, int]]:
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    coll = collective_bytes(compiled.as_text())
    return (float(cost.get("flops", 0.0)),
            float(cost.get("bytes accessed", 0.0)), coll)


def _pattern_param_sds(cfg: ModelConfig, mesh, rules):
    """SDS + shardings for ONE pattern's params (unstacked scan slice)."""
    schedule = cfg.block_schedule()
    prefix_len, pattern, repeats = blocks_mod.factor_schedule(schedule)

    from repro.models.layers import Builder
    b = Builder(jax.random.PRNGKey(0), jnp.dtype(cfg.dtype), abstract=True)
    for pos, kind in enumerate(pattern):
        blocks_mod.init_block(b.sub(str(pos)), cfg, kind,
                              cross=cfg.is_encoder_decoder)
    return b.params, b.axes, pattern, repeats, prefix_len


def body_cost(cfg: ModelConfig, shape: ShapeConfig, mesh, rules, *,
              fl_pods: int = 0, moe_strategy: str = "grouped"):
    """Compile one scan-body step (fwd+bwd for train) and return its cost.

    Returns (flops, bytes, coll_dict, repeats) where the costs are for ONE
    pattern iteration under the production sharding.
    """
    schedule = cfg.block_schedule()
    prefix_len, pattern, repeats = blocks_mod.factor_schedule(schedule)
    if not cfg.scan_layers or repeats <= 1:
        return 0.0, 0.0, {}, 1

    sds, axes, pattern, repeats, _ = _pattern_param_sds(cfg, mesh, rules)
    pshard = sharding_tree(mesh, rules, axes, sds)
    sds = with_sharding(sds, pshard)

    b = shape.global_batch // max(fl_pods, 1)
    if shape.mode == "decode":
        s = 1
    else:
        s = shape.seq_len
        if cfg.family == "vlm":
            s += cfg.num_vision_tokens
    dt = jnp.dtype(cfg.dtype)
    with logical_rules(mesh, rules):
        xspec = resolve_spec(("batch", "act_seq", "embed"), (b, s, cfg.d_model))
    x_sds = jax.ShapeDtypeStruct(
        (b, s, cfg.d_model), dt,
        sharding=NamedSharding(mesh, xspec or P()))
    pos_sds = jax.ShapeDtypeStruct((b, s), jnp.int32)

    window = cfg.sliding_window

    if shape.mode == "train":
        def body(params, x, positions):
            def f(pp, xx):
                aux = jnp.zeros((), jnp.float32)
                for pos, kind in enumerate(pattern):
                    xx, aux, _ = blocks_mod.block_apply(
                        pp[str(pos)], cfg, kind, xx, positions, aux,
                        window=window, moe_strategy=moe_strategy)
                return (xx.astype(jnp.float32).mean() + aux)
            if cfg.remat:
                f = jax.checkpoint(f, prevent_cse=False)
            loss, grads = jax.value_and_grad(f)(params, x)
            return loss, grads
        lowered = jax.jit(body).lower(sds, x_sds, pos_sds)
    elif shape.mode == "prefill":
        def body(params, x, positions):
            aux = jnp.zeros((), jnp.float32)
            for pos, kind in enumerate(pattern):
                x, aux, _ = blocks_mod.block_apply(
                    params[str(pos)], cfg, kind, x, positions, aux,
                    window=window, moe_strategy=moe_strategy)
            return x
        lowered = jax.jit(body).lower(sds, x_sds, pos_sds)
    else:  # decode: one pattern block with its cache slice
        cache_sds = jax.eval_shape(
            lambda: {str(p): blocks_mod.init_block_cache(
                cfg, k, shape.global_batch, shape.seq_len, window)
                for p, k in enumerate(pattern)})
        cache_axes = {str(p): blocks_mod.block_cache_axes(k)
                      for p, k in enumerate(pattern)}
        cshard = sharding_tree(mesh, rules, cache_axes, cache_sds)
        cache_sds = with_sharding(cache_sds, cshard)
        x1 = jax.ShapeDtypeStruct((shape.global_batch, 1, cfg.d_model), dt)
        pos1 = jax.ShapeDtypeStruct((), jnp.int32)

        def body(params, x, cache, pos):
            new_c = {}
            for p, kind in enumerate(pattern):
                x, new_c[str(p)] = blocks_mod.block_decode(
                    params[str(p)], cfg, kind, x, cache[str(p)], pos,
                    window=window, moe_strategy=moe_strategy)
            return x, new_c
        lowered = jax.jit(body, donate_argnums=(2,)).lower(
            sds, x1, cache_sds, pos1)

    compiled = lowered.compile()
    flops, bytes_, coll = _cost_of(compiled)
    if fl_pods:
        # body compiled per-pod; the vmapped main runs fl_pods copies that
        # are pod-sharded, so per-DEVICE cost is unchanged. Scale totals by
        # pods only where we aggregate cluster-wide (caller handles chips).
        pass
    return flops, bytes_, coll, repeats


def corrected_cost(main_compiled, cfg: ModelConfig, shape: ShapeConfig,
                   mesh, rules, *, fl_pods: int = 0,
                   moe_strategy: str = "grouped"):
    """(flops_dev, bytes_dev, coll_dev_dict) with scan-body correction.
    Used for prefill/decode (single outer program + layer scan)."""
    flops, bytes_, coll = _cost_of(main_compiled)
    bf, bb, bc, repeats = body_cost(cfg, shape, mesh, rules,
                                    fl_pods=fl_pods,
                                    moe_strategy=moe_strategy)
    if repeats > 1:
        flops += bf * (repeats - 1)
        bytes_ += bb * (repeats - 1)
        for k, v in bc.items():
            coll[k] = coll.get(k, 0) + v * (repeats - 1)
    return flops, bytes_, coll


def train_cost(cfg: ModelConfig, shape: ShapeConfig, mesh, rules, *,
               optimizer, microbatches: int = 1, fl_pods: int = 0,
               moe_strategy: str = "grouped"):
    """Composable per-step cost for the (possibly microbatched) train step:

        total = mb × (grads_B + (R−1) × layer_body_C) + opt_update_D

    B = fwd+bwd of the whole model on ONE microbatch (layer scan counted
        once by XLA, corrected by C), grads forced to param sharding so the
        data-axis gradient reduction is included;
    C = one extra layer-scan iteration (body_cost);
    D = optimizer update (params/grads/moments traffic).

    All terms are per-device costs of SPMD-partitioned modules.
    """
    import dataclasses as _dc

    from repro.launch.steps import abstract_state, input_specs

    # ---- B: one-microbatch grads ---------------------------------------
    pods = max(fl_pods, 1)
    mb_shape = _dc.replace(shape,
                           global_batch=shape.global_batch // pods
                           // microbatches)
    params_sds, opt_sds, opt = abstract_state(
        cfg, optimizer, mesh=mesh, rules=rules)
    specs = input_specs(cfg, mb_shape, mesh, rules)
    pshards = jax.tree.map(lambda s: s.sharding, params_sds)

    def grads_fn(params, batch):
        def lf(p):
            return model_mod.loss_fn(p, cfg, batch,
                                     moe_strategy=moe_strategy)
        (_, _), grads = jax.value_and_grad(lf, has_aux=True)(params)
        return grads

    b_compiled = jax.jit(grads_fn, out_shardings=pshards).lower(
        params_sds, specs).compile()
    bf, bb, bcoll = _cost_of(b_compiled)

    # ---- C: per-extra-layer cost ----------------------------------------
    cf, cb, ccoll, repeats = body_cost(cfg, mb_shape, mesh, rules,
                                       moe_strategy=moe_strategy)

    # ---- D: optimizer update --------------------------------------------
    grads_sds = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                       sharding=s.sharding), params_sds)
    step_sds = jax.ShapeDtypeStruct((), jnp.int32)

    def upd(params, grads, opt_state, step):
        return opt.update(params, grads, opt_state, step)

    d_compiled = jax.jit(upd, donate_argnums=(0, 2)).lower(
        params_sds, grads_sds, opt_sds, step_sds).compile()
    df, db, dcoll = _cost_of(d_compiled)

    flops = microbatches * (bf + (repeats - 1) * cf) + df
    bytes_ = microbatches * (bb + (repeats - 1) * cb) + db
    coll: Dict[str, float] = {}
    for src, mult in ((bcoll, microbatches),
                      (ccoll, microbatches * (repeats - 1)), (dcoll, 1)):
        for k, v in src.items():
            coll[k] = coll.get(k, 0) + v * mult
    return flops, bytes_, coll
