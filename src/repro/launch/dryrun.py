"""Multi-pod dry-run: prove the distribution config is coherent by lowering
and compiling every (architecture × input shape × mesh) combination on the
production mesh, with ShapeDtypeStruct inputs (no allocation), and dump
memory/cost/roofline data for EXPERIMENTS.md.

A CPU tool by design: it pins JAX to the CPU platform with 512 host
devices before JAX initialises, so it never takes a chip. It exits 1 when
any (arch, shape) pair failed.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-0.6b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]
"""
import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro.config import SHAPES, ModelConfig, ShapeConfig
from repro.configs import ARCH_IDS, get_config
from repro.launch import roofline as rf
from repro.launch.mesh import make_production_mesh
from repro.launch.sharding_rules import base_rules
from repro.launch.steps import (abstract_state, build_decode_step,
                                build_fl_train_step, build_prefill_step,
                                build_train_step, input_specs)
from repro.sharding import logical_rules

# shapes skipped per DESIGN.md (noted, not silent)
SKIPS = {
    ("whisper-tiny", "long_500k"):
        "decoder capped at 448 learned positions; 512k-token whisper decode "
        "is not a meaningful computation (DESIGN.md §shape-skips)",
}

# scenario cost reports are compiled over this many epochs (the presets'
# event timelines all fit well inside it)
SCENARIO_HORIZON = 50

# archs needing the sliding-window variant for long_500k (full-attention
# families; window makes decode memory/compute linear)
SLIDING_WINDOW_FOR_LONG = 4096
FULL_ATTENTION_FAMILIES = ("dense", "moe", "vlm")


def pick_optimizer(cfg: ModelConfig) -> str:
    # Adafactor above ~25B params: Adam moments would not fit HBM.
    return "adafactor" if cfg.param_count() > 25e9 else "adam"


def pick_microbatches(cfg: ModelConfig, shape: ShapeConfig,
                      fl_pods: int) -> int:
    """Grad-accumulation depth: big models need it to bound per-step
    activation memory (EXPERIMENTS.md §Dry-run notes the policy)."""
    if shape.mode != "train":
        return 1
    if cfg.param_count() < 10e9:
        return 1
    b_pod = shape.global_batch // max(fl_pods, 1)
    return max(1, min(8, b_pod // 16))


def pick_moe_strategy(cfg: ModelConfig, variant: str = "baseline") -> str:
    # expert-parallel shard_map whenever the model has routed experts
    if cfg.moe is None:
        return "grouped"
    return "eplocal_fp8" if "fp8" in variant else "eplocal"


def effective_config(arch: str, shape: ShapeConfig,
                     variant: str = "baseline") -> ModelConfig:
    cfg = get_config(arch)
    if shape.name == "long_500k" and cfg.family in FULL_ATTENTION_FAMILIES:
        cfg = dataclasses.replace(cfg, sliding_window=SLIDING_WINDOW_FOR_LONG)
    if "noremat" in variant:
        cfg = dataclasses.replace(cfg, remat=False)
    return cfg


def run_dryrun(arch: str, shape_name: str, *, multi_pod: bool = False,
               variant: str = "baseline", optimizer: str = "",
               accum_dtype: str = "float32", fl: bool = True,
               scenario: str = "", cd_enrolled: int = 10_000,
               cd_sample_k: int = 64, shard_workers: int = 8,
               verbose: bool = True):
    """Lower + compile one (arch, shape, mesh). Returns result dict.

    ``fl=False`` with multi_pod lowers the FedAvg-across-pods baseline:
    params replicated over pods, per-step gradient all-reduce crossing the
    pod boundary (the centralized comparison point for §Perf)."""
    shape = SHAPES[shape_name]
    if (arch, shape_name) in SKIPS:
        return {"arch": arch, "shape": shape_name,
                "mesh": "multi" if multi_pod else "single",
                "status": "skipped", "reason": SKIPS[(arch, shape_name)]}
    cfg = effective_config(arch, shape, variant)
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    rules = base_rules(multi_pod, variant=variant)
    opt_name = optimizer or pick_optimizer(cfg)
    fl_pods = mesh.shape.get("pod", 0) if (multi_pod and fl and
                                           shape.mode == "train") else 0
    if fl_pods:
        # inside the vmap(spmd_axis_name="pod") body, constraints must not
        # mention the pod axis — vmap supplies it for the batched dims.
        rules = {**rules, "batch": ("data",)}

    moe_strategy = pick_moe_strategy(cfg, variant)
    microbatches = pick_microbatches(cfg, shape, fl_pods)
    if "mb16" in variant:
        microbatches = max(microbatches, 16)

    t0 = time.time()
    with mesh, logical_rules(mesh, rules):
        specs = input_specs(cfg, shape, mesh, rules, fl_pods=fl_pods)
        if shape.mode == "train":
            params_sds, opt_sds, opt = abstract_state(
                cfg, opt_name, mesh=mesh, rules=rules, fl_pods=fl_pods)
            step_sds = jax.ShapeDtypeStruct((), jnp.int32)
            adt = jnp.dtype(accum_dtype)
            if fl_pods:
                step_fn = build_fl_train_step(
                    cfg, opt, moe_strategy=moe_strategy,
                    microbatches=microbatches, spmd_axis_name="pod",
                    accum_dtype=adt)
            else:
                step_fn = build_train_step(cfg, opt,
                                           moe_strategy=moe_strategy,
                                           microbatches=microbatches,
                                           accum_dtype=adt)
            lowered = jax.jit(step_fn, donate_argnums=(0, 1)).lower(
                params_sds, opt_sds, step_sds, specs)
        elif shape.mode == "prefill":
            params_sds, _, _ = abstract_state(cfg, "sgd", mesh=mesh,
                                              rules=rules)
            step_fn = build_prefill_step(cfg, moe_strategy=moe_strategy)
            lowered = jax.jit(step_fn).lower(params_sds, specs)
        else:  # decode
            params_sds, _, _ = abstract_state(cfg, "sgd", mesh=mesh,
                                              rules=rules)
            dec_strategy = moe_strategy if cfg.moe is not None else "dense"
            step_fn = build_decode_step(cfg, moe_strategy=dec_strategy)
            args = [params_sds, specs["tokens"], specs["cache"],
                    specs["pos"]]
            kw = {}
            if cfg.is_encoder_decoder:
                kw["enc_out"] = specs["enc_out"]
            lowered = jax.jit(step_fn, donate_argnums=(2,)).lower(*args, **kw)
        t_lower = time.time() - t0

        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    # DeFTA gossip step (the paper's cross-pod aggregation): lower+compile
    # separately — it runs every K train steps, not inside train_step.
    gossip_info = None
    if fl_pods:
        from repro.launch.steps import build_gossip_step
        from repro.launch.roofline import collective_bytes as _cb
        with mesh, logical_rules(mesh, rules):
            mix_sds = jax.ShapeDtypeStruct((fl_pods, fl_pods), jnp.float32)
            g_lowered = jax.jit(build_gossip_step(cfg),
                                donate_argnums=(0,)).lower(params_sds,
                                                           mix_sds)
            g_compiled = g_lowered.compile()
        g_cost = g_compiled.cost_analysis()
        if isinstance(g_cost, (list, tuple)):
            g_cost = g_cost[0]
        g_coll = _cb(g_compiled.as_text())
        from repro.launch.costing import gossip_cost
        g_costs = {fmt: gossip_cost(cfg, fl_pods, wire=fmt)
                   for fmt in (None, "bf16", "int8")}
        gossip_info = {
            "collective_gbytes_per_chip": sum(g_coll.values()) / 1e9,
            "collective_breakdown": {k: v / 1e9 for k, v in g_coll.items()},
            "t_collective_s": sum(g_coll.values()) / rf.ICI_BW,
            "flops_dev": float(g_cost.get("flops", 0.0)),
            # algorithmic wire bytes per round, by gossip wire format —
            # the int8 row is what mix_pytree(wire="int8") actually ships
            "wire_gbytes_per_round": {
                fmt or "fp32": gc["round_bytes"] / 1e9
                for fmt, gc in g_costs.items()},
            # the ppermute ring transport's realized bytes (nnz row
            # selection fused into the schedule == the algorithmic
            # contract) vs the pre-selection whole-stack rotation
            "ppermute_ring_gbytes_per_round": {
                fmt or "fp32": gc["ring_bytes"] / 1e9
                for fmt, gc in g_costs.items()},
            "ppermute_dense_rotation_gbytes_per_round":
                g_costs[None]["ring_bytes_dense_rotation"] / 1e9,
        }
        # cross-device participation column: what the same model costs per
        # round when only a sampled cohort (not the enrolled population)
        # is on the wire — the churn-as-default deployment shape
        from repro.launch.costing import participation_cost
        p_costs = {fmt: participation_cost(
            cfg, cd_enrolled, cd_sample_k, wire=fmt,
            dropout=0.05, straggle=0.10)
            for fmt in (None, "bf16", "int8")}
        p0 = p_costs[None]
        gossip_info["participation"] = {
            "enrolled": p0["enrolled"],
            "sample_k": p0["sample_k"],
            "sampling_rate": p0["sampling_rate"],
            "rounds_between_participations":
                p0["rounds_between_participations"],
            "wire_reduction_vs_full": p0["wire_reduction"],
            "cohort_wire_gbytes_per_round": {
                fmt or "fp32": pc["round_bytes"] / 1e9
                for fmt, pc in p_costs.items()},
            "expected_wire_gbytes_per_round": {
                fmt or "fp32": pc["expected_round_bytes"] / 1e9
                for fmt, pc in p_costs.items()},
            "full_participation_wire_gbytes_per_round":
                p0["round_bytes_full_participation"] / 1e9,
        }
        # worker-sharding column: the cross-shard contract of a sharded
        # round program at simulation scale — per-shard HBM for the
        # carried worker state, how the topology's support splits into
        # intra-shard (padded-CSR, on-device) vs cross-shard (ppermute
        # ring) edges, and the ring bytes per shard boundary
        # (roofline.sharded_ring_bytes == WorkerShardPlan.ring_bytes)
        from repro.core.topology import make_topology as _mt
        from repro.launch.costing import worker_shard_cost
        ws_w = cd_enrolled
        ws_adj = _mt("random_kout", ws_w, 4, seed=0)
        ws = {fmt: worker_shard_cost(cfg, ws_w, shard_workers, wire=fmt,
                                     adjacency=ws_adj)
              for fmt in (None, "bf16", "int8")}
        ws0 = ws[None]
        gossip_info["worker_sharding"] = {
            "workers": ws_w,
            "shards": ws0["shards"],
            "block": ws0["block"],
            "intra_edges": ws0["intra_edges"],
            "cross_edges": ws0["cross_edges"],
            "used_shard_pairs": ws0["used_pairs"],
            "per_shard_hbm_gb": ws0["per_shard_hbm_bytes"] / 1e9,
            "replicated_hbm_gb": ws0["replicated_hbm_bytes"] / 1e9,
            "ring_gbytes_per_round": {
                fmt or "fp32": c["ring_bytes"] / 1e9
                for fmt, c in ws.items()},
            "bytes_per_boundary": {
                fmt or "fp32": c["bytes_per_boundary"]
                for fmt, c in ws.items()},
        }
        if verbose:
            print(f"  worker sharding: {ws_w} workers / "
                  f"{ws0['shards']} shards (block {ws0['block']}) -> "
                  f"{ws0['per_shard_hbm_bytes'] / 1e9:.2f} GB/shard vs "
                  f"{ws0['replicated_hbm_bytes'] / 1e9:.2f} replicated; "
                  f"edges {ws0['intra_edges']} intra / "
                  f"{ws0['cross_edges']} cross "
                  f"({ws0['used_pairs']} shard pairs on the ring, "
                  f"{ws0['ring_bytes'] / 1e9:.2f} GB/round fp32)")
        # telemetry-plane buffer column: what the in-scan metrics probes
        # add to the carried state when a run streams a ledger — device
        # buffer bytes only, zero extra dispatches (repro/telemetry)
        from repro.launch.costing import telemetry_cost
        tc_pod = telemetry_cost(fl_pods, SCENARIO_HORIZON,
                                scenario=bool(scenario))
        tc_cd = telemetry_cost(cd_sample_k, SCENARIO_HORIZON,
                               kind="cross_device")
        gossip_info["telemetry"] = {
            "pod_probes": tc_pod["probes"],
            "pod_bytes_per_round": tc_pod["bytes_per_round"],
            "pod_buffer_kb": tc_pod["buffer_bytes"] / 1e3,
            "cross_device_probes": tc_cd["probes"],
            "cross_device_bytes_per_round": tc_cd["bytes_per_round"],
            "cross_device_buffer_kb": tc_cd["buffer_bytes"] / 1e3,
            "window_rounds": SCENARIO_HORIZON,
        }
        if verbose:
            print(f"  telemetry: {tc_pod['probes']} pod probes "
                  f"({tc_pod['bytes_per_round']:.0f} B/round, "
                  f"{tc_pod['buffer_bytes'] / 1e3:.1f} kB per "
                  f"{SCENARIO_HORIZON}-round window); "
                  f"{tc_cd['probes']} cross-device probes "
                  f"({tc_cd['bytes_per_round']:.0f} B/round)")
        if verbose:
            print(f"  participation: {p0['sample_k']}/{p0['enrolled']} "
                  f"sampled ({p0['sampling_rate']:.2%}) -> "
                  f"{p0['round_bytes'] / 1e9:.2f} GB/round vs "
                  f"{p0['round_bytes_full_participation'] / 1e9:.2f} "
                  f"full-participation "
                  f"({p0['wire_reduction']:.0f}x wire cut; a user is "
                  f"observed every "
                  f"~{p0['rounds_between_participations']:.0f} rounds)")
        # privacy column: the secagg wire's pad-material cost (the wire
        # bytes themselves are UNCHANGED — the OTP masks in place in the
        # wire format's integer ring) and the naive DP accountant over
        # the scenario horizon (launch/costing.privacy_cost)
        from repro.launch.costing import privacy_cost
        pv = {fmt: privacy_cost(cfg, fl_pods, SCENARIO_HORIZON, wire=fmt,
                                dp_sigma=1.0)
              for fmt in (None, "bf16", "int8")}
        pv0 = pv[None]
        gossip_info["privacy"] = {
            "directed_edges": pv0["directed_edges"],
            "pad_gbytes_per_round": {
                fmt or "fp32": c["pad_bytes"] / 1e9
                for fmt, c in pv.items()},
            "wire_overhead_bytes": pv0["wire_overhead_bytes"],
            "dp_epsilon_at_sigma": {
                f"{sig:g}": rf.dp_epsilon(sig, SCENARIO_HORIZON)
                for sig in (0.5, 1.0, 2.0)},
            "dp_delta": 1e-5,
            "rounds": SCENARIO_HORIZON,
        }
        if verbose:
            eps1 = gossip_info["privacy"]["dp_epsilon_at_sigma"]["1"]
            print(f"  privacy: secagg pads {pv0['pad_bytes'] / 1e9:.2f} "
                  f"GB/round fp32 over {pv0['directed_edges']} directed "
                  f"edges (wire overhead 0 B — in-place OTP); "
                  f"dp_sigma=1.0 -> eps={eps1:.1f} over "
                  f"{SCENARIO_HORIZON} rounds (naive composition, "
                  f"delta=1e-5)")
        if scenario:
            # scenario summary + cost delta: compile the named event
            # timeline over the pod workers and report how churn /
            # partitions move the per-round wire bytes vs the static run
            from repro.launch.costing import scenario_gossip_cost
            from repro.scenarios import compile_scenario, get_scenario
            spec = get_scenario(scenario, fl_pods)
            compiled_scn = compile_scenario(spec, fl_pods,
                                            SCENARIO_HORIZON)
            sc = scenario_gossip_cost(cfg, fl_pods, compiled_scn)
            gossip_info["scenario"] = {
                "summary": sc["summary"],
                "mean_edge_fraction": sc["mean_edge_fraction"],
                "wire_gbytes_per_round": sc["round_bytes_scenario"] / 1e9,
                "wire_gbytes_per_round_static": sc["round_bytes"] / 1e9,
                # the --fl transport's realized ring bytes (nnz row
                # selection): what a scenario-driven multi-pod run
                # actually permutes per gossip round
                "ppermute_ring_gbytes_per_round":
                    sc["ring_bytes_scenario"] / 1e9,
                "ppermute_ring_gbytes_per_round_static":
                    sc["ring_bytes"] / 1e9,
            }
            if verbose:
                print(f"  scenario {scenario}: mean edge fraction "
                      f"{sc['mean_edge_fraction']:.3f} -> "
                      f"{sc['round_bytes_scenario'] / 1e9:.2f} GB/round "
                      f"(static {sc['round_bytes'] / 1e9:.2f}); "
                      f"ppermute ring {sc['ring_bytes_scenario'] / 1e9:.2f}"
                      f" GB/round (nnz row selection)")

    mem = rf.compiled_bytes(compiled)
    # scan-aware correction: XLA counts while bodies once (see costing.py)
    from repro.launch.costing import corrected_cost, train_cost
    # FL steps are pod-independent: cost them on the single-pod submesh
    # (the 512-dev mesh with an unsharded pod axis makes GSPMD replicate).
    cost_mesh = make_production_mesh(multi_pod=False) if fl_pods else mesh
    with cost_mesh, logical_rules(cost_mesh, rules):
        if shape.mode == "train":
            flops_dev, bytes_dev, coll_dev = train_cost(
                cfg, shape, cost_mesh, rules, optimizer=opt_name,
                microbatches=microbatches, fl_pods=fl_pods,
                moe_strategy=moe_strategy)
        else:
            flops_dev, bytes_dev, coll_dev = corrected_cost(
                compiled, cfg, shape, mesh, rules, fl_pods=fl_pods,
                moe_strategy=moe_strategy if cfg.moe else "grouped")
    peak_bytes = mem["peak"]
    roof = rf.analyze(arch, shape_name, "multi" if multi_pod else "single",
                      chips, {"flops": flops_dev, "bytes accessed": bytes_dev},
                      "", rf.model_flops_estimate(cfg, shape),
                      peak_bytes, coll_override=coll_dev)
    result = {
        "arch": arch, "shape": shape_name,
        "mesh": "multi" if multi_pod else "single",
        "status": "ok", "chips": chips, "optimizer": opt_name,
        "variant": variant,
        "accum_dtype": accum_dtype,
        "params_b": cfg.param_count() / 1e9,
        "microbatches": microbatches,
        "moe_strategy": moe_strategy,
        "active_params_b": cfg.param_count(active_only=True) / 1e9,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory": {
            "temp_gb": mem["temp"] / 2**30,
            "arg_gb": mem["argument"] / 2**30,
            "out_gb": mem["output"] / 2**30,
            "alias_gb": mem["alias"] / 2**30,
            "peak_per_device_gb": peak_bytes / 2**30,
        },
        "roofline": roof.to_dict(),
        "gossip": gossip_info,
    }
    if verbose:
        print(f"[{arch} × {shape_name} × "
              f"{'multi' if multi_pod else 'single'}] "
              f"compile={t_compile:.0f}s "
              f"peak/dev={peak_bytes / 2**30:.2f}GiB "
              f"flops/dev={flops_dev / 1e12:.2f}T "
              f"bottleneck={roof.bottleneck} "
              f"(c={roof.t_compute*1e3:.1f}ms m={roof.t_memory*1e3:.1f}ms "
              f"x={roof.t_collective*1e3:.1f}ms)")
    return result


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="")
    ap.add_argument("--shape", default="")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--variant", default="baseline")
    ap.add_argument("--optimizer", default="")
    ap.add_argument("--accum-dtype", default="float32")
    ap.add_argument("--fedavg-baseline", action="store_true",
                    help="multi-pod without the FL pod axis (params "
                    "replicated across pods; grad AR crosses pods)")
    ap.add_argument("--scenario", default="",
                    help="attach a named scenario's summary + gossip cost "
                    "delta to multi-pod FL dry-runs (paper_noise[@K], "
                    "churn_signflip, storm)")
    ap.add_argument("--cd-enrolled", type=int, default=10_000,
                    help="cross-device participation column: enrolled "
                    "population size (multi-pod FL dry-runs)")
    ap.add_argument("--cd-sample-k", type=int, default=64,
                    help="cross-device participation column: per-round "
                    "cohort size")
    ap.add_argument("--shard-workers", type=int, default=8,
                    help="worker-sharding column: shard count for the "
                    "cross-shard HBM / ring-bytes contract (multi-pod "
                    "FL dry-runs)")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    pairs = []
    if args.all:
        for a in ARCH_IDS:
            if a == "paper-small":
                continue
            for s in SHAPES:
                pairs.append((a, s))
    else:
        pairs.append((args.arch, args.shape))

    failed = []
    for arch, shape in pairs:
        tag = f"{arch}_{shape}_{'multi' if args.multi_pod else 'single'}"
        if args.fedavg_baseline:
            tag += "_fedavg"
        if args.variant != "baseline":
            tag += f"_{args.variant}"
        out_path = os.path.join(args.out, tag + ".json")
        try:
            res = run_dryrun(arch, shape, multi_pod=args.multi_pod,
                             variant=args.variant,
                             optimizer=args.optimizer,
                             accum_dtype=args.accum_dtype,
                             fl=not args.fedavg_baseline,
                             scenario=args.scenario,
                             cd_enrolled=args.cd_enrolled,
                             cd_sample_k=args.cd_sample_k,
                             shard_workers=args.shard_workers)
        except Exception as e:  # record failures; they are bugs to fix
            traceback.print_exc()
            res = {"arch": arch, "shape": shape, "status": "FAILED",
                   "error": f"{type(e).__name__}: {e}"}
            failed.append(tag)
        with open(out_path, "w") as f:
            json.dump(res, f, indent=1)
    if failed:
        print(f"FAILED: {len(failed)} of {len(pairs)} pairs: "
              f"{', '.join(failed)}")
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
