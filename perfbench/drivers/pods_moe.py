"""Driver for the cross-silo pod trainer on a DeepSeek-V3-style MoE model
(``launch/train.py --fl --arch moonlight-16b-a3b``).

It is the ``pods`` driver (``pods.py``: the same objects, loop, set-up,
window and comparison) with four differences:

* the model's sizes come from the configuration's published keys
  (``hidden_size``, ``kv_lora_rank``, ``n_routed_experts`` ...: the
  Hugging Face names) instead of ``pods.MODEL_KEYS``;
* the train step is ``build_fl_train_step(..., with_stats=True)``: it also
  returns each pod's expert loads and dropped pairs, which the loop keeps
  on the device beside the losses and reads after the window (no host
  sync of its own); zero dropped pairs is one of the checks;
* the step is handed to ``repro.telemetry.trace.note_program`` under its
  name ``fl_step``, so the metrics can map its ops to the model's scopes
  (``mla``, ``moe_route``, ``moe_experts``, ``moe_shared``);
* as ``train.run_model`` does, each step starts the next step's batch
  on the ``TokenBatcher``'s background thread before it is dispatched,
  so the host makes it while the device computes (the ``pods`` driver
  makes each batch before its step, while the device waits).
"""
from __future__ import annotations

import dataclasses
import os
import sys
import time

import numpy as np

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import run as harness  # noqa: E402

# a private copy of the pods driver, whose ``model_config`` this one sets
pods = harness.load_module(os.path.join(BENCH, "drivers", "pods.py"),
                           "perfbench_driver_pods_for_moe")

STAGES = ("mla", "moe_route", "moe_experts", "moe_shared")


def model_config(config: dict):
    """The program's model config for ``config["arch"]`` with the sizes the
    configuration file states under their published names."""
    from repro.config import MLAConfig
    from repro.configs import get_config

    base = get_config(config["arch"])
    mla = MLAConfig(kv_lora_rank=config["kv_lora_rank"],
                    qk_nope_head_dim=config["qk_nope_head_dim"],
                    qk_rope_head_dim=config["qk_rope_head_dim"],
                    v_head_dim=config["v_head_dim"])
    moe = dataclasses.replace(
        base.moe, num_experts=config["n_routed_experts_published"],
        top_k=config["num_experts_per_tok"],
        num_shared_experts=config["n_shared_experts"],
        d_expert=config["moe_intermediate_size"],
        routed_scaling=config["routed_scaling_factor"],
        router_aux_weight=config["seq_aux_alpha"],
        bias_update_rate=config["bias_update_gamma"],
        num_held=config["n_routed_experts"], held_start=config["held_start"])
    return dataclasses.replace(
        base, num_layers=config["num_hidden_layers"],
        d_model=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=mla.qk_head_dim, d_ff=config["intermediate_size"],
        vocab_size=config["vocab_size"], rope_theta=config["rope_theta"],
        norm_eps=config["rms_norm_eps"],
        tie_embeddings=config["tie_word_embeddings"],
        first_dense=config["first_k_dense_replace"], mla=mla, moe=moe,
        dtype=config["dtype"])


pods.model_config = model_config
make_batcher = pods.batcher


def build(config: dict, traffic: dict, seed: int, devices):
    """``pods.build``'s objects, its train step replaced by one that also
    returns the MoE stats and first starts the next step's batch
    (``TokenBatcher.prefetch``, as ``run_model`` does); the stats of
    every step are kept in ``b["stats"]`` (device arrays)."""
    import jax

    from repro.launch.steps import build_fl_train_step
    from repro.telemetry import trace

    b = pods.build(config, traffic, seed, devices)
    prog = jax.jit(build_fl_train_step(
        b["cfg"], b["opt"], spmd_axis_name="pod" if b["mesh"] else None,
        with_stats=True), donate_argnums=(0, 1))
    kept = b["stats"] = []
    feed = make_batcher(config, traffic, seed, b["cfg"].vocab_size)
    # ``pods.drive`` asks this module copy for its batcher: this run's
    pods.batcher = lambda *a: feed

    def fl_step(params, opt_state, step, batch):
        if not kept:
            trace.note_program("fl_step", prog,
                               (params, opt_state, step, batch),
                               stages=STAGES)
        feed.prefetch(len(kept) + 1)
        *out, stats = prog(params, opt_state, step, batch)
        kept.append(stats)
        return tuple(out)

    b["fl_step"] = fl_step
    return b


def expert_counts(stats: list) -> dict:
    """The kept stats on the host: ``loads`` [steps, pods, layers, E] and
    ``dropped`` [steps] (all pods and layers)."""
    import jax

    loads, dropped = [], []
    for st in stats:
        layers = jax.tree.leaves(st, is_leaf=lambda x: isinstance(x, dict)
                                 and "load" in x)
        # per MoE group: [pods, E] (one layer) or [pods, repeats, E]
        loads.append(np.concatenate(
            [np.asarray(s["load"]).reshape(s["load"].shape[0], -1,
                                           s["load"].shape[-1])
             for s in layers], axis=1))
        dropped.append(sum(int(np.asarray(s["dropped"]).sum())
                           for s in layers))
    return {"loads": np.stack(loads), "dropped": np.asarray(dropped)}


def first_steps(config: dict, traffic: dict, seed: int, devices) -> dict:
    """The set-up steps alone (what ``run`` hands the comparison)."""
    b = build(config, traffic, seed, devices)
    got = pods.drive(b, config, traffic, seed, 0.0, None)
    got["counts"] = expert_counts(b["stats"])
    return got


def run(config: dict, traffic: dict, *, seed: int, seconds: float, window,
        devices) -> dict:
    """One run: set-up, the window, then the comparison with the plain
    reference."""
    b = build(config, traffic, seed, devices)
    got = pods.drive(b, config, traffic, seed, seconds, window)
    counts = expert_counts(b["stats"])
    del b
    t_ref = time.perf_counter()
    want = reference(config, traffic, seed, got)
    print(f"[perfbench] reference {time.perf_counter() - t_ref:.1f} s",
          file=sys.stderr)
    tokens_step = config["global_batch"] * config["seq"]
    start, held = config["held_start"], config["n_routed_experts"]
    window_loads = counts["loads"][len(counts["loads"]) - got["steps"]:]
    return {
        "attempted": got["steps"],
        "failed": got["nonfinite"],
        "e2e": {"tokens_per_s": got["steps"] * tokens_step / window.seconds,
                "peak_hbm_gb": window.peak_bytes / 1e9},
        "checks": compare(config, got, want) + [
            ("dropped_pairs", int(counts["dropped"].sum()), 0)],
        "ctx": {"steps": got["steps"], "gossip_rounds": got["gossip_rounds"],
                "tokens_per_step": tokens_step,
                "n_params": config["active_param_count"],
                "held_loads": window_loads[..., start:start + held],
                "expert_shape": (config["hidden_size"],
                                 config["moe_intermediate_size"],
                                 config["pods"] * held)},
    }


compare = pods.compare
control_as_got = pods.control_as_got
reference = pods.reference
