"""Driver for the cross-silo pod trainer (``launch/train.py --fl``).

It builds the objects ``train.run_model`` builds for ``--fl`` (the
vmapped train step ``steps.build_fl_train_step``, the gossip round
``steps.build_pod_gossip_step`` over ``engine.build_pod_round``, the
stacked parameters, optimizer and pod state) and drives them with
``run_model``'s loop: each step's batch made on the host by the program's
``TokenBatcher`` and fed, one train step, a gossip round every
``gossip_every`` steps, the losses read back on the host after each step.
The weights and the batches come from ``--seed`` (``run_model`` fixes its
weight key), and the same objects serve set-up, the comparison's steps and
the window.

Set-up runs the first two gossip cycles (``2 · gossip_every`` steps, each
cycle ending with its gossip round, and at least the compared steps), so
every program variant is compiled (or loaded from the cache); the first
three steps or more, up to a gossip round, are the ones the reference
follows (through a second cycle, the later steps' noise drowns the
faults the comparison has to catch). The window runs from there to the
first gossip step past ``--seconds``, so it holds whole gossip cycles; it
includes making and feeding each step's batch.
"""
from __future__ import annotations

import sys
import time

import numpy as np


MODEL_KEYS = ("num_layers", "d_model", "num_heads", "num_kv_heads",
              "head_dim", "d_ff", "vocab_size", "rope_theta", "norm_eps",
              "qk_norm", "tie_embeddings", "dtype")


def model_config(config: dict):
    """The program's model config for ``config["arch"]``, with the sizes the
    configuration file states (which the reference reads too)."""
    import dataclasses

    from repro.configs import get_config

    return dataclasses.replace(get_config(config["arch"]), **{
        k: config["model"][k] for k in MODEL_KEYS})


def build(config: dict, traffic: dict, seed: int, devices):
    import jax
    import jax.numpy as jnp

    import generate
    from repro.config import DeFTAConfig
    from repro.core.engine import init_pod_state, sketch_shape
    from repro.core.gossip import uses_error_feedback
    from repro.core.topology import make_topology
    from repro.launch.mesh import make_pod_mesh
    from repro.launch.sharding_rules import base_rules
    from repro.launch.steps import (build_fl_train_step,
                                    build_pod_gossip_step)
    from repro.models import model as model_mod
    from repro.optim import make_optimizer
    from repro.sharding import WorkerShards

    cfg = model_config(config)
    pods = config["pods"]
    opt = make_optimizer(config["optimizer"], config["learning_rate"])
    mesh = make_pod_mesh(pods) if (config["transport"] == "ppermute"
                                   or 1 < pods <= len(devices)) else None
    key = jax.random.PRNGKey(generate.prng_seed(seed))

    def on_pods(fn, *a):
        if mesh is None:
            return jax.jit(fn)(*a)
        ws = WorkerShards(mesh, axis="pod")
        out = jax.tree.map(
            lambda s: ws.row_sharding(len(s.shape))
            if s.shape and s.shape[0] == pods else ws.replicated(),
            jax.eval_shape(fn, *a))
        return jax.jit(fn, out_shardings=out)(*a)

    def init(k):
        p = model_mod.init_params(k, cfg)
        stack = lambda t: jax.tree.map(lambda x: jnp.stack([x] * pods), t)
        return stack(p), stack(opt.init(p))

    params, opt_state = on_pods(init, key)
    dcfg = DeFTAConfig(
        num_workers=pods, avg_peers=pods - 1,
        num_sampled=min(2, pods - 1), topology="dense",
        aggregation="defta", use_dts=config["pod_dts"], dts_signal="loss",
        time_machine=False, gossip_dtype=config["gossip_wire"],
        gossip_error_feedback=config["gossip_error_feedback"],
        gossip_wire_round="nearest")
    adj = make_topology("dense", pods, pods - 1)
    sizes = np.full(pods, config["global_batch"])
    rules = {}
    if mesh is not None:
        rules = {**base_rules(multi_pod=True), "batch": ("data",)}
    fl_step = jax.jit(build_fl_train_step(
        cfg, opt, spmd_axis_name="pod" if mesh else None),
        donate_argnums=(0, 1))
    gossip_rnd, pod_tr = build_pod_gossip_step(
        cfg, dcfg, pods, sizes, adjacency=adj,
        transport=config["transport"], mesh=mesh)
    gossip = jax.jit(gossip_rnd, donate_argnums=(0, 1))
    pstate = on_pods(lambda p, k: init_pod_state(
        jax.random.fold_in(k, config["pod_key_tag"]), pods, p,
        wire_error=uses_error_feedback(dcfg), sketch=sketch_shape(dcfg)),
        params, key)
    return {"cfg": cfg, "opt": opt, "mesh": mesh, "rules": rules,
            "init": lambda: on_pods(init, key)[0],
            "params": params, "opt_state": opt_state, "pstate": pstate,
            "fl_step": fl_step, "gossip": gossip, "pods": pods,
            "key": key, "transport": pod_tr}


def batcher(config: dict, traffic: dict, seed: int, vocab: int):
    """The program's ``TokenBatcher`` for the run (what ``run_model``
    feeds), checked against the traffic mix it has to draw."""
    from repro.data.loader import TokenBatcher

    if min(vocab, traffic["markov_vocab"]) != min(vocab, 512):
        raise ValueError("TokenBatcher draws from the first 512 token ids; "
                         f"the traffic asks for {traffic['markov_vocab']}")
    return TokenBatcher(vocab, config["seq"], config["global_batch"],
                        seed=seed)


def grad_norms_fn(opt_state):
    """Per-leaf ‖g‖ of the first step, per pod, from adafactor's state after
    that step (its decay is 0 at step 1, so the factored row means hold
    mean(g² + eps) exactly): [pods] per leaf, as a flat list."""
    import jax
    import jax.numpy as jnp

    def one(s):
        if "vr" in s:
            ncol = s["vc"].shape[-1]
            tot = jnp.sum(s["vr"], axis=tuple(range(1, s["vr"].ndim))) * ncol
        else:
            tot = jnp.sum(s["v"], axis=tuple(range(1, s["v"].ndim)))
        return jnp.sqrt(tot)

    return [one(s) for s in jax.tree.leaves(
        opt_state["f"], is_leaf=lambda s: isinstance(s, dict) and (
            "vr" in s or "v" in s))]


def change_norms(params, start):
    """Per-leaf ‖params − start‖ per pod (both stacked)."""
    import jax
    import jax.numpy as jnp

    def fn(params, start):
        return [jnp.sqrt(jnp.sum(jnp.square(
            p.astype(jnp.float32) - q.astype(jnp.float32)),
            axis=tuple(range(1, p.ndim))))
            for p, q in zip(jax.tree.leaves(params), jax.tree.leaves(start))]
    return [np.asarray(c) for c in jax.jit(fn)(params, start)]


def compared_steps(traffic: dict) -> int:
    """The steps the reference follows: at least three, ending with a
    gossip round."""
    every = traffic["gossip_every"]
    return every * -(-3 // every)


def drive(b: dict, config: dict, traffic: dict, seed: int, seconds: float,
          window) -> dict:
    """``run_model``'s loop over the built objects: set-up steps, then the
    window. Returns what the comparison reads and the window's counts;
    with ``window=None`` it returns once the compared steps are done."""
    import jax
    import jax.numpy as jnp

    every = traffic["gossip_every"]
    n_cmp = compared_steps(traffic)
    # the second cycle's steps and gossip round compile again (their
    # inputs are the first's outputs, typed apart from the fresh state),
    # so the window opens after it, and after the compared steps
    n_setup = max(2 * every, n_cmp)
    feed = batcher(config, traffic, seed, b["cfg"].vocab_size)
    pods, rows = b["pods"], config["global_batch"] // b["pods"]
    fl_step, gossip = b["fl_step"], b["gossip"]
    params, opt_state, pstate = b["params"], b["opt_state"], b["pstate"]
    del b["params"], b["opt_state"], b["pstate"]
    step = jnp.zeros((), jnp.int32)
    out = {"losses": [], "grad": None, "change": None, "steps": 0,
           "gossip_rounds": 0, "nonfinite": 0}
    i = 0
    span = window.span if window is not None else (lambda name: _Null())
    mark = window.mark if window is not None else (lambda name: None)
    mark("build")
    with _mesh_ctx(b):
        while True:
            with span("batch"):
                hb = feed.batch_at(i)
                batch = {k: jnp.asarray(v).reshape(pods, rows, -1)
                         for k, v in hb.items()}
            params, opt_state, step, losses = fl_step(params, opt_state,
                                                      step, batch)
            if i == 0:
                np.asarray(losses)
                mark("first step")
                out["grad"] = [np.asarray(g) for g in
                               jax.jit(grad_norms_fn)(opt_state)]
                mark("gradient norms")
            gossiped = (i + 1) % every == 0
            if gossiped:
                pstate, params = gossip(pstate, params, losses, None)
            with span("losses"):
                host_losses = np.asarray(losses, np.float64)
            if gossiped and i < n_setup:
                mark(f"steps to {i + 1} and gossip round")
            i += 1
            if i <= n_cmp:
                out["losses"].append(host_losses)
                if i == n_cmp:
                    out["change"] = change_norms(params, b["init"]())
                    mark("change norms")
                    if window is None:
                        return out
            if i <= n_setup:
                if i == n_setup:
                    window.open()
                continue
            out["steps"] += 1
            out["gossip_rounds"] += int(gossiped)
            out["nonfinite"] += int(not np.isfinite(host_losses).all())
            if gossiped and window.elapsed() >= seconds:
                window.close()
                return out


def first_steps(config: dict, traffic: dict, seed: int, devices) -> dict:
    """The set-up steps alone (what ``run`` hands the comparison)."""
    b = build(config, traffic, seed, devices)
    return drive(b, config, traffic, seed, 0.0, None)


def run(config: dict, traffic: dict, *, seed: int, seconds: float, window,
        devices) -> dict:
    """One run: set-up, the window, then the comparison with the plain
    reference."""
    b = build(config, traffic, seed, devices)
    got = drive(b, config, traffic, seed, seconds, window)
    del b
    t_ref = time.perf_counter()
    want = reference(config, traffic, seed, got)
    print(f"[perfbench] reference {time.perf_counter() - t_ref:.1f} s",
          file=sys.stderr)
    tokens_step = config["global_batch"] * config["seq"]
    return {
        "attempted": got["steps"],
        "failed": got["nonfinite"],
        "e2e": {"tokens_per_s": got["steps"] * tokens_step / window.seconds,
                "peak_hbm_gb": window.peak_bytes / 1e9},
        "checks": compare(config, got, want),
        "ctx": {"steps": got["steps"], "gossip_rounds": got["gossip_rounds"],
                "tokens_per_step": tokens_step,
                "n_params": config["param_count"]},
    }


def compare(config: dict, got: dict, want: dict):
    import check

    return check.pod_checks(got, want, config["limits"])


def control_as_got(got: dict, ctl: dict) -> dict:
    return {"losses": list(ctl["losses"]), "grad": list(ctl["grad"]),
            "change": list(ctl["change"])}


class _Null:
    def __enter__(self):
        return None

    def __exit__(self, *a):
        return False


def _mesh_ctx(b):
    if b["mesh"] is None:
        return _Null()
    import contextlib

    from repro.sharding import logical_rules

    stack = contextlib.ExitStack()
    stack.enter_context(b["mesh"])
    stack.enter_context(logical_rules(b["mesh"], b["rules"]))
    return stack


def reference(config: dict, traffic: dict, seed: int, got: dict,
              dtype: str = "float32", fault: str = ""):
    import importlib

    ref = importlib.import_module("reference." + config["reference"])
    return ref.run_steps(config, traffic, seed,
                         steps=compared_steps(traffic), dtype=dtype,
                         fault=fault)
