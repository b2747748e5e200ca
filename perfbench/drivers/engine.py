"""Driver for the DeFTA simulation engine (``core.defta``/``core.engine``).

The round is built with the program's own ``make_topology``,
``init_state`` and ``build_round_fn`` (telemetry off) and driven by
``engine.drive_epochs``, the chunked ``lax.scan`` superstep that
``run_defta`` uses. The harness's ``eval_fn`` wraps ``core.defta.evaluate``
at every eval boundary, as a user's run does, and ends the call at the
first boundary past ``--seconds``.

Set-up is everything up to the end of the second boundary's evaluation:
data, state, the first two chunks (each compiles, or loads its program
from the cache: the second chunk's inputs are typed apart from the fresh
state's) and their evaluations. The window runs from there to the
boundary that ends it, evaluations included. The first chunk's rounds are
the ones the plain reference follows.
"""
from __future__ import annotations

import sys
import time

import numpy as np


class _WindowClosed(Exception):
    def __init__(self, state):
        self.state = state


def build(config: dict, traffic: dict, seed: int):
    """The program's objects for one run: task, cfg, round body, state,
    device data, host data and the attacker mask."""
    import jax
    import jax.numpy as jnp

    from repro.config import DeFTAConfig, TrainConfig
    from repro.core.defta import (_pad_workers, build_round_fn,
                                  resolve_scenario)
    from repro.core.engine import init_state, sketch_shape
    from repro.core.gossip import uses_error_feedback
    from repro.core.tasks import cnn_task
    from repro.core.topology import make_topology

    import generate

    t = config["task"]
    task = cnn_task(t["hw"], t["channels"], t["num_classes"],
                    width=t["width"])
    cfg = DeFTAConfig(num_workers=config["workers"],
                      avg_peers=config["avg_peers"],
                      num_sampled=config["num_sampled"],
                      topology=config["topology"],
                      local_epochs=config["local_epochs"],
                      gossip_dtype=config["gossip_dtype"],
                      gossip_error_feedback=config["gossip_error_feedback"],
                      use_dts=config["use_dts"],
                      seed=config["topology_seed"])
    train = TrainConfig(learning_rate=config["learning_rate"],
                        batch_size=config["batch_size"])
    data = generate.federated_images(traffic, cfg.num_workers, t, seed,
                                     config["test_size"])
    scenario = resolve_scenario(config["scenario"], cfg,
                                traffic["horizon_rounds"])
    w = scenario.num_workers
    malicious = scenario.malicious.copy()
    adj = make_topology(cfg.topology, w, cfg.avg_peers, cfg.seed)
    data, sizes = _pad_workers(data, data["sizes"], w - cfg.num_workers)
    state = init_state(jax.random.PRNGKey(generate.prng_seed(seed)), task, w,
                       wire_error=uses_error_feedback(cfg),
                       sketch=sketch_shape(cfg))
    rnd_fn = build_round_fn(task, cfg, train, adj, sizes, malicious,
                            gossip_backend=config["gossip_backend"],
                            scenario=scenario,
                            num_classes=t["num_classes"])
    jdata = {k: jnp.asarray(data[k]) for k in ("x", "y", "mask")}
    return {"task": task, "cfg": cfg, "rnd_fn": rnd_fn, "state": state,
            "jdata": jdata, "data": data, "malicious": malicious,
            "adj": adj, "sizes": sizes}


def snapshot(state, malicious):
    """What the comparison reads from a state, on the host."""
    import jax

    return {"params": jax.tree.map(np.asarray, state.params),
            "last_loss": np.asarray(state.last_loss),
            "best_loss": np.asarray(state.best_loss),
            "conf": np.asarray(state.conf),
            "epoch": np.asarray(state.epoch)}


def first_steps(config: dict, traffic: dict, seed: int, devices) -> dict:
    """The timed path's first chunk alone (what ``run`` hands the
    comparison), for the seed sweeps."""
    import jax

    from repro.core.engine import drive_epochs

    b = build(config, traffic, seed)
    p0 = jax.tree.map(np.asarray, b["state"].params)

    def eval_fn(st, done):
        raise _WindowClosed(snapshot(st, b["malicious"]))

    try:
        drive_epochs(b["rnd_fn"], b["state"], b["jdata"],
                     traffic["horizon_rounds"],
                     eval_every=traffic["eval_every"], eval_fn=eval_fn)
    except _WindowClosed as closed:
        return {"p0": p0, "state": closed.state,
                "malicious": b["malicious"], "data": b["data"]}
    raise RuntimeError("the first chunk never reached its boundary")


def reference(config: dict, traffic: dict, seed: int, got: dict,
              dtype: str = "float32", fault: str = ""):
    import importlib

    ref = importlib.import_module("reference." + config["reference"])
    return ref.run_rounds(config, traffic, seed,
                          rounds=traffic["eval_every"], data=got["data"],
                          dtype=dtype, fault=fault)


def compare(config: dict, got: dict, want: dict):
    import check

    return check.engine_checks(got["p0"], got["state"], want,
                               got["malicious"], config["limits"])


def control_as_got(got: dict, ctl: dict) -> dict:
    return dict(got, p0=ctl["params0"], state=ctl)


def run(config: dict, traffic: dict, *, seed: int, seconds: float, window,
        devices) -> dict:
    """One run: set-up, the window, then the comparison with the plain
    reference."""
    import jax

    from repro.core.defta import evaluate
    from repro.core.engine import drive_epochs

    import yardstick

    b = build(config, traffic, seed)
    window.mark("build")
    task, mal = b["task"], b["malicious"]
    every = traffic["eval_every"]
    p0 = jax.tree.map(np.asarray, b["state"].params)
    seen = {"boundaries": [], "first": None, "nonfinite": 0}

    def eval_fn(st, done):
        with window.span("eval"):
            acc, _, _ = evaluate(task, st, b["data"]["test_x"],
                                 b["data"]["test_y"], mal)
        if seen["first"] is None:
            seen["first"] = snapshot(st, mal)
            window.mark("first chunk and eval")
            return done, acc
        if window.t_open is None:
            # the second chunk compiles again (its inputs are the first
            # chunk's outputs, typed apart from the fresh state), so the
            # window opens only after it
            window.open()
            seen["boundaries"].append(done)
            return done, acc
        seen["boundaries"].append(done)
        with window.span("check"):
            if not np.isfinite(np.asarray(st.last_loss)[~mal]).all():
                seen["nonfinite"] += every
        if window.elapsed() >= seconds:
            window.close()
            raise _WindowClosed(st)
        return done, acc

    try:
        drive_epochs(b["rnd_fn"], b["state"], b["jdata"],
                     traffic["horizon_rounds"], eval_every=every,
                     eval_fn=eval_fn)
        raise RuntimeError(f"the run reached its horizon of "
                           f"{traffic['horizon_rounds']} rounds before "
                           f"{seconds} s had passed")
    except _WindowClosed as closed:
        final = closed.state
    rounds = seen["boundaries"][-1] - seen["boundaries"][0]
    del final, b["state"], b["jdata"]

    t_ref = time.perf_counter()
    got = {"p0": p0, "state": seen["first"], "malicious": mal,
           "data": b["data"]}
    want = reference(config, traffic, seed, got)
    print(f"[perfbench] reference {time.perf_counter() - t_ref:.1f} s",
          file=sys.stderr)
    checks = compare(config, got, want)
    t = config["task"]
    flops_round = yardstick.engine_round_train_flops(
        workers=int((~mal).sum()) + int(mal.sum()),
        local_epochs=config["local_epochs"],
        steps_per_epoch=b["data"]["x"].shape[1] // config["batch_size"],
        batch=config["batch_size"],
        image_flops=yardstick.cnn_forward_flops(
            t["hw"], t["channels"], t["width"], t["num_classes"]))
    return {
        "attempted": rounds,
        "failed": seen["nonfinite"],
        "e2e": {"rounds_per_s": rounds / window.seconds,
                "peak_hbm_gb": window.peak_bytes / 1e9},
        "checks": checks,
        "ctx": {"rounds": rounds, "round_train_flops": flops_round,
                "workers": len(mal), "leaf_features": [
                    int(np.prod(v.shape[1:])) for v in jax.tree.leaves(p0)],
                "gossip": {"wire": config["gossip_dtype"],
                           "adjacency": b["adj"]}},
    }
