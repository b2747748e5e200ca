"""Chip smoke: DeFTA's main path, once, on a TPU, through the entry points
a user calls, with every result checked.

    python chip_smoke.py                # one chip: phases A and B
    python chip_smoke.py --four-chips   # four chips: the paths that exist
                                        # only across chips, each against
                                        # its one-mesh reference

Phase A, the engine at the paper's largest world (Table 2): 20 vanilla
workers plus the ``paper_noise`` attacker, the paper's CNN on 28x28
non-iid images, through ``core.defta.run_defta`` with the sparse backend
on the int8 + EF21 wire and DTS on, so both padded-CSR Pallas kernels run
compiled. 8 epochs with an eval every 4 must take exactly 2 dispatches.

Phase B, one model at full width: ``launch/train.py``'s ``--fl`` path on
qwen3-0.6b, 2 pods on one chip, adafactor, int8 wire, pod DTS, 4 steps
gossiping every 2.

``--four-chips`` runs instead (i) ``run_defta(shards=4)`` at W=10,000
against ``shards=None`` on one chip of the same process, and (ii) phase
B's flags with ``--pods 4 --transport ppermute`` over a mesh of the four
chips against ``--transport in_jit``.

One process holds the chip for the whole run. Each phase prints its
numbers on lines of their own; a failed check exits 1. The last line of
stdout is the JSON verdict, printed only when every phase passed.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

NUM_CLASSES = 10


def log(tag: str, **kv):
    print(f"[{tag}] " + " ".join(f"{k}={v}" for k, v in kv.items()),
          flush=True)


def memory_stats() -> dict:
    """Device 0's allocator statistics (empty where not reported)."""
    import jax

    return jax.devices()[0].memory_stats() or {}


def peak_bytes():
    """Device 0's peak bytes in use since the process started: a phase's
    own peak only when no earlier phase went higher."""
    return memory_stats().get("peak_bytes_in_use")


def require_tpu(count: int):
    """The chip or nothing: exits before any phase runs unless JAX's
    first device is a TPU and ``count`` of them are present."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX found no TPU (platform "
                         f"{devs[0].platform!r}); nothing was run")
    if len(devs) < count:
        raise SystemExit(f"chip_smoke: needs {count} TPU chips, JAX "
                         f"found {len(devs)}; nothing was run")
    return devs


def max_err(a, b) -> float:
    import jax
    import jax.numpy as jnp

    return max(float(jnp.max(jnp.abs(x.astype(jnp.float32)
                                     - y.astype(jnp.float32))))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


# ---------------------------------------------------------------------------
# Phase A: the DeFTA engine
# ---------------------------------------------------------------------------

def phase_defta(*, workers: int = 20, hw: int = 28, width: int = 16,
                n_per_worker: int = 200, alpha: float = 0.5,
                local_epochs: int = 10, epochs: int = 8, eval_every: int = 4,
                seed: int = 0):
    """``run_defta`` on the paper's CNN; returns the list of failed
    checks (empty when the phase passed)."""
    import jax
    import numpy as np

    from repro.config import DeFTAConfig, TrainConfig
    from repro.core.defta import evaluate, run_defta
    from repro.core.tasks import cnn_task
    from repro.data.synthetic import federated_dataset
    from repro.telemetry import RunLedger

    cfg = DeFTAConfig(num_workers=workers, avg_peers=4, num_sampled=2,
                      local_epochs=local_epochs, gossip_dtype="int8",
                      gossip_error_feedback=True, use_dts=True, seed=seed)
    train = TrainConfig(learning_rate=0.05, batch_size=32)
    data = federated_dataset("image", workers, np.random.default_rng(seed),
                             n_per_worker=n_per_worker, alpha=alpha,
                             hw=hw, num_classes=NUM_CLASSES)
    task = cnn_task(hw, 1, NUM_CLASSES, width=width)
    ledger = RunLedger()
    t0 = time.perf_counter()
    st, _, mal, hist = run_defta(
        jax.random.PRNGKey(seed), task, cfg, train, data, epochs=epochs,
        scenario="paper_noise", gossip_backend="sparse",
        eval_every=eval_every, test_x=data["test_x"],
        test_y=data["test_y"], ledger=ledger)
    jax.block_until_ready(st)
    wall = time.perf_counter() - t0
    acc, acc_std, _ = evaluate(task, st, data["test_x"], data["test_y"],
                               mal)
    losses = np.asarray(st.last_loss)[~mal]
    budget = -(-epochs // eval_every)
    sup = ledger.superstep_s
    log("A", workers=f"{workers}+{int(mal.sum())}", epochs=epochs,
        dispatches=ledger.dispatches, budget=budget)
    log("A", run_s=f"{wall:.3f}", dispatch_s=[round(s, 4) for s in sup])
    if len(sup) >= 2:
        log("A", compile_s_est=f"{sup[0] - sup[-1]:.3f}",
            round_s=f"{sup[-1] / min(eval_every, epochs):.5f}")
    log("A", process_peak_bytes_in_use=peak_bytes())
    log("A", eval=[(e, round(m, 4), round(s, 4)) for e, m, s in hist])
    log("A", final_acc=f"{acc:.4f}", acc_std=f"{acc_std:.4f}",
        chance=1.0 / NUM_CLASSES,
        vanilla_loss_mean=f"{float(losses.mean()):.4f}")
    fails = []
    if ledger.dispatches != budget:
        fails.append(f"A: {ledger.dispatches} dispatches, budget {budget}")
    if not np.isfinite(losses).all():
        fails.append("A: non-finite vanilla loss")
    if not acc > 1.0 / NUM_CLASSES:
        fails.append(f"A: accuracy {acc:.4f} at or below chance")
    return fails


# ---------------------------------------------------------------------------
# Phase B: one model at full width through launch/train.py
# ---------------------------------------------------------------------------

FL_ARGS = ["--fl", "--arch", "qwen3-0.6b", "--optimizer", "adafactor",
           "--gossip-wire", "int8", "--pod-dts", "--steps", "4",
           "--gossip-every", "2", "--batch", "8", "--seq", "128"]


def run_train(argv, phase: str):
    """``train.run_model`` on ``argv``; logs its numbers, returns
    (result, failed checks). With ``--memory-report`` in ``argv`` each
    step program's compiled peak must fit the device's memory limit."""
    import numpy as np

    from repro.launch import train

    res = train.run_model(train.parse_args(argv))
    losses, step_s = res["losses"], res["step_s"]
    log(phase, argv=" ".join(argv))
    log(phase, step_s=[round(s, 4) for s in step_s])
    if len(step_s) >= 4:
        # step 0 compiles the train step, step 1 the gossip round
        log(phase, train_compile_s_est=f"{step_s[0] - step_s[2]:.3f}",
            gossip_compile_s_est=f"{step_s[1] - step_s[3]:.3f}")
    limit = memory_stats().get("bytes_limit")
    for name, mem in res["memory"].items():
        log(phase, program=name, bytes_limit=limit, **mem)
    log(phase, process_peak_bytes_in_use=peak_bytes())
    log(phase, losses=np.round(losses, 5).tolist())
    fails = []
    if not np.isfinite(losses).all():
        fails.append(f"{phase}: non-finite loss")
    for name, mem in res["memory"].items():
        if limit and mem["peak"] > limit:
            fails.append(f"{phase}: {name} needs {mem['peak']} B, the "
                         f"device holds {limit}")
    return res, fails


def phase_train(extra=()):
    return run_train(FL_ARGS + ["--pods", "2", "--memory-report"]
                     + list(extra), "B")[1]


# ---------------------------------------------------------------------------
# --four-chips
# ---------------------------------------------------------------------------

def phase_shards(*, workers: int = 10_000, shards: int = 4,
                 epochs: int = 2, seed: int = 0):
    """The sharded engine's 10k-worker world (tests/test_shard_engine.py),
    sparse + int8: ``shards=4`` against ``shards=None`` in this process,
    both at the platform's default matmul precision; states agree to the
    int8 wire's sharded-parity bound and each run keeps the dispatch
    budget."""
    import jax
    import numpy as np

    from repro.config import DeFTAConfig, TrainConfig
    from repro.core.defta import run_defta
    from repro.core.gossip import SHARD_PARITY_TOL
    from repro.core.tasks import mlp_task
    from repro.data.synthetic import federated_dataset
    from repro.telemetry import RunLedger

    cfg = DeFTAConfig(num_workers=workers, avg_peers=4, num_sampled=2,
                      local_epochs=1, gossip_dtype="int8", seed=seed)
    train = TrainConfig(learning_rate=0.05, batch_size=8)
    data = federated_dataset("vector", workers, np.random.default_rng(seed),
                             n_per_worker=8, alpha=0.5)
    task = mlp_task(32, NUM_CLASSES)
    tol = SHARD_PARITY_TOL["int8"]
    out = {}
    for name, n in (("one", None), ("sharded", shards)):
        led = RunLedger()
        t0 = time.perf_counter()
        st, *_ = run_defta(jax.random.PRNGKey(seed), task, cfg, train,
                           data, epochs=epochs, eval_every=epochs,
                           gossip_backend="sparse", ledger=led, shards=n)
        jax.block_until_ready(st)
        out[name] = (st, led)
        log("S", run=name, shards=n or 1, run_s=f"{time.perf_counter() - t0:.3f}",
            dispatches=led.dispatches,
            dispatch_s=[round(s, 4) for s in led.superstep_s])
    (s1, l1), (s4, l4) = out["one"], out["sharded"]
    errs = {"params": max_err(s1.params, s4.params),
            "backup": max_err(s1.backup, s4.backup),
            "conf": max_err(s1.conf, s4.conf),
            "best_loss": max_err(s1.best_loss, s4.best_loss)}
    log("S", **{f"err_{k}": f"{v:.3e}" for k, v in errs.items()}, tol=tol)
    log("S", process_peak_bytes_in_use=peak_bytes())
    fails = [f"S: {k} differs by {v:.3e} > {tol}"
             for k, v in errs.items() if not v <= tol]
    if not (np.asarray(s1.epoch) == np.asarray(s4.epoch)).all():
        fails.append("S: per-worker epochs differ")
    for name, led in (("one", l1), ("sharded", l4)):
        if led.dispatches != 1:
            fails.append(f"S: {name} took {led.dispatches} dispatches, "
                         f"budget 1")
    return fails


def phase_pod_mesh(pods: int = 4, extra=()):
    """Phase B's flags on ``pods`` pods over a mesh of the chips: the
    ppermute ring against the in-jit transport. Parameters agree within
    one int8 wire step (max|leaf| / 127) of each leaf."""
    import jax
    import jax.numpy as jnp

    base = FL_ARGS + ["--pods", str(pods)] + list(extra)
    ring, f1 = run_train(base + ["--transport", "ppermute"], "M-ppermute")
    ref, f2 = run_train(base + ["--transport", "in_jit"], "M-in_jit")
    worst = 0.0
    for a, b in zip(jax.tree.leaves(ring["params"]),
                    jax.tree.leaves(ref["params"])):
        a32, b32 = a.astype(jnp.float32), b.astype(jnp.float32)
        step = float(jnp.max(jnp.abs(b32))) / 127.0
        err = float(jnp.max(jnp.abs(a32 - b32)))
        worst = max(worst, err / step if step else (0.0 if not err
                                                    else math.inf))
    log("M", worst_err_in_int8_steps=f"{worst:.4f}", tol=1.0)
    fails = f1 + f2
    if not worst <= 1.0:
        fails.append(f"M: ppermute and in_jit params differ by {worst:.4f} "
                     f"int8 steps")
    return fails


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run the four-chip paths (sharded worker axis, "
                         "pod mesh) and their references, nothing else")
    args = ap.parse_args(argv)
    count = 4 if args.four_chips else 1
    devs = require_tpu(count)

    from repro.cache import enable_compile_cache
    log("setup", platform=devs[0].platform, kind=devs[0].device_kind,
        devices=len(devs), compile_cache=enable_compile_cache())
    phases = [phase_shards, phase_pod_mesh] if args.four_chips \
        else [phase_defta, phase_train]
    fails = []
    for phase in phases:
        t0 = time.perf_counter()
        fails += phase()
        log("time", phase=phase.__name__,
            wall_s=f"{time.perf_counter() - t0:.3f}")
    if fails:
        for f in fails:
            print("FAIL:", f, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": count}}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
