"""The pod cell's control (the plain reference with float8 matmul
operands) is refused by the comparison with the cell's limits, a run with
the timed path broken underneath comes out not correct, once for each
fault the cell can have, at its gossip cadence and at a round after every
step, and four pods on a mesh of four devices agree with the reference
(small cuts of the cell on the CPU)."""
import json
import os
import subprocess
import sys
import time

import jax
import pytest

import control
import run as harness
from cells import GOSSIP_EVERY, pods_cell


@pytest.mark.parametrize("every", GOSSIP_EVERY)
def test_pods_control_is_refused_and_program_passes(every):
    r = control.readings(pods_cell(every), 2**31 + 13, program=True,
                         devices=jax.devices())
    assert all(v <= lim for _, v, lim in r["program"]), r["program"]
    assert any(v > lim for _, v, lim in r["control"]), r["control"]


def plant(monkeypatch, fault):
    from repro.launch import steps

    if fault == "unchanged":            # a step that returns its state
        orig = steps.build_fl_train_step

        def build(*a, **kw):
            inner = orig(*a, **kw)

            def fl_step(params, opt, step, batch):
                _, _, step2, losses = inner(params, opt, step, batch)
                return params, opt, step2, losses
            return fl_step
        monkeypatch.setattr(steps, "build_fl_train_step", build)
    if fault == "half_batch":           # each pod's mean over half its rows
        orig = steps.build_fl_train_step

        def build(*a, **kw):
            inner = orig(*a, **kw)

            def fl_step(params, opt, step, batch):
                rows = batch["tokens"].shape[1]
                half = {k: v[:, :max(rows // 2, 1)] for k, v in batch.items()}
                return inner(params, opt, step, half)
            return fl_step
        monkeypatch.setattr(steps, "build_fl_train_step", build)
    if fault == "no_exchange":          # the gossip round sends nothing
        orig = steps.build_pod_gossip_step

        def build(*a, **kw):
            rnd, tr = orig(*a, **kw)

            def gossip_round(pstate, params, losses, start=None):
                nxt, _ = rnd(pstate, params, losses, start)
                return nxt, params
            return gossip_round, tr
        monkeypatch.setattr(steps, "build_pod_gossip_step", build)
    return harness.load_module(os.path.join(harness.BENCH, "drivers",
                                            "pods.py"),
                               "perfbench_driver_pods")


@pytest.mark.parametrize("every", GOSSIP_EVERY)
@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "no_exchange"])
def test_pods_fault_is_not_correct(monkeypatch, fault, every):
    line = harness.run_cell(pods_cell(every), seed=2**31 + 23, seconds=0.5,
                            trace=False, devices=jax.devices(),
                            t_start=time.perf_counter(),
                            driver=plant(monkeypatch, fault))
    assert line["correct"] is False, line["checks"]


FOUR_PODS = """
import json, sys
import jax
import control
from cells import pods_cell
found = pods_cell()
found["config"].update(pods=4, transport="ppermute", global_batch=8)
r = control.readings(found, 2**31 + 13, program=True,
                     devices=jax.devices())
print(json.dumps(r))
"""


def test_four_pods_on_a_mesh_match_the_reference():
    """One pod per device over the ``pod`` mesh with the ppermute ring
    (four CPU devices, toy widths): the program passes the comparison
    and the control does not, so the reference's four-pod sampling and
    mix are the program's."""
    tests = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([tests, harness.BENCH,
                                           os.path.join(harness.ROOT,
                                                        "src")]))
    proc = subprocess.run([sys.executable, "-c", FOUR_PODS], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    r = json.loads(proc.stdout.strip().splitlines()[-1])
    assert all(v <= lim for _, v, lim in r["program"]), r["program"]
    assert any(v > lim for _, v, lim in r["control"]), r["control"]
