"""A run of the engine cell with its timed path broken underneath (the
chip check skipped, a small cut of the cell on the CPU) comes out not
correct, once for each fault the cell can have."""
import os
import time

import jax
import jax.numpy as jnp
import pytest

import run as harness
from cells import engine_cell


def _engine_driver():
    return harness.load_module(os.path.join(harness.BENCH, "drivers",
                                            "engine.py"),
                               "perfbench_driver_engine")


def plant(monkeypatch, fault):
    """The engine driver with ``fault`` planted in the program."""
    from repro.core import engine

    if fault == "unchanged":            # a round that returns its state
        orig = engine.drive_epochs

        def drive_epochs(rnd_fn, state, *a, **kw):
            return orig(lambda s, d, e=None: s, state, *a, **kw)
        monkeypatch.setattr(engine, "drive_epochs", drive_epochs)
    if fault == "half_batch":           # each minibatch's mean over half
        orig = engine.local_train_fn

        def ltrain(task, *a, **kw):
            def loss(p, x, y, m):
                half = jnp.arange(m.shape[0]) < m.shape[0] // 2
                return task.loss(p, x, y, m * half)
            return orig(task.__class__(task.name, task.init, loss,
                                       task.accuracy), *a, **kw)
        monkeypatch.setattr(engine, "local_train_fn", ltrain)
    if fault == "no_exchange":          # the gossip mix sends nothing
        orig_tr = engine.make_transport

        def make_transport(*a, **kw):
            tr = orig_tr(*a, **kw)

            def mix(P, stacked, residual=None, key=None, round_=None):
                return stacked if residual is None else (stacked, residual)
            return engine.Transport(kind=tr.kind, wire=tr.wire,
                                    use_ef=tr.use_ef,
                                    stochastic=tr.stochastic, mix=mix)
        monkeypatch.setattr(engine, "make_transport", make_transport)
    return _engine_driver()


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "no_exchange"])
def test_engine_fault_is_not_correct(monkeypatch, fault):
    line = harness.run_cell(engine_cell(), seed=2**31 + 21, seconds=0.5,
                            trace=False, devices=jax.devices(),
                            t_start=time.perf_counter(),
                            driver=plant(monkeypatch, fault))
    assert line["correct"] is False, line["checks"]
