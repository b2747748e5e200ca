"""Ahead-of-time compiles of the main path for a described TPU v5e chip.

The TPU compiler is installed with JAX, so a chip that is described but not
attached still refuses what the real chip would refuse: a gather Mosaic
cannot lower, a tile past the scoped VMEM limit, a step that does not fit
HBM. Interpret mode sees none of that. Nothing runs here, so these tests
say nothing about results or times.

The topology is described inside a module-scoped fixture (never at import
or collection time): only one process at a time may load the TPU library,
and under pytest-xdist every worker imports every test file.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.gossip_mix import gossip_mix_pallas
from repro.kernels.gossip_mix_quant import gossip_mix_quant_pallas
from repro.kernels.gossip_mix_sparse import gossip_mix_sparse_pallas

V5E_HBM_BYTES = 16e9
K = 7                       # padded-CSR slots: avg_peers=4 plus slack
F = 7 * 7 * 32 * 10         # the paper CNN's widest leaf (cnn_task(28, 1, 10))


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:               # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_compile_cache():
    """A described chip's executables cannot be read back from the
    persistent cache; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_case(name, w, dtype, s):
    if name == "gossip_mix":
        fn = lambda p, x: gossip_mix_pallas(p, x, out_dtype=jnp.float32,
                                            interpret=False)
        return fn, (_sds((w, w), jnp.float32, s), _sds((w, F), dtype, s))
    if name == "gossip_mix_sparse":
        fn = lambda i, v, x: gossip_mix_sparse_pallas(
            i, v, x, out_dtype=jnp.float32, interpret=False)
        return fn, (_sds((w, K), jnp.int32, s), _sds((w, K), jnp.float32, s),
                    _sds((w, F), dtype, s))
    fn = lambda i, v, sc, q: gossip_mix_quant_pallas(i, v, sc, q,
                                                     interpret=False)
    return fn, (_sds((w, K), jnp.int32, s), _sds((w, K), jnp.float32, s),
                _sds((w,), jnp.float32, s), _sds((w, F), dtype, s))


@pytest.mark.parametrize("name,dtype", [
    ("gossip_mix", jnp.float32), ("gossip_mix", jnp.int8),
    ("gossip_mix_sparse", jnp.float32), ("gossip_mix_quant", jnp.int8)])
@pytest.mark.parametrize("w", [500, 2500])
def test_gossip_kernel_compiles_for_v5e(one_chip, name, dtype, w):
    fn, args = _kernel_case(name, w, dtype, one_chip)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), name


PODS, BATCH, SEQ = 2, 8, 128      # chip_smoke.py phase B


def _nbytes(tree):
    return sum(int(np.prod(x.shape)) * jnp.dtype(x.dtype).itemsize
               for x in jax.tree.leaves(tree))


def _qwen3_pods(one_chip):
    """qwen3-0.6b at full width stacked over PODS on one chip: (config,
    per-pod abstract params, stack placing a per-pod tree on the chip)."""
    from repro.configs import get_config
    from repro.models import model as mm

    cfg = get_config("qwen3-0.6b")
    params = mm.abstract_params(cfg)
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    assert n == 596_049_920                 # full width, not reduced
    stack = lambda t: jax.tree.map(
        lambda x: _sds((PODS,) + x.shape, x.dtype, one_chip), t)
    return cfg, params, stack


def _ef_residual_bytes(params):
    """The int8 wire's EF21 residuals: one f32 copy of the stacked params
    (``engine.init_pod_state(wire_error=True)``), resident all run."""
    return PODS * 4 * sum(int(np.prod(x.shape))
                          for x in jax.tree.leaves(params))


def test_qwen3_two_pod_adafactor_train_step_fits_v5e(one_chip):
    """launch/train.py --fl's train step for qwen3-0.6b at full width, 2
    pods on one chip with adafactor (chip_smoke.py phase B): argument
    plus temporary bytes, plus the int8 wire's EF residuals that stay
    resident between gossip rounds, stay under the chip's 16 GB."""
    from repro.launch.steps import build_fl_train_step
    from repro.optim import make_optimizer

    cfg, params, stack = _qwen3_pods(one_chip)
    opt = make_optimizer("adafactor", 1e-3)
    toks = _sds((PODS, BATCH // PODS, SEQ), jnp.int32, one_chip)
    compiled = jax.jit(build_fl_train_step(cfg, opt),
                       donate_argnums=(0, 1)).lower(
        stack(params), stack(jax.eval_shape(opt.init, params)),
        _sds((), jnp.int32, one_chip),
        {"tokens": toks, "labels": toks}).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + _ef_residual_bytes(params))
    assert used < V5E_HBM_BYTES, used / 1e9


def test_qwen3_two_pod_int8_gossip_round_fits_v5e(one_chip):
    """The same run's gossip round (int8 wire, EF21 on, pod DTS): its
    arguments (stacked params plus pod state with the f32 EF residuals)
    and temporaries, plus the optimizer state that stays resident between
    train steps, stay under the chip's 16 GB."""
    from repro.config import DeFTAConfig
    from repro.core.engine import init_pod_state
    from repro.core.topology import make_topology
    from repro.launch.steps import build_pod_gossip_step
    from repro.optim import make_optimizer

    cfg, params, stack = _qwen3_pods(one_chip)
    dcfg = DeFTAConfig(num_workers=PODS, avg_peers=PODS - 1, num_sampled=1,
                       topology="dense", use_dts=True, gossip_dtype="int8",
                       gossip_error_feedback=True)
    rnd, tr = build_pod_gossip_step(
        cfg, dcfg, PODS, np.full(PODS, BATCH),
        adjacency=make_topology("dense", PODS, PODS - 1))
    assert tr.wire == "int8" and tr.use_ef
    stacked = stack(params)
    pstate = jax.tree.map(
        lambda x: _sds(x.shape, x.dtype, one_chip),
        jax.eval_shape(lambda p: init_pod_state(
            jax.random.PRNGKey(0), PODS, p, wire_error=True), stacked))
    assert _nbytes(pstate.wire_err) == _ef_residual_bytes(params)
    compiled = jax.jit(rnd, donate_argnums=(0, 1)).lower(
        pstate, stacked, _sds((PODS,), jnp.float32, one_chip),
        None).compile()
    mem = compiled.memory_analysis()
    opt = make_optimizer("adafactor", 1e-3)
    resident = _nbytes(stack(jax.eval_shape(opt.init, params)))
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes + resident
    assert used < V5E_HBM_BYTES, used / 1e9
