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
import re

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


@pytest.fixture(scope="module")
def qwen3_gossip_round(one_chip):
    """The qwen3.pods2.gossip4 cell's gossip round (int8 wire, EF21 on,
    pod DTS on the loss delta, 2 full-width qwen3-0.6b pods on one chip)
    lowered and compiled for a described v5e: (lowered, compiled, the
    optimizer-state bytes that stay resident between train steps)."""
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
    lowered = jax.jit(rnd, donate_argnums=(0, 1)).lower(
        pstate, stacked, _sds((PODS,), jnp.float32, one_chip), None)
    opt = make_optimizer("adafactor", 1e-3)
    resident = _nbytes(stack(jax.eval_shape(opt.init, params)))
    return lowered, lowered.compile(), resident


def test_qwen3_two_pod_int8_gossip_round_fits_v5e(qwen3_gossip_round):
    """The same run's gossip round (int8 wire, EF21 on, pod DTS): its
    arguments (stacked params plus pod state with the f32 EF residuals)
    and temporaries, plus the optimizer state that stays resident between
    train steps, stay under the chip's 16 GB."""
    _, compiled, resident = qwen3_gossip_round
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes + resident
    assert used < V5E_HBM_BYTES, used / 1e9


_INSTR = r"^\s*(?:ROOT\s+)?%?[\w.\-]+ = "
# the gossip round of qwen3.pods2.gossip4 and the round of cnn21.paper,
# counted on the tree before the three round builders shared their stages
QWEN3_GOSSIP_LOWERED = 1355
QWEN3_GOSSIP_TEMP_BYTES = 2_490_351_104
CNN21_ROUND_LOWERED = 3927


def _count(text):
    return len(re.findall(_INSTR, text, re.M))


def test_qwen3_pod_step_compiles_to_the_same_program(one_chip):
    """qwen3-0.6b's two-pod train step at the benchmark cell's shape (3
    rows of 1024 a pod) lowers and compiles to as many HLO instructions,
    with as many temporary bytes, as before the latent-attention and
    sigmoid-router changes to the shared model code (counted on the tree
    before them: 2,380 lowered, 4,437 compiled, 8,101,410,816 bytes)."""
    from repro.launch.steps import build_fl_train_step
    from repro.optim import make_optimizer

    cfg, params, stack = _qwen3_pods(one_chip)
    opt = make_optimizer("adafactor", 1e-3)
    toks = _sds((PODS, 3, 1024), jnp.int32, one_chip)
    lowered = jax.jit(build_fl_train_step(cfg, opt),
                      donate_argnums=(0, 1)).lower(
        stack(params), stack(jax.eval_shape(opt.init, params)),
        _sds((), jnp.int32, one_chip), {"tokens": toks, "labels": toks})
    assert _count(lowered.as_text(dialect="hlo")) == 2380
    compiled = lowered.compile()
    assert _count(compiled.as_text()) == 4437
    assert compiled.memory_analysis().temp_size_in_bytes == 8_101_410_816


def test_qwen3_gossip_round_compiles_to_the_same_program(
        qwen3_gossip_round):
    """The qwen3 cell's gossip round lowers to as many HLO instructions,
    and compiles with as many temporary bytes, as before its peer sample,
    mixing and confidence update became the code the dense and
    cross-device rounds run (counted on the tree before that change)."""
    lowered, compiled, _ = qwen3_gossip_round
    assert _count(lowered.as_text(dialect="hlo")) == QWEN3_GOSSIP_LOWERED
    assert compiled.memory_analysis().temp_size_in_bytes \
        == QWEN3_GOSSIP_TEMP_BYTES


def test_cnn21_round_lowers_to_the_same_program(one_chip, monkeypatch):
    """The cnn21.paper cell's round (21 CNN workers of 300 padded 28x28
    images, the paper_noise attacker, random 4-out topology, 2 sampled
    peers, 10 local epochs, DTS, the sparse int8 + EF21 wire) lowered for
    a described v5e, its gossip kernels through Mosaic as on the chip:
    as many HLO instructions as before the dense, pod and cross-device
    rounds shared their stages (counted on the tree before that change)."""
    from importlib import import_module

    from repro.config import DeFTAConfig, TrainConfig
    from repro.core.defta import build_round_fn, resolve_scenario
    from repro.core.engine import init_state, sketch_shape
    from repro.core.gossip import uses_error_feedback
    from repro.core.tasks import cnn_task
    from repro.core.topology import make_topology

    # the package re-exports the kernel under the module's name
    sparse_mod = import_module("repro.kernels.gossip_mix_sparse")
    monkeypatch.setattr(sparse_mod, "resolve_interpret",
                        lambda interpret=None: False)
    task = cnn_task(28, 1, 10, width=16)
    cfg = DeFTAConfig(num_workers=20, avg_peers=4, num_sampled=2,
                      topology="random_kout", local_epochs=10,
                      gossip_dtype="int8", gossip_error_feedback=True,
                      use_dts=True, seed=0)
    train = TrainConfig(learning_rate=0.05, batch_size=32)
    scenario = resolve_scenario("paper_noise", cfg, 4000)
    w, n = scenario.num_workers, 300
    adj = make_topology(cfg.topology, w, cfg.avg_peers, cfg.seed)
    rnd = build_round_fn(task, cfg, train, adj, np.full(w, 200),
                         scenario.malicious, gossip_backend="sparse",
                         scenario=scenario, num_classes=10)
    state = jax.tree.map(
        lambda x: _sds(x.shape, x.dtype, one_chip),
        jax.eval_shape(lambda k: init_state(
            k, task, w, wire_error=uses_error_feedback(cfg),
            sketch=sketch_shape(cfg)), jax.random.PRNGKey(0)))
    data = {"x": _sds((w, n, 28 * 28), jnp.float32, one_chip),
            "y": _sds((w, n), jnp.int32, one_chip),
            "mask": _sds((w, n), jnp.float32, one_chip)}
    text = jax.jit(rnd).lower(state, data, _sds((), jnp.int32, one_chip)
                              ).as_text(dialect="hlo")
    assert "tpu_custom_call" in text
    assert _count(text) == CNN21_ROUND_LOWERED


@pytest.fixture(scope="module")
def moonlight_step(one_chip):
    """The moonlight.pods2.gossip4 cell's train step compiled for a
    described v5e: Moonlight-16B-A3B's block at its published widths, cut
    to 5 layers, 8 held experts and a 20,480 vocabulary, 2 pods of one
    8,192-token row, adafactor, with the MoE stats. (compiled, params)."""
    import dataclasses

    from repro.configs import get_config
    from repro.launch.steps import build_fl_train_step
    from repro.models import model as mm
    from repro.optim import make_optimizer

    base = get_config("moonlight-16b-a3b")
    cfg = dataclasses.replace(base, num_layers=5, vocab_size=20_480,
                              moe=dataclasses.replace(base.moe, num_held=8))
    params = mm.abstract_params(cfg)
    assert sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params)) \
        == 568_484_608
    stack = lambda t: jax.tree.map(
        lambda x: _sds((PODS,) + x.shape, x.dtype, one_chip), t)
    opt = make_optimizer("adafactor", 1e-2)
    toks = _sds((PODS, 1, 8192), jnp.int32, one_chip)
    compiled = jax.jit(build_fl_train_step(cfg, opt, with_stats=True),
                       donate_argnums=(0, 1)).lower(
        stack(params), stack(jax.eval_shape(opt.init, params)),
        _sds((), jnp.int32, one_chip),
        {"tokens": toks, "labels": toks}).compile()
    return compiled, params


def _kernel_calls(text):
    """Names of the Pallas custom calls of a compiled module."""
    return re.findall(
        r"^\s*(?:ROOT\s+)?%?([\w.-]+) = [^\n]*custom-call\([^\n]*"
        r"tpu_custom_call", text, re.M)


def test_moonlight_two_pod_train_step_fits_v5e(moonlight_step):
    """The Moonlight cell's train step: arguments, temporaries (8.16 GB;
    9.07 GB while latent attention took the jnp blocked path) and the int8
    wire's EF residuals fit the chip, and the held experts run as the
    TPU's grouped matmul kernels (megablox ``gmm`` and ``tgmm``)."""
    compiled, params = moonlight_step
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + _ef_residual_bytes(params))
    assert used < V5E_HBM_BYTES, used / 1e9
    kernels = {re.sub(r"\.\d+$", "", n)
               for n in _kernel_calls(compiled.as_text())}
    assert {"gmm", "tgmm"} <= kernels, kernels
    assert "ragged-dot" not in compiled.as_text()


def test_moonlight_attention_is_the_flash_kernel_in_scope_mla(
        moonlight_step):
    """In the Moonlight step latent attention at 8,192 positions runs as
    the splash flash kernel (forward, ``dq`` and ``dkv`` custom calls), each
    of which ``trace.hlo_scopes`` maps to ``mla`` (so ``mla_ms.moonlight``
    counts it), and no ``while`` loop of the jnp blocked path is left in
    that scope."""
    from repro.telemetry.trace import hlo_scopes

    text = moonlight_step[0].as_text()
    scopes = hlo_scopes(text, ("mla", "moe_route", "moe_experts",
                               "moe_shared"))
    splash = [n for n in _kernel_calls(text) if n.startswith("splash_mha")]
    kinds = {re.sub(r"_(no_)?residuals\.\d+$", "", n) for n in splash}
    assert kinds == {"splash_mha_fwd", "splash_mha_dq", "splash_mha_dkv"}, \
        kinds
    assert {scopes[n] for n in splash} == {"mla"}, \
        {n: scopes[n] for n in splash}
    loops = re.findall(r"^\s*(?:ROOT\s+)?%?([\w.-]+) = [^\n]* while\(",
                       text, re.M)
    assert loops and not [n for n in loops if scopes[n] == "mla"]


@pytest.mark.parametrize("kind", ["gqa", "mla"])
def test_long_attention_on_a_v5e_mesh_keeps_the_jnp_path(topo, kind):
    """``attention()`` at qwen3-0.6b's widths and ``mla()`` at Moonlight's,
    4,096 positions, forward and backward. On one chip they compile to the
    splash kernel. On a 2x2 v5e mesh under ``base_rules`` (batch over
    ``data``, heads over ``model``) the compiler partitions the program and
    cannot partition a Mosaic kernel, so they take the jnp blocked path,
    which compiles there."""
    from repro.configs import get_config
    from repro.launch.mesh import auto_mesh
    from repro.launch.sharding_rules import base_rules
    from repro.models import attention, mla
    from repro.models.layers import Builder
    from repro.sharding import logical_rules, named_sharding, partitioned

    cfg = get_config("qwen3-0.6b" if kind == "gqa" else "moonlight-16b-a3b")
    init, apply = ((attention.init_attention, attention.attention)
                   if kind == "gqa" else (mla.init_mla, mla.mla))
    b = Builder(jax.random.PRNGKey(0), jnp.dtype(cfg.dtype), abstract=True)
    init(b, cfg)
    rows, seq = 2, 4096
    x_shape, pos_shape = (rows, seq, cfg.d_model), (rows, seq)

    def loss(p, x, pos):
        return jnp.sum(apply(p, cfg, x, pos).astype(jnp.float32))

    def compiled_text(place):
        params = {n: _sds(a.shape, a.dtype, place(b.axes[n], a.shape))
                  for n, a in b.params.items()}
        x = _sds(x_shape, jnp.dtype(cfg.dtype),
                 place(("batch", "act_seq", "embed"), x_shape))
        pos = _sds(pos_shape, jnp.int32, place(("batch", "seq"), pos_shape))
        return jax.jit(jax.grad(loss)).lower(params, x, pos).compile() \
            .as_text()

    one = SingleDeviceSharding(topo.devices[0])
    assert not partitioned()
    assert _kernel_calls(compiled_text(lambda axes, shape: one))
    mesh = auto_mesh((2, 2), ("data", "model"), devices=topo.devices)
    with mesh, logical_rules(mesh, base_rules(multi_pod=False)):
        assert partitioned()
        text = compiled_text(named_sharding)
    assert not _kernel_calls(text)
    assert " while(" in text
