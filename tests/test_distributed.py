"""Distributed-semantics tests. These need >1 device, so each runs in a
subprocess that sets xla_force_host_platform_device_count BEFORE jax init
(the main pytest process keeps the default single device per the spec).
"""
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_py(code: str, devices: int = 8, timeout: int = 520):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=timeout,
                       env=env)
    assert r.returncode == 0, r.stdout + "\n" + r.stderr
    return r.stdout


def test_eplocal_moe_matches_dense_oracle():
    """shard_map expert-parallel MoE == dense oracle (high capacity)."""
    run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.config import reduced
        from repro.configs import get_config
        from repro.models import init_params
        from repro.models.moe import moe_ffn
        from repro.sharding import logical_rules

        mesh = jax.make_mesh((4, 2), ("data", "model"))
        cfg = reduced(get_config("deepseek-moe-16b"))
        params = init_params(jax.random.PRNGKey(0), cfg)
        moe_p = params["layers"]["1"]["moe"]
        x = jax.random.normal(jax.random.PRNGKey(1), (8, 16, cfg.d_model))
        with mesh, logical_rules(mesh, {}):
            y_ref, aux_ref = moe_ffn(moe_p, cfg, x, strategy="dense")
            from repro.models.moe_eplocal import moe_eplocal
            y_ep, aux_ep = jax.jit(
                lambda p, xx: moe_eplocal(p, cfg, xx, cap_factor=8.0)
            )(moe_p, x)
        err = float(jnp.max(jnp.abs(y_ref - y_ep)))
        assert err < 2e-4, err
        assert abs(float(aux_ref - aux_ep)) < 1e-4
        print("ok", err)
    """)


def test_eplocal_replicated_tokens_path():
    """batch=1 (long_500k style) replicated-token fallback == dense."""
    run_py("""
        import jax, jax.numpy as jnp
        from repro.config import reduced
        from repro.configs import get_config
        from repro.models import init_params
        from repro.models.moe import moe_ffn
        from repro.models.moe_eplocal import moe_eplocal
        from repro.sharding import logical_rules

        mesh = jax.make_mesh((4, 2), ("data", "model"))
        cfg = reduced(get_config("jamba-v0.1-52b"))
        params = init_params(jax.random.PRNGKey(0), cfg)
        moe_p = params["layers"]["1"]["moe"]
        x = jax.random.normal(jax.random.PRNGKey(1), (1, 3, cfg.d_model))
        with mesh, logical_rules(mesh, {}):
            y_ref, _ = moe_ffn(moe_p, cfg, x, strategy="dense")
            y_ep, _ = jax.jit(lambda p, xx: moe_eplocal(p, cfg, xx))(moe_p, x)
        err = float(jnp.max(jnp.abs(y_ref - y_ep)))
        assert err < 2e-4, err
        print("ok", err)
    """)


def test_fl_step_pods_independent_and_gossip_mixes():
    """DeFTA-across-pods semantics: (1) without gossip the two pods train
    independently (different data -> different params); (2) the gossip step
    with uniform P makes them equal."""
    run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.config import reduced
        from repro.configs import get_config
        from repro.launch.mesh import make_debug_mesh
        from repro.launch.sharding_rules import base_rules
        from repro.launch.steps import build_fl_train_step, build_gossip_step
        from repro.models import model as mm
        from repro.optim import make_optimizer
        from repro.sharding import logical_rules

        pods = 2
        mesh = make_debug_mesh(data=2, model=2, pods=pods)
        # inside vmap(spmd_axis_name="pod") constraints must not mention pod
        rules = {**base_rules(multi_pod=True), "batch": ("data",)}
        cfg = reduced(get_config("granite-3-2b"))
        opt = make_optimizer("sgd", 0.05)
        key = jax.random.PRNGKey(0)
        params = mm.init_params(key, cfg)
        stacked = jax.tree.map(lambda x: jnp.stack([x] * pods), params)
        opt_state = opt.init(stacked)
        B, S = 4, 16
        toks = jax.random.randint(key, (pods, B, S), 0, cfg.vocab_size)
        batch = {"tokens": toks, "labels": toks}
        with mesh, logical_rules(mesh, rules):
            step = jax.jit(build_fl_train_step(cfg, opt,
                                               spmd_axis_name="pod"))
            p2, o2, _, losses = step(stacked, opt_state, jnp.int32(0), batch)
            # pods saw different data -> diverged params
            w0 = jax.tree.leaves(p2)[3]
            assert bool(jnp.any(jnp.abs(w0[0] - w0[1]) > 1e-7))
            # uniform gossip -> pods identical afterwards
            P = jnp.full((pods, pods), 0.5)
            gossip = jax.jit(build_gossip_step(cfg))
            p3 = gossip(p2, P)
            for leaf in jax.tree.leaves(p3):
                np.testing.assert_allclose(np.asarray(leaf[0], np.float32),
                                           np.asarray(leaf[1], np.float32),
                                           atol=1e-5)
            # and the per-pod loss on the SAME batch is now the same
        print("ok")
    """, devices=8)


def test_microbatched_step_equals_full_batch():
    """grad accumulation == single big batch (same loss trajectory)."""
    run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.config import reduced
        from repro.configs import get_config
        from repro.launch.steps import build_train_step
        from repro.models import model as mm
        from repro.optim import make_optimizer

        cfg = reduced(get_config("qwen3-0.6b"))
        opt = make_optimizer("sgd", 0.01)
        key = jax.random.PRNGKey(0)
        params = mm.init_params(key, cfg)
        toks = jax.random.randint(key, (8, 16), 0, cfg.vocab_size)
        batch = {"tokens": toks, "labels": toks}
        s1 = jax.jit(build_train_step(cfg, opt, microbatches=1))
        s4 = jax.jit(build_train_step(cfg, opt, microbatches=4))
        p1, _, _, l1 = s1(params, opt.init(params), jnp.int32(0), batch)
        p4, _, _, l4 = s4(params, opt.init(params), jnp.int32(0), batch)
        for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p4)):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32), atol=3e-5)
        print("ok", float(l1), float(l4))
    """, devices=1)


def test_costing_correction_matches_unrolled():
    """scan-corrected flops ~= unrolled-lowering flops (the correction's
    validity gate)."""
    run_py("""
        import dataclasses, jax, jax.numpy as jnp
        from repro.config import reduced, SHAPES, ShapeConfig
        from repro.configs import get_config
        from repro.launch.costing import train_cost
        from repro.launch.mesh import auto_mesh
        from repro.launch.sharding_rules import base_rules
        from repro.launch.steps import build_train_step, input_specs, abstract_state
        from repro.sharding import logical_rules

        mesh = auto_mesh((2, 2), ("data", "model"))
        rules = base_rules(False)
        cfg = dataclasses.replace(
            reduced(get_config("granite-3-2b"), num_layers=6, d_model=256),
            dtype="bfloat16", scan_layers=True, remat=True)
        shape = ShapeConfig("t", 128, 8, "train")
        with mesh, logical_rules(mesh, rules):
            f_corr, b_corr, _ = train_cost(cfg, shape, mesh, rules,
                                           optimizer="sgd")
            # unrolled reference
            cfg_u = dataclasses.replace(cfg, scan_layers=False, remat=False)
            params_sds, opt_sds, opt = abstract_state(cfg_u, "sgd",
                                                      mesh=mesh, rules=rules)
            specs = input_specs(cfg_u, shape, mesh, rules)
            comp = jax.jit(build_train_step(cfg_u, opt)).lower(
                params_sds, opt_sds, jax.ShapeDtypeStruct((), jnp.int32),
                specs).compile()
            cost = comp.cost_analysis()
            cost = cost[0] if isinstance(cost, (list, tuple)) else cost
            f_unrolled = float(cost["flops"])
        ratio = f_corr / f_unrolled
        # remat makes the scanned version do MORE flops (recompute); accept
        # [0.9, 2.0]
        assert 0.9 < ratio < 2.0, (f_corr, f_unrolled, ratio)
        print("ok", ratio)
    """, devices=4)


def test_ppermute_gossip_sparse_dense_and_quant_payloads():
    """mix_pytree_ppermute parity vs the einsum oracle on an 8-device
    mesh, across the three wire configurations it supports:
    * sparse ``adjacency`` (offset-skipping ring) at fp32,
    * the documented dense fallback (adjacency=None, all W offsets),
    * the quantized int8 payload (+ per-row scales) — which must equal
      mix_pytree's einsum int8 path bit-for-bit up to fp32 accumulation
      order, since both mix the SAME encoded payload."""
    run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.gossip import mix_pytree, mix_pytree_ppermute
        from repro.core.aggregation import mixing_matrix
        from repro.core.topology import make_topology

        w = 8
        mesh = jax.make_mesh((w,), ("pod",))
        adj = make_topology("ring", w, 2, seed=0)
        sizes = np.arange(1, w + 1) * 10
        P = jnp.asarray(mixing_matrix(adj, sizes, "defta"), jnp.float32)
        stacked = {"a": jax.random.normal(jax.random.PRNGKey(0), (w, 33)),
                   "b": jax.random.normal(jax.random.PRNGKey(1), (w, 4, 5))}

        with mesh:
            # 1. sparse adjacency, fp32 wire
            ref = mix_pytree(P, stacked)
            out = jax.jit(lambda p, s: mix_pytree_ppermute(
                p, s, mesh, adjacency=adj))(P, stacked)
            for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(ref)):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           atol=1e-5, err_msg="sparse")

            # 2. dense fallback: no adjacency, all offsets — still exact
            out_d = jax.jit(lambda p, s: mix_pytree_ppermute(
                p, s, mesh))(P, stacked)
            for a, b in zip(jax.tree.leaves(out_d), jax.tree.leaves(ref)):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           atol=1e-5, err_msg="dense")

            # 3. quantized int8 payload == einsum int8 path (same encode)
            ref_q = mix_pytree(P, stacked, wire="int8")
            out_q = jax.jit(lambda p, s: mix_pytree_ppermute(
                p, s, mesh, adjacency=adj, wire="int8"))(P, stacked)
            for a, b in zip(jax.tree.leaves(out_q), jax.tree.leaves(ref_q)):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           atol=1e-4, err_msg="int8")

            # 4. int8 + EF residual: ppermute and einsum agree on BOTH
            zeros = jax.tree.map(
                lambda x: jnp.zeros(x.shape, jnp.float32), stacked)
            ref_m, ref_r = mix_pytree(P, stacked, wire="int8",
                                      residual=zeros)
            out_m, out_r = jax.jit(lambda p, s, r: mix_pytree_ppermute(
                p, s, mesh, adjacency=adj, wire="int8", residual=r)
            )(P, stacked, zeros)
            for a, b in zip(jax.tree.leaves(out_r), jax.tree.leaves(ref_r)):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           atol=1e-6, err_msg="residual")
        print("ok")
    """, devices=8)


def test_fl_gossip_step_int8_wire_with_error_feedback():
    """build_gossip_step(wire='int8', error_feedback=True) on the pod
    mesh: uniform P still equalizes pods (all-ones-direction exactness is
    not required — check pods agree with each other and with the fp32
    step within the quantization bound), and the residual buffers are
    nonzero after a lossy step."""
    run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.config import reduced
        from repro.configs import get_config
        from repro.launch.mesh import make_debug_mesh
        from repro.launch.sharding_rules import base_rules
        from repro.launch.steps import build_gossip_step
        from repro.models import model as mm
        from repro.sharding import logical_rules

        pods = 2
        mesh = make_debug_mesh(data=2, model=2, pods=pods)
        rules = base_rules(multi_pod=True)
        cfg = reduced(get_config("granite-3-2b"))
        key = jax.random.PRNGKey(0)
        params = mm.init_params(key, cfg)
        # two distinct pod replicas
        stacked = jax.tree.map(
            lambda x: jnp.stack([x, x + 0.01 * jnp.sign(x)]), params)
        P = jnp.full((pods, pods), 0.5)
        with mesh, logical_rules(mesh, rules):
            g32 = jax.jit(build_gossip_step(cfg))
            g8 = jax.jit(build_gossip_step(cfg, wire="int8",
                                           error_feedback=True))
            ref = g32(stacked, P)
            err0 = jax.tree.map(
                lambda x: jnp.zeros(x.shape, jnp.float32), stacked)
            out, err1 = g8(stacked, P, err0)
        worst = 0.0
        for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(ref)):
            # pods equalized
            np.testing.assert_allclose(np.asarray(a[0], np.float32),
                                       np.asarray(a[1], np.float32),
                                       atol=1e-5)
            worst = max(worst, float(jnp.max(jnp.abs(
                a.astype(jnp.float32) - b.astype(jnp.float32)))))
        assert worst < 0.05, worst          # quantization-bounded
        assert any(float(jnp.abs(r).max()) > 0
                   for r in jax.tree.leaves(err1))
        print("ok", worst)
    """, devices=8)


def test_dryrun_entrypoint_small():
    """python -m repro.launch.dryrun must succeed end-to-end for a pair on
    the REAL 512-device production mesh (this is the deliverable's gate)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch",
         "whisper-tiny", "--shape", "train_4k", "--out",
         "/tmp/dryrun_test"],
        capture_output=True, text=True, timeout=520, env=env)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "bottleneck=" in r.stdout
