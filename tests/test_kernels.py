"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, interpret=True."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import flash_attention, gossip_mix, moe_router_topk
from repro.kernels.ref import (flash_attention_ref, gossip_mix_ref,
                               moe_router_topk_ref)


@pytest.mark.parametrize("w,f", [(4, 100), (8, 4096), (20, 777), (60, 2048)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gossip_mix_sweep(w, f, dtype):
    key = jax.random.PRNGKey(w * f)
    P = jax.nn.softmax(jax.random.normal(key, (w, w)), -1).astype(jnp.float32)
    stack = jax.random.normal(jax.random.fold_in(key, 1), (w, f)).astype(dtype)
    out = gossip_mix(P, stack)
    ref = gossip_mix_ref(P.astype(jnp.float32),
                         stack.astype(jnp.float32)).astype(dtype)
    atol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=atol)


def test_gossip_mix_row_stochastic_preserves_constant():
    """P row-stochastic => mixing a constant stack is identity (the property
    DeFTA aggregation relies on)."""
    w, f = 12, 512
    P = jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(0), (w, w)), -1)
    stack = jnp.full((w, f), 3.14159)
    np.testing.assert_allclose(np.asarray(gossip_mix(P, stack)), 3.14159,
                               rtol=1e-5)


@pytest.mark.parametrize("f,block_f,per_lane,want", [
    (100, 2048, 1, 128),                # narrow leaf: one 128-lane block
    (100_000, 2048, 1, 2048),           # wide leaf, small rows: the cap
    (100_000, 2048, 4096, 2048),        # 2048 * 4096 B = 8 MiB fits
    (100_000, 2048, 8192, 1024),        # 16 MiB does not: halve once
    (100_000, 2048, 1 << 20, 128),      # nothing fits: floor of 128 lanes
])
def test_fit_block_f_halves_to_the_vmem_budget(f, block_f, per_lane, want):
    from repro.kernels.common import LANES, VMEM_BUDGET, fit_block_f
    bf, nbytes = fit_block_f(f, block_f, lambda b: b * per_lane)
    assert (bf, nbytes) == (want, want * per_lane)
    assert nbytes <= VMEM_BUDGET or bf == LANES


@pytest.mark.parametrize("b,h,s,d", [(2, 4, 256, 64), (1, 2, 128, 32),
                                     (2, 2, 384, 128), (1, 8, 512, 64)])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 128),
                                           (False, 0)])
def test_flash_attention_sweep(b, h, s, d, causal, window):
    key = jax.random.PRNGKey(b + h + s + d)
    q = jax.random.normal(jax.random.fold_in(key, 1), (b, h, s, d))
    k = jax.random.normal(jax.random.fold_in(key, 2), (b, h, s, d))
    v = jax.random.normal(jax.random.fold_in(key, 3), (b, h, s, d))
    out = flash_attention(q, k, v, causal=causal, window=window)
    ref = flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=5e-5)


def test_flash_attention_bf16():
    key = jax.random.PRNGKey(7)
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i),
                                 (1, 2, 256, 64)).astype(jnp.bfloat16)
               for i in range(3))
    out = flash_attention(q, k, v)
    ref = flash_attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=3e-2)


def test_flash_attention_unpadded_seq():
    # S not a block multiple exercises the padding path
    key = jax.random.PRNGKey(9)
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i), (1, 2, 200, 32))
               for i in range(3))
    out = flash_attention(q, k, v, block_q=128, block_k=128)
    ref = flash_attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=5e-5)


@pytest.mark.parametrize("t,e,k", [(64, 8, 2), (100, 64, 6), (512, 384, 8),
                                   (33, 16, 2)])
def test_moe_router_sweep(t, e, k):
    logits = jax.random.normal(jax.random.PRNGKey(t + e), (t, e))
    gates, idx = moe_router_topk(logits, k)
    gref, iref = moe_router_topk_ref(logits, k)
    np.testing.assert_allclose(np.asarray(gates), np.asarray(gref),
                               atol=1e-5)
    assert bool((idx == iref).all())


def test_moe_router_gates_normalized():
    logits = jax.random.normal(jax.random.PRNGKey(3), (128, 64)) * 3
    gates, idx = moe_router_topk(logits, 6)
    np.testing.assert_allclose(np.asarray(gates.sum(-1)), 1.0, atol=1e-5)
    # indices are distinct per row
    assert all(len(set(row)) == 6 for row in np.asarray(idx))


@pytest.mark.parametrize("g,h,t,n,p", [(2, 2, 64, 16, 32), (1, 4, 128, 32, 64),
                                       (3, 1, 32, 8, 16)])
def test_ssd_chunk_sweep(g, h, t, n, p):
    from repro.kernels.ops import ssd_chunk
    from repro.kernels.ref import ssd_chunk_ref
    key = jax.random.PRNGKey(g * t)
    C = jax.random.normal(jax.random.fold_in(key, 0), (g, t, n))
    B = jax.random.normal(jax.random.fold_in(key, 1), (g, t, n))
    # negative cumulative decays (realistic: dA <= 0 cumsum)
    acum = -jnp.abs(jax.random.normal(jax.random.fold_in(key, 2),
                                      (g, h, t))).cumsum(-1)
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(key, 3),
                                           (g, h, t)))
    x = jax.random.normal(jax.random.fold_in(key, 4), (g, h, t, p))
    out = ssd_chunk(C, B, acum, dt, x)
    ref = ssd_chunk_ref(C, B, acum, dt, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-4)


def test_ssd_chunk_matches_model_ssm_y_diag():
    """The kernel computes exactly the y_diag term of models/ssm.ssd_scan."""
    from repro.kernels.ops import ssd_chunk
    from repro.models.ssm import _segsum
    key = jax.random.PRNGKey(0)
    b_, nc, t, hh, n, p = 1, 2, 32, 2, 8, 16
    Cc = jax.random.normal(jax.random.fold_in(key, 0), (b_, nc, t, n))
    Bc = jax.random.normal(jax.random.fold_in(key, 1), (b_, nc, t, n))
    dtc = jax.nn.softplus(jax.random.normal(jax.random.fold_in(key, 2),
                                            (b_, nc, t, hh)))
    xc = jax.random.normal(jax.random.fold_in(key, 3), (b_, nc, t, hh, p))
    A = -jnp.abs(jax.random.normal(jax.random.fold_in(key, 4), (hh,)))
    dA = jnp.moveaxis(dtc * A[None, None, None, :], -1, 2)
    dA_cumsum = jnp.cumsum(dA, axis=-1)
    L = jnp.exp(_segsum(dA))
    scores = jnp.einsum("bcqn,bckn->bcqk", Cc, Bc)
    y_ref = jnp.einsum("bcqk,bchqk,bckh,bckhp->bcqhp", scores, L, dtc, xc)
    out = ssd_chunk(Cc.reshape(b_ * nc, t, n), Bc.reshape(b_ * nc, t, n),
                    dA_cumsum.reshape(b_ * nc, hh, t),
                    jnp.moveaxis(dtc, -1, 2).reshape(b_ * nc, hh, t),
                    jnp.moveaxis(xc, 3, 2).reshape(b_ * nc, hh, t, p))
    out = jnp.moveaxis(out.reshape(b_, nc, hh, t, p), 2, 3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(y_ref),
                               atol=2e-4)
