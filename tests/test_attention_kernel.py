"""The TPU path of ``blocked_attention_sdpa``: the Pallas splash flash kernel
(``flash_attention_sdpa``), run here in interpret mode, against the pure-jnp
blocked path that the CPU takes and that the TPU took before it; and which
shapes ``blocked_attention_sdpa`` leaves on the jnp path.

Inputs are float32, so the two paths differ only in the order of their
sums: 2e-5 is about forty float32 ulps at the largest outputs (up to 6).
"""
import jax
import numpy as np
import pytest

from repro.models.attention import (_sdpa, blocked_attention_jnp,
                                    blocked_attention_sdpa, causal_mask,
                                    flash_attention_sdpa, flash_tiling)

TOL = dict(atol=2e-5, rtol=1e-5)


def _qkv(lead, s, hq, hkv, dqk, dv, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], lead + (s, hq, dqk)),
            jax.random.normal(ks[1], lead + (s, hkv, dqk)),
            jax.random.normal(ks[2], lead + (s, hkv, dv)),
            jax.random.normal(ks[3], lead + (s, hq, dv)))


@pytest.mark.parametrize("pods,batch,s,hq,hkv,dqk,dv", [
    # latent attention's widths under a vmap over a pods axis
    (2, 1, 512, 2, 2, 192, 128),
    # grouped-query attention: two query heads a kv head, equal widths
    (0, 2, 256, 4, 2, 128, 128),
], ids=["mla_pods", "gqa"])
def test_flash_kernel_matches_blocked_jnp_path(pods, batch, s, hq, hkv, dqk,
                                               dv):
    """Forward values and the gradients of q, k and v."""
    lead = (pods, batch) if pods else (batch,)
    q, k, v, dout = _qkv(lead, s, hq, hkv, dqk, dv)
    tiles = flash_tiling(s, 128, 128, 128)
    kernel = lambda q, k, v: flash_attention_sdpa(q, k, v, tiles=tiles,
                                                  interpret=True)
    ref = lambda q, k, v: blocked_attention_sdpa(q, k, v, block_q=128,
                                                 block_k=128)
    if pods:
        kernel, ref = jax.vmap(kernel), jax.vmap(ref)
    got, pull = jax.vjp(kernel, q, k, v)
    want, pull_ref = jax.vjp(ref, q, k, v)
    assert got.shape == lead + (s, hq, dv)
    np.testing.assert_allclose(got, want, **TOL)
    for name, g, w in zip("qkv", pull(dout), pull_ref(dout)):
        np.testing.assert_allclose(g, w, err_msg=f"d{name}", **TOL)


def _calls_kernel(fn, *args) -> bool:
    return "pallas_call" in str(jax.make_jaxpr(fn)(*args))


@pytest.mark.parametrize("s,window,causal,kernel", [
    (512, 0, True, True),         # tiled causal self-attention: the kernel
    (320, 0, True, False),        # 320 positions: no 128-lane key block
    (512, 128, True, False),      # a sliding window: the local mask is not
                                  # wired into the kernel
    (512, 0, False, False),       # not causal
], ids=["tiled", "ragged_length", "window", "bidirectional"])
def test_blocked_attention_selects_kernel_by_shape(s, window, causal, kernel):
    """Only shapes ``flash_tiling`` tiles reach the kernel (traced in the
    TPU branch here, never run); on the CPU every shape computes the jnp
    path, which matches a plain masked softmax."""
    q, k, v, _ = _qkv((1,), s, 2, 2, 64, 32)
    assert (flash_tiling(s) is not None) == (s % 128 == 0)
    fn = lambda q, k, v: blocked_attention_sdpa(q, k, v, causal=causal,
                                                window=window, block_q=128,
                                                block_k=128)
    assert _calls_kernel(fn, q, k, v) == kernel
    out = fn(q, k, v)
    np.testing.assert_array_equal(out, blocked_attention_jnp(
        q, k, v, causal=causal, window=window, block_q=128, block_k=128))
    mask = causal_mask(s, s, window) if causal else None
    np.testing.assert_allclose(out, _sdpa(q, k, v, mask, 2), **TOL)
