"""gossip_mix_quant — fused int8 dequantize→mix as a Pallas kernel.

The quantized gossip wire format (core/gossip.py) ships each worker's
flattened model row as int8 with ONE fp32 scale per row:

    q:     [W, F] int8 — round(row / scale), clipped to ±127
    scale: [W] f32     — max|row| / 127 (symmetric, per row)

The naive lowering dequantizes the whole stack to fp32 HBM
(``q.astype(f32) * scale``) and then runs the sparse mixing kernel — a full
extra fp32 stack write+read that erases most of the 4× wire-byte win. This
op fuses the two:

    out[i] = Σ_k val[i, k] · scale[idx[i, k]] · q[idx[i, k], :]

* The dequant scales are folded into the CSR weights ONCE, before the
  kernel (``sval[i, k] = val[i, k] · scale[idx[i, k]]``, a [W, K] op) so
  the inner loop is exactly the sparse kernel's K load+FMA chain.
* The INT8 stack streams from HBM; each (W, BF) block is widened to fp32
  once in VMEM (``gossip_mix_sparse.gather_mix``), so fp32 rows exist only
  block-at-a-time on chip, never materialized in HBM.
* Accumulation is fp32; ``out_dtype`` sets the store dtype (the engine's
  parameter dtype, so the wire cast never leaks out).

TPU follow-up (ROADMAP): keep the int8 block un-widened in VMEM and let
the VPU widen during the FMA.

The pure-jnp contract is ``repro.kernels.ref.gossip_mix_quant_ref``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.gossip_mix_sparse import DEFAULT_BLOCK_F, gather_mix


@functools.partial(jax.jit,
                   static_argnames=("out_dtype", "block_f", "interpret"))
def gossip_mix_quant_pallas(idx, val, scale, q, *, out_dtype=jnp.float32,
                            block_f: int = DEFAULT_BLOCK_F,
                            interpret=None):
    """idx: [W, K] int32; val: [W, K]; scale: [W] or [W, 1] f32;
    q: [W, F] int8. Returns [W, F] in ``out_dtype``."""
    scale = scale.reshape(-1).astype(jnp.float32)
    sval = val.astype(jnp.float32) * jnp.take(scale, idx)
    return gather_mix(idx, sval, q, out_dtype=out_dtype, block_f=block_f,
                      interpret=interpret)
