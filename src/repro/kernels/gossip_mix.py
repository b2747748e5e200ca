"""gossip_mix — DeFTA's aggregation hot-spot as a Pallas TPU kernel.

Computes ``out = P @ W`` where P is the [W, W] mixing matrix (W = world
size) and W is the [W, F] stack of flattened worker params (F = model
size, huge: up to 10^12). The op is memory-bound, so the kernel's job is
streaming efficiency:

* The stack's (W, BF) parameter block stays resident in VMEM while blocks
  of P's rows sweep it (one fetch per BF columns); a stack narrower than
  32 bits (the int8 wire) is widened into an fp32 VMEM scratch once per
  parameter block.
* Each grid step is one (BR×W)·(W×BF) MXU matmul.
* BF shrinks (down to 128 lanes) until a step fits ``VMEM_BUDGET``.

Weight rows are fp32 in the simulation engine; narrower stacks are
accumulated in fp32 (preferred_element_type) and cast back.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import (ROW_BLOCK, compiler_params, fit_block_f,
                                  resolve_interpret)

DEFAULT_BLOCK_F = 2048


def plan_tiles(w: int, f: int, itemsize: int, out_itemsize: int,
               block_f: int):
    """(row block, row blocks, BF, VMEM bytes) of a [W, F] stack."""
    br = w if w <= ROW_BLOCK else ROW_BLOCK
    nr = -(-w // br)
    widen = itemsize < 4

    def footprint(bf):
        return (2 * br * w * 4 + 2 * w * bf * itemsize
                + (4 * w * bf if widen else 0) + 2 * br * bf * out_itemsize)

    return (br, nr) + fit_block_f(f, block_f, footprint)


def _kernel(p_ref, w_ref, o_ref, *widened):
    if widened:                              # narrow stack: widen once
        src = widened[0]

        @pl.when(pl.program_id(1) == 0)
        def _():
            src[...] = w_ref[...].astype(jnp.float32)
        w = src[...]
    else:
        w = w_ref[...].astype(jnp.float32)
    o_ref[...] = jax.lax.dot(
        p_ref[...], w, precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("out_dtype", "block_f", "interpret"))
def gossip_mix_pallas(P, w, *, out_dtype=None,
                      block_f: int = DEFAULT_BLOCK_F,
                      interpret=None):
    """P: [W, W]; w: [W, F] (any F — padded to the tile plan here).
    ``out_dtype``: store dtype (default w.dtype; accumulation is fp32
    regardless — int8 wire payloads pass out_dtype=f32 so the quantized
    grid never rounds the mix back through the wire dtype).
    ``interpret=None`` follows the platform (``resolve_interpret``)."""
    interpret = resolve_interpret(interpret)
    n, f = w.shape
    out_dtype = out_dtype or w.dtype
    narrow = jnp.dtype(w.dtype).itemsize < 4
    br, nr, bf, footprint = plan_tiles(
        n, f, jnp.dtype(w.dtype).itemsize, jnp.dtype(out_dtype).itemsize,
        block_f)
    fp = -(-f // bf) * bf
    out = pl.pallas_call(
        _kernel,
        grid=(fp // bf, nr),
        in_specs=[
            pl.BlockSpec((br, n), lambda i, r: (r, 0)),   # rows of P
            pl.BlockSpec((n, bf), lambda i, r: (0, i)),   # resident block
        ],
        out_specs=pl.BlockSpec((br, bf), lambda i, r: (r, i)),
        out_shape=jax.ShapeDtypeStruct((nr * br, fp), out_dtype),
        scratch_shapes=[pltpu.VMEM((n, bf), jnp.float32)] if narrow else [],
        compiler_params=compiler_params(footprint, interpret, "gossip_mix"),
        interpret=interpret,
    )(jnp.pad(P.astype(jnp.float32), ((0, nr * br - n), (0, 0))),
      jnp.pad(w, ((0, 0), (0, fp - f))))
    return out[:n, :f]
