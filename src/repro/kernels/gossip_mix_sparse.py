"""gossip_mix_sparse — padded-CSR gossip aggregation as a Pallas kernel.

The dense ``gossip_mix`` kernel does ``P @ W`` with a [W, W] matmul per
parameter tile — O(W²·F) MXU work even though DeFTA topologies keep the
per-row peer count K = avg_peers + 1 ≪ W (paper §5: K≈5 at any world
size). This kernel takes the topology's padded-CSR form instead:

    idx: [W, K] int32 — row i's peer slots (padded rows repeat i)
    val: [W, K] f32   — mixing weights, 0.0 on padding / unsampled peers

and computes ``out[i] = Σ_k val[i, k] · stack[idx[i, k]]`` so HBM reads and
compute scale O(W·K·F) = O(nnz·F). Layout (one grid step = one parameter
block × one block of output rows):

* The stack's (W, BF) parameter block stays resident in VMEM while the
  row blocks sweep it (its block index changes only with the parameter
  axis, so it is fetched once per BF columns). A stack narrower than 32
  bits is widened into an f32 VMEM scratch once per parameter block:
  Mosaic loads single rows at a dynamic offset only from 32-bit memory.
* idx/val arrive one row block at a time in SMEM; each output row is K
  dynamic single-row loads and K scalar-times-vector FMAs on the VPU.
  There is no vector gather, which Mosaic does not lower.
* BF shrinks (down to 128 lanes) until a step fits ``VMEM_BUDGET``; the
  row block shrinks until its idx/val slots fit SMEM.
* Accumulation and the kernel's store are fp32; ``out_dtype`` is applied
  after the call (bf16 wire format composes, see core/gossip.py).

The pure-jnp contract is ``repro.kernels.ref.gossip_mix_sparse_ref``; the
dense kernel remains the oracle in tests and benchmarks.
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

# Fully unroll the peer loop up to this K: the unrolled load+FMA chain
# keeps the accumulator in registers, and compile time stays low in the
# sparse regime the kernel is auto-selected for (K = avg_peers + 1 ≪ W).
# Past the cap — near-dense topologies, where the dense kernel wins
# anyway — a fori_loop bounds compile time.
UNROLL_MAX_K = 128

SMEM_SLOTS = 8192    # (row, peer) slots of idx/val one step keeps in SMEM
SMEM_TILE = 1024     # Mosaic's tiling of a long rank-1 SMEM operand


def plan_tiles(w: int, k: int, f: int, itemsize: int, block_f: int):
    """(row block, row blocks, SMEM slots per row block, BF, VMEM bytes)
    of a [W, F] stack with K peer slots per row."""
    br = ROW_BLOCK
    while br > 8 and br * k > SMEM_SLOTS:
        br //= 2
    if w <= br:                              # one row block: full dims
        br = w
    nr = -(-w // br)
    slots = br * k if nr == 1 else -(-br * k // SMEM_TILE) * SMEM_TILE
    widen = itemsize < 4

    def footprint(bf):
        return (2 * w * bf * itemsize + (4 * w * bf if widen else 0)
                + 2 * br * bf * 4)

    return (br, nr, slots) + fit_block_f(f, block_f, footprint)


def _kernel(idx_ref, val_ref, w_ref, o_ref, *widened, k_slots, rows):
    src = w_ref
    if widened:                              # narrow stack: widen once
        src = widened[0]

        @pl.when(pl.program_id(1) == 0)
        def _():
            src[...] = w_ref[...].astype(jnp.float32)

    def row(i, carry):
        base = i * k_slots

        def body(k, acc):
            j = idx_ref[base + k]
            return acc + val_ref[base + k] * \
                src[pl.ds(j, 1), :].astype(jnp.float32)

        acc = jnp.zeros((1, o_ref.shape[1]), jnp.float32)
        if k_slots <= UNROLL_MAX_K:
            for k in range(k_slots):
                acc = body(k, acc)
        else:
            acc = jax.lax.fori_loop(0, k_slots, body, acc)
        o_ref[pl.ds(i, 1), :] = acc
        return carry

    jax.lax.fori_loop(0, rows, row, 0)


def gather_mix(idx, val, w, *, out_dtype, block_f: int, interpret):
    """out[i] = Σ_k val[i, k] · w[idx[i, k]] for any [W, F] stack (rows
    and columns are padded to the tile plan here and sliced back)."""
    interpret = resolve_interpret(interpret)
    n, f = w.shape
    k = idx.shape[1]
    br, nr, slots, bf, footprint = plan_tiles(
        n, k, f, jnp.dtype(w.dtype).itemsize, block_f)
    fp = -(-f // bf) * bf

    def flat_slots(a):                       # [W, K] -> [nr * slots]
        a = jnp.pad(a, ((0, nr * br - n), (0, 0))).reshape(nr, br * k)
        return jnp.pad(a, ((0, 0), (0, slots - br * k))).reshape(-1)

    smem = pl.BlockSpec((slots,), lambda i, r: (r,),
                        memory_space=pltpu.SMEM)
    out = pl.pallas_call(
        functools.partial(_kernel, k_slots=k, rows=br),
        grid=(fp // bf, nr),
        in_specs=[smem, smem,
                  pl.BlockSpec((n, bf), lambda i, r: (0, i))],
        out_specs=pl.BlockSpec((br, bf), lambda i, r: (r, i)),
        out_shape=jax.ShapeDtypeStruct((nr * br, fp), jnp.float32),
        scratch_shapes=[pltpu.VMEM((n, bf), jnp.float32)]
        if jnp.dtype(w.dtype).itemsize < 4 else [],
        compiler_params=compiler_params(footprint, interpret,
                                        "gossip_mix_sparse"),
        interpret=interpret,
    )(flat_slots(idx.astype(jnp.int32)), flat_slots(val.astype(jnp.float32)),
      jnp.pad(w, ((0, 0), (0, fp - f))))
    return out[:n, :f].astype(out_dtype)


@functools.partial(jax.jit,
                   static_argnames=("out_dtype", "block_f", "interpret"))
def gossip_mix_sparse_pallas(idx, val, w, *, out_dtype=None,
                             block_f: int = DEFAULT_BLOCK_F,
                             interpret=None):
    """idx: [W, K] int32; val: [W, K]; w: [W, F]. Returns [W, F] in
    ``out_dtype`` (default w's dtype; accumulation is fp32 regardless).
    ``block_f`` caps the parameter block; ``interpret=None`` follows the
    platform (``kernels.common.resolve_interpret``)."""
    return gather_mix(idx, val, w, out_dtype=out_dtype or w.dtype,
                      block_f=block_f, interpret=interpret)
