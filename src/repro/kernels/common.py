"""What every Pallas kernel of the package shares: the interpret-mode rule
and the VMEM tiling budget of the gossip kernels.

Interpret mode is chosen here and nowhere else. On a TPU every kernel
compiles through Mosaic; on the CPU (the tests, rehearsals without a chip) it
runs in the Pallas interpreter. Any other backend is an error — there is
no silent fallback to the interpreter or to ``kernels/ref.py``.
"""
from __future__ import annotations

from typing import Optional

import jax

# Bytes of VMEM one gossip-kernel grid step may hold (inputs and outputs
# double-buffered, plus any widening scratch). v5e's scoped VMEM default is
# 16 MiB; the tile planners shrink the parameter-axis block until the step
# fits this budget, and raise the scoped limit only when even the narrowest
# block (128 lanes) does not.
VMEM_BUDGET = 12 << 20
# Most VMEM a kernel may ask for (v5e holds 128 MiB per core).
VMEM_CAP = 100 << 20
LANES = 128
ROW_BLOCK = 128      # output rows per gossip-kernel grid step (at most)


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """``None`` → the platform's mode: compiled on TPU, interpreted on CPU.
    An explicit bool is kept (ahead-of-time TPU compiles from a CPU host
    pass ``False``)."""
    if interpret is not None:
        return bool(interpret)
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(f"Pallas kernels have no {backend!r} path: they "
                       f"compile on TPU and run interpreted on the CPU")


def pow2_lanes(n: int, cap: int) -> int:
    """Smallest power-of-two multiple of 128 lanes covering ``n``, clamped
    to [128, cap] (``cap`` rounded down to a power of two)."""
    cap = max(LANES, 1 << (max(cap, 1).bit_length() - 1))
    want = max(LANES, 1 << max(n - 1, 1).bit_length())
    return min(cap, want)


def fit_block_f(f: int, block_f: int, footprint):
    """(BF, its VMEM bytes): the widest parameter block, from
    ``pow2_lanes(f, block_f)`` halving down to 128 lanes, whose grid step
    ``footprint(bf)`` fits ``VMEM_BUDGET`` (128 lanes when none does)."""
    bf = pow2_lanes(f, block_f)
    while bf > LANES and footprint(bf) > VMEM_BUDGET:
        bf //= 2
    return bf, footprint(bf)


def compiler_params(footprint: int, interpret: bool, what: str):
    """Mosaic parameters for a grid of (parameter blocks, row blocks): the
    parameter axis is parallel, the row axis carries the resident tile in
    order. The scoped VMEM limit is raised to cover ``footprint``; a
    footprint past ``VMEM_CAP`` is refused here with the fix named."""
    if interpret:
        return None
    from jax.experimental.pallas import tpu as pltpu

    if footprint > VMEM_CAP:
        raise ValueError(
            f"{what}: one grid step needs {footprint / 2**20:.1f} MiB of "
            f"VMEM even at {LANES} lanes per block (cap "
            f"{VMEM_CAP / 2**20:.0f} MiB) — shard the worker axis "
            f"(run_defta(shards=...)) to bring the per-device W down")
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=max(16 << 20, footprint + (4 << 20)))
