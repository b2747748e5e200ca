"""Tile sweep of the flash-attention kernel at the latent-attention shape of
the ``moonlight.pods2.gossip4`` cell: 2 pods (vmapped) x 1 row x 16 heads x
8,192 positions, q.k width 192, v width 128, bfloat16, causal.

For every tiling (``block_q`` 256 / 512; ``block_kv`` 512 / 1,024 / 2,048;
the kv compute block; splash's fused backward kernel or the separate ``dq``
and ``dkv`` kernels the model takes) it times one layer's attention forward,
and its forward and backward (``jax.grad`` of q, k and v), as the median of
``--iters`` calls after a warm-up, each ending in ``block_until_ready``. The
pure-jnp blocked path (``attention.blocked_attention_jnp``, the path the TPU
took before the kernel) is timed the same way. So is the tiling that
``flash_tiling`` picks with float32 inputs: every matmul operand of the
kernel is then float32 (the scaled q, and the backward's p and ds, which it
rounds to the inputs' dtype), which bounds how much of its gain over the jnp
path comes from bfloat16 operands. Last, the picked tiling is checked
against the jnp path: the largest gap of the output and of each gradient,
over the largest magnitude of the jnp path's.

Run on one TPU chip; off the chip it exits with an error and no numbers:

    PYTHONPATH=src python benchmarks/flash_tiles.py [--iters 10] \\
        [--skip-sweep] [--out experiments/flash_tiles.jsonl]

``--skip-sweep`` times only the jnp path and the picked tiling.

Each row is printed as one JSON line and appended to ``--out``.
"""
from __future__ import annotations

import argparse
import dataclasses
import itertools
import json
import os
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.attention import (FLASH_BLOCK_KV, FLASH_BLOCK_KV_COMPUTE,
                                    FLASH_BLOCK_Q, blocked_attention_jnp,
                                    flash_attention_sdpa, flash_tiling)

PODS, ROWS, SEQ, HEADS, QK, V = 2, 1, 8192, 16, 192, 128


def tilings():
    """(block_q, block_kv, block_kv_compute, fused) of the sweep."""
    for bq, bkv, fuse in itertools.product((256, 512), (512, 1024, 2048),
                                           (False, True)):
        for bkc in sorted({512, bkv}):
            yield bq, bkv, bkc, fuse


def fused(tiles):
    """``tiles`` with splash's one fused backward kernel in place of the
    dq and dkv kernels."""
    return dataclasses.replace(tiles, use_fused_bwd_kernel=True,
                               block_q_dq=None, block_kv_dq=None)


def inputs(seed: int = 0, dtype=jnp.bfloat16):
    """q, k, v and an output cotangent at the cell's shape."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    shape = lambda d: (PODS, ROWS, SEQ, HEADS, d)
    draw = lambda key, d: jax.random.normal(key, shape(d), dtype)
    return draw(ks[0], QK), draw(ks[1], QK), draw(ks[2], V), draw(ks[3], V)


def programs(attend):
    """(forward, forward and backward) of ``attend`` over the pods vmap,
    jitted."""
    fwd = jax.jit(jax.vmap(attend))

    def loss(q, k, v, do):
        out = jax.vmap(attend)(q, k, v)
        return jnp.sum(out.astype(jnp.float32) * do.astype(jnp.float32))

    return fwd, jax.jit(jax.grad(loss, argnums=(0, 1, 2)))


def median_ms(fn, args, iters: int) -> float:
    jax.block_until_ready(fn(*args))                # compile and warm up
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    return statistics.median(times) * 1e3


def rel_gap(a, b) -> float:
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.abs(a - b).max() / np.abs(b).max())


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--skip-sweep", action="store_true")
    ap.add_argument("--out", default="experiments/flash_tiles.jsonl")
    args = ap.parse_args()
    if jax.default_backend() != "tpu":
        raise SystemExit("flash_tiles.py measures the TPU kernel: no TPU "
                         f"here (backend {jax.default_backend()!r})")
    device = jax.devices()[0].device_kind
    q, k, v, do = inputs()
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)

    def emit(row):
        row["device"] = device
        print(json.dumps(row), flush=True)
        with open(args.out, "a") as f:
            f.write(json.dumps(row) + "\n")

    jnp_fwd, jnp_grad = programs(blocked_attention_jnp)
    emit({"path": "jnp", "fwd_ms": median_ms(jnp_fwd, (q, k, v), args.iters),
          "fwd_bwd_ms": median_ms(jnp_grad, (q, k, v, do), args.iters)})

    def time_flash(row, tiles, args_):
        fwd, grad = programs(
            lambda q, k, v, tiles=tiles: flash_attention_sdpa(q, k, v,
                                                              tiles=tiles))
        try:
            row["fwd_ms"] = median_ms(fwd, args_[:3], args.iters)
            row["fwd_bwd_ms"] = median_ms(grad, args_, args.iters)
        except jax.errors.JaxRuntimeError as e:    # a tiling Mosaic refuses
            row["refused"] = f"{type(e).__name__}: {str(e)[:300]}"
        emit(row)

    picked = [(FLASH_BLOCK_Q, FLASH_BLOCK_KV, FLASH_BLOCK_KV_COMPUTE, False)]
    for bq, bkv, bkc, fuse in picked if args.skip_sweep else tilings():
        tiles = flash_tiling(SEQ, bq, bkv, bkc)
        time_flash({"path": "flash", "block_q": bq, "block_kv": bkv,
                    "block_kv_compute": bkc, "fused_bwd": fuse},
                   fused(tiles) if fuse else tiles, (q, k, v, do))

    # every matmul operand float32: the bound on bfloat16's share of the gain
    time_flash({"path": "flash_f32", "tiles": repr(flash_tiling(SEQ))},
               flash_tiling(SEQ), inputs(dtype=jnp.float32))

    # the tiling the model takes, against the jnp path
    fwd, grad = programs(flash_attention_sdpa)
    got = (fwd(q, k, v),) + tuple(grad(q, k, v, do))
    want = (jnp_fwd(q, k, v),) + tuple(jnp_grad(q, k, v, do))
    emit({"path": "check", "tiles": repr(flash_tiling(SEQ)),
          **{f"rel_gap_{n}": rel_gap(a, b)
             for n, a, b in zip(("out", "dq", "dk", "dv"), got, want)}})


if __name__ == "__main__":
    main()
