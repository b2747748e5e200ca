"""Share of its roofline that the held experts' grouped matmuls reach in
the pod train step: the least time their work needs on the chip (the
larger of operations over peak FLOP/s and bytes over peak bandwidth,
``moe_yardstick.grouped_matmul`` for the (token, expert) pairs the
program's counter reports each step and layer) over the device time of
the grouped-matmul ops in the trace, in percent. Every grouped-matmul op
of a step and layer does the same pairs' work (one weight of D x F), so
the least time is the number of such ops per step and layer times each
step and layer's least time."""
import sys

import numpy as np

import moe_yardstick
import trace_reduce
import yardstick

# the TPU's grouped matmuls: megablox ``gmm`` (rows x weights, and the
# rows' gradient) and ``tgmm`` (the weights' gradient); ``ragged-dot``
# where the program runs XLA's ragged dot instead
KERNELS = ("gmm", "tgmm", "ragged-dot")


def read(ctx):
    loads = ctx.get("held_loads")
    ops = next(iter(ctx["trace"]["ops"].values()), [])
    found = trace_reduce.kernel_ops(ops, KERNELS)
    if loads is None or not found or not ctx["steps"]:
        return None
    loads = np.asarray(loads)                  # [steps, pods, layers, held]
    steps, _, layers, _ = loads.shape
    per = len(found) / (steps * layers)        # ops a step and layer
    d, f, groups = ctx["expert_shape"]
    peaks = yardstick.chip_peaks(ctx["device_kind"])
    least, bounds = 0.0, set()
    for rows in loads.sum(axis=(1, 3)).ravel():    # pairs a step and layer
        w = moe_yardstick.grouped_matmul(int(rows), d, f, groups)
        t, bound = yardstick.roofline_time(w["ops"], w["bytes"], peaks)
        least += t
        bounds.add(bound)
    ns = sum(o["dur"] for o in found)
    print(f"[perfbench] expert_gmm_roofline: {len(found)} ops, {per:.2f} a "
          f"step and layer, bound {sorted(bounds)}", file=sys.stderr)
    return 100.0 * per * least / (ns / 1e9)
