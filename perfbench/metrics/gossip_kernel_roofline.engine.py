"""Share of its roofline that the padded-CSR gossip kernel reaches: the
least time its work needs on the chip (the larger of operations over peak
FLOP/s and bytes over peak bandwidth, ``yardstick.padded_csr_mix`` from
W, K, F and the wire dtype, per parameter leaf and round) over the device
time of the kernel's events in the trace, in percent."""
import sys

import numpy as np

import trace_reduce
import yardstick

KERNELS = ("gossip_mix_quant_pallas", "gossip_mix_sparse_pallas")


def read(ctx):
    ops = next(iter(ctx["trace"]["ops"].values()), [])
    ns = sum(o["dur"] for o in trace_reduce.kernel_ops(ops, KERNELS))
    if not ns or not ctx["rounds"]:
        return None
    g = ctx["gossip"]
    adj = np.asarray(g["adjacency"], bool) | np.eye(ctx["workers"],
                                                     dtype=bool)
    slots = int(adj.sum(axis=1).max())
    peaks = yardstick.chip_peaks(ctx["device_kind"])
    least, bounds = 0.0, set()
    for f in ctx["leaf_features"]:
        w = yardstick.padded_csr_mix(ctx["workers"], slots, f, g["wire"])
        t, bound = yardstick.roofline_time(w["ops"], w["bytes"], peaks)
        least += t
        bounds.add(bound)
    print(f"[perfbench] gossip_kernel_roofline bound: {sorted(bounds)}",
          file=sys.stderr)
    return 100.0 * least * ctx["rounds"] / (ns / 1e9)
