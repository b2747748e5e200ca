"""Device milliseconds per step of the pod train step's ops in the three
MoE scopes (``moe_route``: router, balance loss and loads;
``moe_experts``: dispatch and the held experts' grouped matmuls;
``moe_shared``: the shared experts), each printed on stderr."""
import sys

import moe_trace


def read(ctx):
    parts = {s: moe_trace.scope_ms(ctx, s)
             for s in ("moe_route", "moe_experts", "moe_shared")}
    if any(v is None for v in parts.values()):
        return None
    for s, v in parts.items():
        print(f"[perfbench] {s}: {v:.3f} ms a step", file=sys.stderr)
    return sum(parts.values())
