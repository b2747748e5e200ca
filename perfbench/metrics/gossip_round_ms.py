"""Device milliseconds per gossip round of the pod gossip-round program
(``engine.build_pod_round``, jitted as ``gossip_round``): int8 encode,
EF21, the mix and the trust update, from the trace."""
import trace_reduce


def read(ctx):
    mods = next(iter(ctx["trace"]["modules"].values()), [])
    ns = trace_reduce.module_time(mods, "gossip_round")
    n = ctx["gossip_rounds"]
    return ns / 1e6 / n if ns and n else None
