"""Device milliseconds per step of the pod train-step program
(``steps.build_fl_train_step``, jitted as ``fl_step``), from the trace."""
import trace_reduce


def read(ctx):
    mods = next(iter(ctx["trace"]["modules"].values()), [])
    ns = trace_reduce.module_time(mods, "fl_step")
    return ns / 1e6 / ctx["steps"] if ns and ctx["steps"] else None
