"""Max over mean of the (token, expert) pairs each held expert computed,
per step, pod and MoE layer, from the program's per-step counter; the
mean over the window's steps, pods and layers (1 = even)."""
import numpy as np


def read(ctx):
    loads = ctx.get("held_loads")
    if loads is None or not len(loads):
        return None
    loads = np.asarray(loads, np.float64)      # [steps, pods, layers, held]
    mean = loads.mean(-1)
    return float(np.mean(loads.max(-1) / np.maximum(mean, 1e-30)))
