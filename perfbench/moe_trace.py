"""Device time of the pod train step (``fl_step``) by the model's named
scopes, through ``program_trace.stages`` and the scope map the program
keeps of the step the ``pods_moe`` driver noted. None where the program
noted no such step or the window ran none."""
from __future__ import annotations

import program_trace


def scope_ms(ctx, scope: str):
    per = program_trace.stages(ctx, "fl_step")
    if per is None or not ctx["steps"]:
        return None
    return per.get(scope, 0.0) / 1e6 / ctx["steps"]
