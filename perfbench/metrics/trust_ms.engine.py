"""Device milliseconds per round of the ``jit_run_chunk`` ops that DTS
produced: the ``damage_check`` and ``trust_update`` stages
(``program_trace.stages``)."""
import program_trace


def read(ctx):
    return program_trace.stage_ms(ctx, "damage_check", "trust_update")
