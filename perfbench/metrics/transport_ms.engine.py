"""Device milliseconds per round of the ``jit_run_chunk`` ops that the
``transport`` stage produced: the int8 encode, EF21 and the padded-CSR
gossip kernel (``program_trace.stages``)."""
import program_trace


def read(ctx):
    return program_trace.stage_ms(ctx, "transport")
