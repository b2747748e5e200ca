"""Device milliseconds per round of the ``jit_run_chunk`` ops that the
``local_train`` stage produced (``program_trace.stages``: each op's HLO
instruction mapped to its ``jax.named_scope`` stage by the program's own
scope map). Prints the table of all stages to stderr."""
import program_trace


def read(ctx):
    return program_trace.stage_ms(ctx, "local_train")
