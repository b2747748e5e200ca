"""Milliseconds per step in which the device runs nothing while the host
is inside the program's ``defta:batch`` spans (``TokenBatcher.batch_at``
making the step's tokens); the host-to-device copy after it is not
inside."""
import program_trace


def read(ctx):
    return program_trace.wait_ms(ctx, "batch", "steps")
