"""Milliseconds per round in which the device runs nothing while the host
is inside the program's ``defta:evaluate`` spans (``core.defta.evaluate``
at each eval boundary): the test set's copy, the eager accuracy ops and
the fetch, as the device sees them."""
import program_trace


def read(ctx):
    return program_trace.wait_ms(ctx, "evaluate", "rounds")
