"""Backend compiles and compile-cache loads that ended inside the traced
window, counted from the program's ``defta:compile`` markers; None unless
the program's compile listener ran in this process."""
import program_trace


def read(ctx):
    return program_trace.compiles(ctx)
