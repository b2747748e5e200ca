"""Device milliseconds per step of the pod train step's (``fl_step``) ops
that the latent attention produced (``jax.named_scope("mla")``, mapped by
the program's scope map; forward, recompute and backward). Prints the
table of all scopes to stderr."""
import moe_trace


def read(ctx):
    return moe_trace.scope_ms(ctx, "mla")
