"""Model FLOP utilization of the pod trainer: 6·N·tokens per second of the
window (all pods, gossip steps included) over the chips' bf16 peak, in
percent. N is the configuration's parameter count; attention scores and
rematerialized recompute are not counted."""
import yardstick


def read(ctx):
    if not ctx["steps"]:
        return None
    peak = yardstick.chip_peaks(ctx["device_kind"])["bf16_flops"]
    rate = ctx["steps"] * ctx["tokens_per_step"] / ctx["window"].seconds
    return 100.0 * yardstick.train_flops_per_token(ctx["n_params"]) * rate \
        / (ctx["chips"] * peak)
