"""Model FLOP utilization of the rounds: the FLOPs one round's local
training requires (forward + backward of every SGD step of every worker,
``yardstick.engine_round_train_flops``) times rounds per second of the
window, over the chips' bf16 peak, in percent. Time-machine compensation
training and evaluation are not counted."""
import yardstick


def read(ctx):
    if not ctx["rounds"]:
        return None
    peak = yardstick.chip_peaks(ctx["device_kind"])["bf16_flops"]
    rate = ctx["rounds"] / ctx["window"].seconds
    return 100.0 * ctx["round_train_flops"] * rate / (ctx["chips"] * peak)
