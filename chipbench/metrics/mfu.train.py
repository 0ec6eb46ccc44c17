"""Model FLOP/s utilization of the train step: the forward and backward
operations a trained token needs (recomputation not counted), times the
window's tokens per second, over the chips' bf16 peak. In percent."""


def read(ctx):
    c = ctx.counters
    rate = c["tokens"] / c["window_s"]
    return 100.0 * rate * c["flops_per_token"] / (
        c["chips"] * ctx.peaks["bf16_flops_per_s"])
