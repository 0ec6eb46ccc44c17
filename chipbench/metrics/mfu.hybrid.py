"""Model FLOP/s utilization of serving a hybrid Mamba2 / attention model:
the forward operations of the prompt and served tokens of the requests
completed in the window (``flops_hybrid``: the Mamba layers'
projections, conv and recurrence, attention's causal half, the MLPs and
the output head), over the window and the bf16 peak. In percent."""


def read(ctx):
    c = ctx.counters
    return 100.0 * c["window_flops"] / c["window_s"] / \
        ctx.peaks["bf16_flops_per_s"]
