"""The master step's share of its roofline: the least time the bytes a
step needs take at the HBM peak (gradients and residuals read, residuals
written, parameters and accumulator read and written, the reduced
gradient written; its operations are far below the compute peak's
bound), over the measured time per step. In percent."""


def read(ctx):
    c = ctx.counters
    step_s = c["window_s"] / c["attempted"]
    return 100.0 * c["step_bytes"] / ctx.peaks["hbm_bytes_per_s"] / step_s
