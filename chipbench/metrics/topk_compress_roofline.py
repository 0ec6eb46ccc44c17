"""The fused top-k compression kernel's share of its roofline: the bytes
one call needs (gradients and residuals read, residuals written, packed
values and indices written) at the HBM peak, over its device time per
call in the trace. In percent; nothing when the kernel is not in the
trace."""

KEYS = ("topk", "fused_compress")


def read(ctx):
    secs = calls = 0
    for key in KEYS:
        s, c = ctx.trace.kernel(key)
        secs, calls = secs + s, calls + c
    if not calls:
        return None
    least = ctx.counters["kernel_bytes"] / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least * calls / secs
