"""The paged flash-decode kernel's share of its roofline: the bytes the
decode dispatches inside the traced span need (K and V of the live
context, counted in whole pages from the engine's StepReport, plus q read
and the output written, every layer) at the HBM peak, over the kernel's
device time in the trace. In percent; nothing when the kernel is not in
the trace or no decode ran in it."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
from chipbench import flops  # noqa: E402


def read(ctx):
    secs, calls = ctx.trace.kernel("flash_decode")
    on, off = ctx.run.traced_ns
    c = ctx.counters
    pages = [p for t0, t1, p in c["steps"] if t0 >= on and t1 <= off and p]
    if not calls or not pages:
        return None
    model = flops.Dense.of(ctx.run.config)
    need = sum(flops.decode_bytes(model, p * c["page_size"], c["max_batch"])
               for p in pages)
    return 100.0 * need / ctx.peaks["hbm_bytes_per_s"] / secs
