"""Median length of the program's ``mlitb.serve.decode`` spans in the
traced window: one decode dispatch, from building its inputs through the
read-back of its sampled tokens to the slots' bookkeeping, on the host's
clock. In milliseconds; nothing when the trace holds no such span."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
from chipbench import spans  # noqa: E402


def read(ctx):
    r = spans.read(ctx)
    return None if r is None else r.median_ms("serve.decode")
