"""Share of the KV pages the paged decode programs walk that hold live
tokens: the sum of ``kv_pages_live`` over the sum of ``kv_pages_walked``
of the program's ``mlitb.serve.decode`` spans in the traced window. In
percent; nothing when the trace holds no such span."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
from chipbench import spans  # noqa: E402


def read(ctx):
    r = spans.read(ctx)
    if r is None:
        return None
    live = r.arg_sum("serve.decode", "kv_pages_live")
    walked = r.arg_sum("serve.decode", "kv_pages_walked")
    if live is None or not walked:
        return None
    return 100.0 * live / walked
