"""Share of the recurrent-state rows the decode programs read and write
that belong to live requests: the sum of ``state_rows_live`` over the
sum of ``state_rows_walked`` of the program's ``mlitb.serve.decode``
spans in the traced window. In percent; nothing when the trace holds no
such span."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
from chipbench import spans  # noqa: E402


def read(ctx):
    r = spans.read(ctx)
    if r is None:
        return None
    live = r.arg_sum("serve.decode", "state_rows_live")
    walked = r.arg_sum("serve.decode", "state_rows_walked")
    if live is None or not walked:
        return None
    return 100.0 * live / walked
