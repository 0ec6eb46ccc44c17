"""Share of the reducer programs' device time (``jit_reduce_*`` runs in
the traced window) spent in ops under the named scope ``reduce/pack``:
the worker trees flattened and stacked with their residual rows. In
percent; nothing when no op of those programs carries a ``reduce/``
scope."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
from chipbench import spans  # noqa: E402

SCOPES = ("reduce/pack",)


def read(ctx):
    r = spans.read(ctx)
    return None if r is None else r.scope_share("jit_reduce_", SCOPES)
