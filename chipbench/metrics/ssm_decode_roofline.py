"""The decode program's Mamba mixers' share of their roofline: the bytes
they need over the decode dispatches in the traced window (their weights
once a dispatch, and the h state and conv ring of the live rows, read
and written: ``flops_hybrid.ssm_decode_bytes`` of each
``mlitb.serve.decode`` span's ``state_rows_live``) at the HBM peak, over
the device time of the ops under the named scope ``serve/ssm`` in the
decode program (``jit_decode_paged``). In percent; nothing when the
trace holds no such span or scope."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
from chipbench import flops_hybrid, spans  # noqa: E402

PROGRAM = "jit_decode_paged"
SCOPE = "serve/ssm"


def read(ctx):
    r = spans.read(ctx)
    if r is None:
        return None
    live = [s.args["state_rows_live"] for s in r.named("serve.decode")
            if "state_rows_live" in s.args]
    secs = sum(s for (prog, path), s in r.scope_s.items()
               if prog.startswith(PROGRAM) and spans.under(path, SCOPE))
    if not live or not secs:
        return None
    model = flops_hybrid.Hybrid.of(ctx.run.config)
    need = sum(flops_hybrid.ssm_decode_bytes(model, int(n)) for n in live)
    return 100.0 * need / ctx.peaks["hbm_bytes_per_s"] / secs
