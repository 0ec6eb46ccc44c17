"""Share of the traced window in which the device idled while the mesh
trainer's host code ran: the idle gaps whose innermost span is
``mlitb.train.step``, ``.mask`` or ``.dispatch`` (a gap under
``mlitb.train.readback`` is the host waiting for the device, and is not
counted). In percent; nothing when the trace holds none of those
spans."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
from chipbench import spans  # noqa: E402

NAMES = ("train.step", "train.mask", "train.dispatch")


def read(ctx):
    r = spans.read(ctx)
    return None if r is None else r.gap_share(NAMES)
