"""Host spans in the profiler's own trace.

``span(name, **args)`` is a ``jax.profiler.TraceAnnotation`` named
``mlitb.<name>``: when a profile is being taken (``jax.profiler.start_trace``
or TensorBoard's capture) the span lands on the host plane of the same
``.xplane.pb`` as the device's operations, with ``args`` as its stats. When
no profile is being taken it is a shared no-op: the untraced path makes
no annotation object. Spans of one thread nest, and the nesting is the
parent link: ``mlitb.serve.decode`` inside ``mlitb.serve.step``.

An argument that costs more than an attribute read to compute is set only
while a profile is being taken::

    with obs.span("serve.step", queued=len(queue)) as sp:
        if obs.tracing():
            sp.set_metadata(live_rows=int(live.sum()))

Device-side names come from ``jax.named_scope`` inside the jitted programs
and from the programs' own function names (``jit_<name>``).
"""
from __future__ import annotations

import jax

PREFIX = "mlitb."


class _Off:
    """The span when no profile is being taken."""

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set_metadata(self, **args) -> None:
        pass


_OFF = _Off()


def tracing() -> bool:
    """Whether a profile is being taken (spans are recorded)."""
    return jax.profiler.TraceAnnotation.is_enabled()


def span(name: str, **args):
    """A host span ``mlitb.<name>`` carrying ``args`` as its stats."""
    if not tracing():
        return _OFF
    return jax.profiler.TraceAnnotation(PREFIX + name, **args)
