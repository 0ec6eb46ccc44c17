"""Share of the traced window in which device 0 ran a collective (the
data-parallel all-reduce of the gradients) with no other op running
beside it: exposed collective time over the window. In percent; nothing
when the trace holds no collective."""


def read(ctx):
    t = ctx.trace
    if not t.collective_s:
        return None
    return 100.0 * t.collective_exposed_s / t.window_s
