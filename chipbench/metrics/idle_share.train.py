"""Share of the traced window in which no operation ran on the device
under the train loop (averaged over the cell's chips). In percent."""


def read(ctx):
    return 100.0 * ctx.trace.idle_share
