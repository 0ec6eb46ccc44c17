"""Output tokens of the requests that completed inside the window, over
the window. Below the knee it follows the order in which the seed lays
out the requests (which of them finish before the window closes), so it
is read here, beside the tail, and judged end to end only in a cell above
the knee."""


def read(ctx):
    return ctx.counters["completed_tokens_per_s"]
