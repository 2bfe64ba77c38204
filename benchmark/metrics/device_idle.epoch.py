"""Share of the traced window, in %, in which the chip ran no op."""

from benchmark import trace


def read(ctx):
    return trace.idle_share(ctx["trace"])
