"""Share of the traced window, in %, in which the chip ran no program, by
the TPU runtime's spans from enqueue to completion of each program run
(the verify cell traces the host only: see `benchmark/trace.py`)."""

from benchmark import trace


def read(ctx):
    return trace.idle_share(ctx["trace"])
