"""Device time per epoch step of the ops under the balances-root, the
record-root and the registry-root scopes."""

from benchmark import trace


def read(ctx):
    tr, c = ctx["trace"], ctx["counters"]
    ns = trace.op_time_ns(tr, scopes=c["scopes"]["merkle"])
    steps = trace.span_count(tr, "bench.step")
    return ns / 1e6 / steps if ns and steps else None
