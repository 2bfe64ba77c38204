"""Statements the executor settled per device batch over the window and
its drain, from the executor's own `stats()` counters."""


def read(ctx):
    c = ctx["counters"]
    return c["settled"] / c["batches"] if c.get("batches") else None
