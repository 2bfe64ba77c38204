"""Registry keys the executor aggregated per statement it settled, over
the window and its drain, from its own `stats()` counters; a program
without the count gives nothing."""


def read(ctx):
    c = ctx["counters"]
    return (c["keys_aggregated"] / c["settled"]
            if c.get("keys_aggregated") and c.get("settled") else None)
