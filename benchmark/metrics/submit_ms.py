"""Mean host time of one `submit_fast_aggregate_verify` call (the wire
parse with decompression and the G1 and G2 subgroup checks), from the
harness's `bench.submit` spans in the traced window."""


def read(ctx):
    spans = [s.dur_ns for s in ctx["trace"].spans if s.name == "bench.submit"]
    return sum(spans) / len(spans) / 1e6 if spans else None
