"""Mean host time of one wire parse of a FastAggregateVerify statement
(decompression and the G1 and G2 subgroup checks), from the program's own
`serve.parse` spans in the traced window: the inside counterpart of
`submit_ms`.  The program counts its spans while a profiler session
records (`telemetry.profiled_spans`); a program without that count gives
nothing."""


def read(ctx):
    try:
        from consensus_specs_tpu.telemetry import profiled_spans
    except ImportError:
        return None
    s = profiled_spans().get("serve.parse")
    return s["total_s"] / s["count"] * 1e3 if s else None
