"""Host time to prepare and enqueue one RLC batch (limb packing, random
coefficients, the transfer and the dispatch), from the program's
`bls.prepare` and `bls.enqueue` spans in the traced window
(`telemetry.profiled_spans`), per prepared batch; a program without
those counts gives nothing."""


def read(ctx):
    try:
        from consensus_specs_tpu.telemetry import profiled_spans
    except ImportError:
        return None
    spans = profiled_spans()
    prep = spans.get("bls.prepare")
    if not prep:
        return None
    enqueue = spans.get("bls.enqueue", {}).get("total_s", 0.0)
    return (prep["total_s"] + enqueue) / prep["count"] * 1e3
