"""Host time of the G1 and G2 subgroup checks per parsed statement, from
the program's `bls.subgroup_g1` and `bls.subgroup_g2` spans over its
`serve.parse` spans in the traced window (`telemetry.profiled_spans`); a
program without those counts gives nothing."""


def read(ctx):
    try:
        from consensus_specs_tpu.telemetry import profiled_spans
    except ImportError:
        return None
    spans = profiled_spans()
    parses = spans.get("serve.parse", {}).get("count")
    checks = [spans[k]["total_s"] for k in ("bls.subgroup_g1",
                                            "bls.subgroup_g2")
              if k in spans]
    return sum(checks) / parses * 1e3 if parses and checks else None
