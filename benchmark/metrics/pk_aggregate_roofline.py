"""The committee aggregation program's share of its memory roofline, in
%: the bytes one batch has to move (the gathered keys and committee rows,
the ids and bits in, the affine sums out; computed by the system from the
registry's limb shapes) over the chip's peak HBM bandwidth, divided by
the program's device time per run (`pk_aggregate_ms`).  This is the
memory leg alone: no published peak bounds the v5e's integer vector work,
so the compute leg is missing and the share reads at most the full
roofline's."""

from benchmark import harness


def read(ctx):
    c, peaks = ctx["counters"], ctx["peaks"]
    if not peaks or not c.get("pk_aggregate_bytes_per_batch"):
        return None
    ms = harness.load_module(harness.BENCH_DIR / "metrics"
                             / "pk_aggregate_ms.py").read(ctx)
    if not ms:
        return None
    least_s = c["pk_aggregate_bytes_per_batch"] / peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / (ms / 1e3)
