"""The sweep's share of its memory roofline, in %: the bytes it has to
move per step (computed from the registry's shapes) over the chip's peak
HBM bandwidth, divided by its device time per step.  This is the memory
leg alone: no published peak bounds the v5e's integer vector work, so the
compute leg is missing and the share reads at most the full roofline's."""

from benchmark import trace


def read(ctx):
    tr, c, peaks = ctx["trace"], ctx["counters"], ctx["peaks"]
    ns = trace.op_time_ns(tr, scopes=c["scopes"]["sweep"])
    steps = trace.span_count(tr, "bench.step")
    if not ns or not steps or not peaks:
        return None
    least_s = c["sweep_bytes_per_step"] / peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / (ns / 1e9 / steps)
