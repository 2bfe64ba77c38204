"""Device time per run of the committee aggregation program (`pk_aggregate`
of `ops/bls_batch/registry.py`), in ms: the mean length of its runs in the
traced window.

The cell traces the host only, where the TPU runtime names a program run
by its `run_id` alone (see `benchmark/trace.py`).  Each batch enqueues the
aggregation program and then its RLC program on one in-order queue, so the
window's whole runs, taken in start order, alternate between the two.
The aggregation runs are the shorter kind: each run is put in the kind
whose median it lies nearer to (on a log scale), and the reader gives the
mean of the shorter kind.  Where the runs do not alternate, or the two
kinds' medians lie within 2x of each other, it gives nothing."""

import math
import statistics

from benchmark import trace


def short_runs_ns(runs_ns: list):
    """The shorter runs of an alternating sequence, or None."""
    if len(runs_ns) < 2:
        return None
    even, odd = runs_ns[0::2], runs_ns[1::2]
    short, long_ = sorted((statistics.median(even), statistics.median(odd)))
    if short <= 0 or long_ < 2 * short:
        return None
    cut = math.sqrt(short * long_)
    kinds = [d < cut for d in runs_ns]
    if any(a == b for a, b in zip(kinds, kinds[1:])):
        return None
    return [d for d, is_short in zip(runs_ns, kinds) if is_short]


def read(ctx):
    runs = sorted(trace.whole_runs(ctx["trace"]), key=lambda m: m.start_ns)
    short = short_runs_ns([m.dur_ns for m in runs])
    return sum(short) / len(short) / 1e6 if short else None
