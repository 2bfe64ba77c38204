"""Time per run of the RLC batch-verify kernel: the mean length of a
program run in the traced window.  The verify cell runs one program, the
RLC kernel of `ops/bls_batch` at its 512 rung.  Its runs are the TPU
runtime's own spans on the host, from enqueue to completion (see
`benchmark/trace.py`), so they include the runtime's completion poll: one
batch's 4 * 10**6 device ops overflow the profiler's device plane.  A run
cut by the window's edge is left out."""

from benchmark import trace


def read(ctx):
    runs = [m.dur_ns for m in trace.whole_runs(ctx["trace"])]
    return sum(runs) / len(runs) / 1e6 if runs else None
