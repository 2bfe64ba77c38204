"""Reduction of a JAX profiler trace (`.xplane.pb`) to device ops and
host spans, and the arithmetic the per-layer readers share.

Device ops are the events of a TPU device plane's "XLA Ops" line
(`/device:TPU:<n>`, where an op event is named by its HLO instruction, as
`%fusion.12 = ...`), and program executions those of its "XLA Modules"
line.  The scope of an op, the `jax.named_scope` path that XLA keeps as
the instruction's `op_name`, is looked up in the compiled program's text
(`hlo_op_scopes`).  Host spans are the harness's own `bench.*`
annotations.  A trace with no device plane, as one taken on the CPU, has
no ops.

A trace taken in the TPU's host-only mode has no device plane either.
There the program runs are read from the TPU runtime's own spans on the
host: a run starts when the runtime enqueues the program
(`DoEnqueueProgram`) and ends when it completes it (`CompleteCallbacks`),
matched by `run_id`, or, where the device is still busy with the run
before, when that one completes.  Such a run covers the device's execution
and the runtime's completion poll.
"""

from __future__ import annotations

import glob
import os
import re
import shutil
import tempfile
import time
from typing import NamedTuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
ENQUEUE, COMPLETE = "DoEnqueueProgram", "CompleteCallbacks"
WINDOW_SPAN = "bench.window"
_OP_NAME = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?metadata=\{[^}]*?op_name=\"([^\"]*)\"")


class Op(NamedTuple):
    device: str
    name: str
    scope: str
    start_ns: float
    dur_ns: float


class Span(NamedTuple):
    name: str
    start_ns: float
    dur_ns: float


class Trace(NamedTuple):
    ops: list            # [Op], of the window
    modules: list        # [Op], one per program execution of the window
    spans: list          # [Span], the harness's, inside the window
    window: tuple        # (start_ns, end_ns) on the clock of ops and modules
    devices: list        # device names that ran an op


class Tracer:
    """The JAX profiler over a window, from its start to `stop()` or to
    the first `poll()` after `seconds`, whichever comes first; the traced
    stretch is the `bench.window` span.  The trace goes to a fresh
    directory under TMPDIR."""

    def __init__(self, seconds: float | None = None,
                 tpu_trace_mode: str | None = None):
        import jax

        self.seconds = seconds
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        # Python function tracing would slow the host path under test
        opts.python_tracer_level = 0
        if tpu_trace_mode:
            opts.advanced_configuration = {"tpu_trace_mode": tpu_trace_mode}
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self.span.__enter__()
        self.t0 = time.perf_counter()
        self.active = True

    def poll(self) -> None:
        if (self.active and self.seconds is not None
                and time.perf_counter() - self.t0 >= self.seconds):
            self.stop()

    def stop(self) -> None:
        import jax

        if self.active:
            self.active = False
            self.span.__exit__(None, None, None)
            jax.profiler.stop_trace()

    def path(self) -> str:
        files = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                          recursive=True)
        if len(files) != 1:
            raise RuntimeError(f"{len(files)} .xplane.pb files under "
                               f"{self.dir}")
        return files[0]

    def remove(self) -> None:
        shutil.rmtree(self.dir, ignore_errors=True)


class _NoTracer:
    def poll(self) -> None:
        pass


NO_TRACER = _NoTracer()


def _stats(event) -> dict:
    out = {}
    for key, value in event.stats:
        out.setdefault(key, value)
    return out


def hlo_op_scopes(hlo_text: str) -> dict:
    """{instruction name: op_name} from a compiled program's HLO text."""
    out = {}
    for line in hlo_text.splitlines():
        m = _OP_NAME.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def instruction(op_name: str) -> str:
    """`%fusion.12 = s32[...] fusion(...)` -> `fusion.12`."""
    return op_name.split(" = ", 1)[0].lstrip("%")


def read(path: str, op_scopes: dict | None = None) -> Trace:
    """The ops and program executions of the `bench.window` span of the
    trace at `path`, and the harness spans inside it; `op_scopes` maps an
    instruction name to its scope path."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    scopes = op_scopes or {}
    ops, modules, spans = [], [], []
    enqueued, completed = {}, {}
    device_planes = [p for p in data.planes if p.name.startswith("/device:")
                     and any(line.name in (OPS_LINE, MODULES_LINE)
                             for line in p.lines)]
    for plane in data.planes:
        on_device = plane.name.startswith("/device:")
        for line in plane.lines:
            if on_device and line.name == OPS_LINE:
                for ev in line.events:
                    name = instruction(ev.name)
                    ops.append(Op(plane.name, name, scopes.get(name, ""),
                                  ev.start_ns, ev.duration_ns))
            elif on_device and line.name == MODULES_LINE:
                for ev in line.events:
                    modules.append(Op(plane.name, ev.name, "",
                                      ev.start_ns, ev.duration_ns))
            elif not on_device:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append(Span(ev.name, ev.start_ns,
                                          ev.duration_ns))
                    elif ev.name in (ENQUEUE, COMPLETE):
                        st = _stats(ev)
                        if "run_id" in st:
                            key = (st.get("device_ordinal", 0), st["run_id"])
                            if ev.name == ENQUEUE:
                                enqueued[key] = ev.start_ns
                            else:
                                completed[key] = ev.start_ns + ev.duration_ns
    if not device_planes:
        # a device runs its queue in order: a run starts when it is
        # enqueued or when the run before it completes, whichever is later
        free_at: dict = {}
        for key, start in sorted(enqueued.items(), key=lambda kv: kv[1]):
            if key in completed:
                start = max(start, free_at.get(key[0], start))
                free_at[key[0]] = completed[key]
                modules.append(Op(f"/device:TPU:{key[0]}", f"run {key[1]}",
                                  "", start, completed[key] - start))
    windows = [s for s in spans if s.name == WINDOW_SPAN]
    if len(windows) != 1:
        raise RuntimeError(f"{len(windows)} {WINDOW_SPAN} spans in {path}")
    lo = windows[0].start_ns
    hi = lo + windows[0].dur_ns
    if device_planes:
        # A device plane keeps its own clock, which the profiler aligns
        # with the host's only to about a millisecond: a program's first op
        # can read as starting before the host launched it.  The harness
        # starts the profiler right before the window span and stops it
        # right after, with the device idle, so every device-plane event
        # belongs to the window, and the window on the device's clock
        # starts at the earlier of the span's start and the first event.
        start = min([lo] + [e.start_ns for e in ops + modules])
        window = (start, start + hi - lo)
    else:
        window = (lo, hi)
        modules = [m for m in modules if m.start_ns < hi
                   and m.start_ns + m.dur_ns > lo]
    return Trace(ops=ops, modules=modules,
                 spans=[s for s in spans if s.start_ns >= lo
                        and s.start_ns + s.dur_ns <= hi
                        and s.name != WINDOW_SPAN],
                 window=window,
                 devices=sorted({o.device for o in ops + modules}))


def whole_runs(trace: Trace) -> list:
    """The program runs that lie wholly inside the window."""
    lo, hi = trace.window
    return [m for m in trace.modules
            if m.start_ns >= lo and m.start_ns + m.dur_ns <= hi]


def busy_events(trace: Trace) -> list:
    """The events that mark the device busy: its ops where the trace has
    them, else its program executions."""
    return trace.ops or trace.modules


def union_ns(intervals) -> float:
    """Length of the union of (start, duration) intervals."""
    total, end = 0.0, None
    for start, dur in sorted(intervals):
        stop = start + dur
        if end is None or start > end:
            total += dur
            end = stop
        elif stop > end:
            total += stop - end
            end = stop
    return total


def _clipped(trace: Trace, device: str) -> list:
    """(start, duration) of the device's busy events, cut to the window."""
    lo, hi = trace.window
    out = []
    for o in busy_events(trace):
        start, stop = max(o.start_ns, lo), min(o.start_ns + o.dur_ns, hi)
        if o.device == device and stop > start:
            out.append((start, stop - start))
    return out


def busy_ns(trace: Trace) -> float:
    """Time of the window in which an op ran, averaged over the devices
    used."""
    if not trace.devices:
        return 0.0
    return sum(union_ns(_clipped(trace, d))
               for d in trace.devices) / len(trace.devices)


def window_ns(trace: Trace) -> float:
    return trace.window[1] - trace.window[0]


def idle_gaps(trace: Trace, device: str):
    """(start, length) of each stretch of the window in which `device` ran
    no op."""
    gaps, cursor = [], trace.window[0]
    for start, dur in sorted(_clipped(trace, device)):
        if start > cursor:
            gaps.append((cursor, start - cursor))
        cursor = max(cursor, start + dur)
    if trace.window[1] > cursor:
        gaps.append((cursor, trace.window[1] - cursor))
    return gaps


def span_at(trace: Trace, t_ns: float) -> str:
    """The innermost harness span open at `t_ns`, or 'no span'."""
    best = None
    for s in trace.spans:
        if s.start_ns <= t_ns <= s.start_ns + s.dur_ns and (
                best is None or s.dur_ns < best.dur_ns):
            best = s
    return best.name if best else "no span"


def span_count(trace: Trace, name: str) -> int:
    return sum(s.name == name for s in trace.spans)


def self_times(ops) -> list:
    """Each op's own time: its duration less that of the ops nested in it
    (a `while` op's event spans the events of its body's ops)."""
    order = sorted(range(len(ops)),
                   key=lambda i: (ops[i].device, ops[i].start_ns,
                                  -ops[i].dur_ns))
    own = [o.dur_ns for o in ops]
    stack = []
    for i in order:
        o = ops[i]
        while stack and (ops[stack[-1]].device != o.device
                         or ops[stack[-1]].start_ns + ops[stack[-1]].dur_ns
                         <= o.start_ns):
            stack.pop()
        if stack:
            own[stack[-1]] -= o.dur_ns
        stack.append(i)
    return own


def op_time_ns(trace: Trace, scopes) -> float:
    """Device time of the ops under any of `scopes` (named-scope path
    components), each op counted for its own time."""
    return sum(t for o, t in zip(trace.ops, self_times(trace.ops))
               if any(s in o.scope.split("/") for s in scopes))


def breakdown(trace: Trace, top: int = 10) -> dict:
    """The ops that took the most device time of their own (or the program
    runs, where the trace has no ops), and the idle time by the harness
    span that was open on the host, each as [name, seconds]."""
    events = busy_events(trace)
    by_op: dict[str, float] = {}
    for o, t in zip(events, self_times(events)):
        key = o.name if trace.ops else "program run"
        if o.scope:
            key = "/".join(p for p in o.scope.split("/")
                           if p.startswith("cst.")) + ":" + o.name
        by_op[key] = by_op.get(key, 0.0) + t
    by_span: dict[str, float] = {}
    for device in trace.devices:
        for start, length in idle_gaps(trace, device):
            label = span_at(trace, start + length / 2)
            by_span[label] = by_span.get(label, 0.0) + length / len(
                trace.devices)
    return {
        "device_ops": [[k, v / 1e9] for k, v in sorted(
            by_op.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[k, v / 1e9] for k, v in sorted(
            by_span.items(), key=lambda kv: -kv[1])[:top]],
    }


def idle_share(trace: Trace):
    """Share of the window, in %, in which the devices ran no op; None
    where no op ran."""
    if not trace.devices:
        return None
    return 100.0 * (1.0 - busy_ns(trace) / window_ns(trace))
