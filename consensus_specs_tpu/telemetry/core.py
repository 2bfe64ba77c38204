"""Process-global telemetry registry: spans, counters, histograms.

Gating contract: everything here is OFF unless `CST_TELEMETRY` is set to
a non-empty value other than "0" (or `CST_TRACE_FILE` names an output
path, which implies collection), and the disabled paths are engineered
to stay off the profile — `span()` returns a shared no-op context
manager and `count()`/`observe()` are a single global-flag check.  The
hot path (per-kernel dispatch in `ops.bls_batch`) therefore instruments
unconditionally and lets this module decide.

A span has two halves with separate gates.  The profiler half: while a
JAX profiler session records, every span enters a
`jax.profiler.TraceAnnotation` named `cst.<span name>`, registry on or
off, so the program's sections land in the profiler's trace on the
clock it aligns with the device; its count and total are kept too
(`profiled_spans()`).  With no session and the registry off a span is
one check of the profiler's state and the shared no-op object.  We
never import jax ourselves: a telemetry layer must not initialize a
backend.

Enabled, the registry is a process singleton guarded by one lock:

- spans     nestable wall-time sections (thread-local nesting stack),
            aggregated by name and appended to a bounded trace-event
            buffer for the Chrome/Perfetto exporter.
- counters  monotonically increasing ints (routing decisions, lane
            accounting, cache stats).
- histograms count/total/min/max summaries of float samples (kernel
            compile-vs-run latencies, MSM sizes).

`first_call(key)` backs the compile-vs-run attribution: the first
dispatch of a given (kernel, padded-shape) pair pays trace+XLA-compile
(or a persistent-cache load), every later dispatch is pure run — so the
instrumentation routes the first wall sample to `kernel.compile_first_s`
and the rest to `kernel.run_s`, which is exactly the split the bench
JSON contract reports (`export.bench_block`).
"""

from __future__ import annotations

import os
import sys
import threading
import time

# trace-event buffer cap: ~100 bytes/event keeps worst case ~20 MB and
# bounds a runaway span loop; drops are counted, never silent
_MAX_EVENTS = 200_000

_lock = threading.Lock()
_tls = threading.local()

_T0 = time.perf_counter()   # chrome-trace timestamp origin (process)

_counters: dict[str, int] = {}
_hists: dict[str, dict] = {}
_spans: dict[str, dict] = {}
_events: list[dict] = []
_events_dropped = 0
_meta: dict[str, object] = {}
_first_keys: set[str] = set()
_gauges: dict[str, dict] = {}
_gauge_events: list[dict] = []
_gauge_events_dropped = 0
# {span name: [count, total_s]} of the spans that opened and closed while
# a profiler session recorded
_profiled: dict[str, list] = {}


def _env_enabled() -> bool:
    if os.environ.get("CST_TELEMETRY", "0") not in ("", "0"):
        return True
    return bool(os.environ.get("CST_TRACE_FILE"))


_enabled = _env_enabled()
_trace_file = os.environ.get("CST_TRACE_FILE") or None
_atexit_registered = False


def _register_atexit() -> None:
    global _atexit_registered
    if _atexit_registered or not _trace_file:
        return
    _atexit_registered = True
    import atexit

    from .export import write_chrome_trace

    atexit.register(lambda: write_chrome_trace(_trace_file))


if _trace_file:
    _register_atexit()


def enabled() -> bool:
    """True when the registry is collecting (CST_TELEMETRY / CST_TRACE_FILE
    or an explicit `configure(enabled=True)`)."""
    return _enabled


def configure(enabled: bool | None = None,
              trace_file: str | None = None) -> None:
    """Programmatic override of the env gate (benches and tests).
    `trace_file` arms the atexit Chrome-trace writer and implies
    collection."""
    global _enabled, _trace_file
    if trace_file is not None:
        _trace_file = trace_file
        _enabled = True
        _register_atexit()
    if enabled is not None:
        _enabled = enabled


def reset(full: bool = False) -> None:
    """Clear the per-config aggregates (counters, histograms, span
    stats) — how the benches isolate per-config telemetry blocks.
    Process-level state survives by default: the trace-event timeline
    (the whole-process CST_TRACE_FILE export), the first-call keys
    (compile attribution is per-process — a kernel compiled during one
    config must not be re-counted as a compile by the next), and the
    meta entries (cache dir etc., recorded once at setup and owed to
    every config's export), and the profiled-span totals.  `full=True`
    wipes those too (test isolation).  The enabled flag and trace-file
    arming are always unaffected."""
    global _events_dropped, _gauge_events_dropped
    with _lock:
        _counters.clear()
        _hists.clear()
        _spans.clear()
        _gauges.clear()
        if full:
            _meta.clear()
            _events.clear()
            _first_keys.clear()
            _profiled.clear()
            _events_dropped = 0
            _gauge_events.clear()
            _gauge_events_dropped = 0
    if full:
        # cost records and watermarks are process-level facts (like the
        # first-call keys they attribute against): per-config resets
        # keep them, full test-isolation resets wipe them too
        from . import costmodel
        costmodel._reset_state()
        # request-trace lifecycle records follow the same rule: they
        # survive per-config resets (the Chrome-trace export is
        # whole-process), full resets wipe them and their id counters
        from . import reqtrace
        reqtrace._reset_state()
        # the live-monitoring layer is process-level too: a full reset
        # stops the SLO watchdog and the exposition endpoint so one
        # test's daemon threads never observe the next test's registry
        from . import metrics_export, monitor
        monitor._reset_state()
        metrics_export._reset_state()
        # the occupancy ledger and the incident event ring follow the
        # process-level rule as well: per-config resets keep them (a
        # run's incident evidence must survive its config loop), full
        # resets restore the env-derived gates and empty both
        from . import flightrec, occupancy
        occupancy._reset_state()
        flightrec._reset_state()


# --- recording primitives ---------------------------------------------------


def count(name: str, n: int = 1) -> None:
    if not _enabled:
        return
    with _lock:
        _counters[name] = _counters.get(name, 0) + n


def observe(name: str, value: float) -> None:
    if not _enabled:
        return
    v = float(value)
    with _lock:
        h = _hists.get(name)
        if h is None:
            _hists[name] = {"count": 1, "total": v, "min": v, "max": v}
        else:
            h["count"] += 1
            h["total"] += v
            if v < h["min"]:
                h["min"] = v
            if v > h["max"]:
                h["max"] = v


def gauge(name: str, value) -> None:
    """Point-in-time level sample (queue depth, in-flight batches):
    unlike `count` it can go DOWN, and unlike `observe` each sample is
    also a timeline event — the Chrome-trace exporter renders gauges as
    'C' (counter) tracks next to the device-memory watermarks, so a
    Perfetto capture of a serve run shows the queue breathing against
    the span timeline.  Aggregates (last/min/max/count) land in
    `snapshot()["gauges"]`."""
    if not _enabled:
        return
    v = float(value)
    t = time.perf_counter()
    global _gauge_events_dropped
    with _lock:
        g = _gauges.get(name)
        if g is None:
            _gauges[name] = {"last": v, "min": v, "max": v, "count": 1}
        else:
            g["last"] = v
            g["count"] += 1
            if v < g["min"]:
                g["min"] = v
            if v > g["max"]:
                g["max"] = v
        if len(_gauge_events) < _MAX_EVENTS:
            _gauge_events.append({"name": name, "value": v,
                                  "ts": (t - _T0) * 1e6})
        else:
            _gauge_events_dropped += 1


def set_meta(key: str, value) -> None:
    if not _enabled:
        return
    with _lock:
        _meta[key] = value


def counter_value(name: str, default: int = 0) -> int:
    """One counter's current value — cheap point read, no registry
    copy (use `snapshot()` for the full picture)."""
    with _lock:
        return _counters.get(name, default)


def span_seconds(name: str, default: float = 0.0) -> float:
    """One span aggregate's cumulative `total_s` — cheap point read.
    Backs delta accounting (tests/conftest.py reads `spec.build` before
    and after each test to split its wall into phases)."""
    with _lock:
        s = _spans.get(name)
        return s["total_s"] if s else default


def add_event(name: str, dur_s: float, **attrs) -> None:
    """Record an already-measured duration as if a span of that length
    just closed: aggregates under `name` and (buffer permitting) a
    trace event ending now, carrying `attrs` as args.  For derived
    timings that were never a live `span()` — e.g. the per-test
    spec-build/test-body phase split, computed from deltas after the
    test ran."""
    if not _enabled:
        return
    dur = max(float(dur_s), 0.0)
    t1 = time.perf_counter()
    global _events_dropped
    with _lock:
        s = _spans.get(name)
        if s is None:
            _spans[name] = {"count": 1, "total_s": dur,
                            "min_s": dur, "max_s": dur}
        else:
            s["count"] += 1
            s["total_s"] += dur
            if dur < s["min_s"]:
                s["min_s"] = dur
            if dur > s["max_s"]:
                s["max_s"] = dur
        if len(_events) < _MAX_EVENTS:
            _events.append({
                "name": name,
                "ts": (t1 - dur - _T0) * 1e6,   # µs, process-relative
                "dur": dur * 1e6,
                "tid": threading.get_ident() & 0x7FFFFFFF,
                "args": dict(attrs),
            })
        else:
            _events_dropped += 1


def first_call(key: str) -> bool:
    """True exactly once per key per process (per `reset(full=True)`):
    the compile-vs-run discriminator for jitted kernel dispatches.
    Disabled mode is a flag check returning False — no lock, no key
    growth — like every other recording primitive."""
    if not _enabled:
        return False
    with _lock:
        if key in _first_keys:
            return False
        _first_keys.add(key)
        return True


# --- spans ------------------------------------------------------------------


class _NullSpan:
    """Shared no-op context manager handed out while disabled."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


def _span_stack() -> list:
    st = getattr(_tls, "stack", None)
    if st is None:
        st = _tls.stack = []
    return st


_annotation_cls = None     # jax.profiler.TraceAnnotation, once jax is in


def _recording():
    """`jax.profiler.TraceAnnotation` while a profiler session records in
    this process, else None.  Telemetry never imports jax itself: the
    bench parent that uses it must stay off JAX, so that its worker
    processes can hold the device."""
    global _annotation_cls
    cls = _annotation_cls
    if cls is None:
        jax = sys.modules.get("jax")
        if jax is None:
            return None
        try:
            cls = _annotation_cls = jax.profiler.TraceAnnotation
        except AttributeError:      # jax is still importing
            return None
    return cls if cls.is_enabled() else None


def _note_profiled(name: str, dur: float) -> None:
    with _lock:
        s = _profiled.get(name)
        if s is None:
            _profiled[name] = [1, dur]
        else:
            s[0] += 1
            s[1] += dur


def profiled_spans() -> dict:
    """{span name: {"count", "total_s"}} of the spans that opened and
    closed while a profiler session recorded, since the process started
    (or the last `reset(full=True)`): the same sections as the trace's
    `cst.*` host events, counted without reducing the trace."""
    with _lock:
        return {k: {"count": c, "total_s": t}
                for k, (c, t) in _profiled.items()}


class _ProfiledSpan:
    """The profiler half alone: a `cst.<name>` annotation, and the span's
    duration added to `profiled_spans()` if the session still records
    when it closes."""

    __slots__ = ("name", "ann", "t0")

    def __init__(self, name: str, cls):
        self.name = name
        self.ann = cls("cst." + name)

    def __enter__(self):
        self.ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        dur = time.perf_counter() - self.t0
        self.ann.__exit__(exc_type, exc, tb)
        if _recording() is not None:
            _note_profiled(self.name, dur)
        return False


class _Span:
    __slots__ = ("name", "attrs", "t0", "ann", "parent")

    def __init__(self, name: str, attrs: dict):
        self.name = name
        self.attrs = attrs
        self.ann = None

    def __enter__(self):
        stack = _span_stack()
        self.parent = stack[-1] if stack else None
        stack.append(self.name)
        cls = _recording()
        if cls is not None:
            self.ann = _ProfiledSpan(self.name, cls)
            self.ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        t1 = time.perf_counter()
        if self.ann is not None:
            self.ann.__exit__(exc_type, exc, tb)
        stack = _span_stack()
        if stack and stack[-1] == self.name:
            stack.pop()
        dur = t1 - self.t0
        global _events_dropped
        with _lock:
            s = _spans.get(self.name)
            if s is None:
                _spans[self.name] = {"count": 1, "total_s": dur,
                                     "min_s": dur, "max_s": dur}
            else:
                s["count"] += 1
                s["total_s"] += dur
                if dur < s["min_s"]:
                    s["min_s"] = dur
                if dur > s["max_s"]:
                    s["max_s"] = dur
            if len(_events) < _MAX_EVENTS:
                args = dict(self.attrs)
                if self.parent:
                    args["parent"] = self.parent
                if exc_type is not None:
                    args["error"] = exc_type.__name__
                _events.append({
                    "name": self.name,
                    "ts": (self.t0 - _T0) * 1e6,    # µs, process-relative
                    "dur": dur * 1e6,
                    "tid": threading.get_ident() & 0x7FFFFFFF,
                    "args": args,
                })
            else:
                _events_dropped += 1
        return False    # never swallow the exception


def span(name: str, **attrs):
    """Nestable wall-time section.  Usage:

        with telemetry.span("bls.batch_verify", lanes=128):
            ...

    With the registry off it is the profiler half alone while a profiler
    session records, and else one shared no-op object (no allocation)."""
    if _enabled:
        return _Span(name, attrs)
    cls = _recording()
    if cls is None:
        return _NULL_SPAN
    return _ProfiledSpan(name, cls)


# --- snapshot ---------------------------------------------------------------


def snapshot() -> dict:
    """Point-in-time copy of the whole registry.  Schema (stable; pinned
    by tests/test_telemetry.py):

        {"enabled": bool,
         "meta":       {str: json-able},
         "counters":   {str: int},
         "histograms": {str: {"count","total","min","max"}},
         "spans":      {str: {"count","total_s","min_s","max_s"}},
         "gauges":     {str: {"last","min","max","count"}},
         "events": int, "events_dropped": int,
         "costmodel": {"kernels": {...}, "watermarks": {...},
                       "wm_events": int, "wm_events_dropped": int},
         "occupancy": {"enabled","events","open_spans",
                       "events_dropped","live"}}
    """
    with _lock:
        snap = {
            "enabled": _enabled,
            "meta": dict(_meta),
            "counters": dict(_counters),
            "histograms": {k: dict(v) for k, v in _hists.items()},
            "spans": {k: dict(v) for k, v in _spans.items()},
            "gauges": {k: dict(v) for k, v in _gauges.items()},
            "events": len(_events),
            "events_dropped": _events_dropped,
        }
    # outside _lock: the cost-model and request-trace registries have
    # their own locks, and their snapshots must not nest under ours
    # (lock-order discipline)
    from . import costmodel, occupancy, reqtrace
    snap["costmodel"] = costmodel.raw_snapshot()
    snap["reqtrace"] = reqtrace.raw_snapshot()
    snap["occupancy"] = occupancy.raw_snapshot()
    return snap


def _events_copy() -> tuple[list[dict], int]:
    with _lock:
        return [dict(e) for e in _events], _events_dropped


def _gauge_events_copy() -> tuple[list[dict], int]:
    """Timeline gauge samples for the Chrome-trace exporter."""
    with _lock:
        return [dict(e) for e in _gauge_events], _gauge_events_dropped


def _save_state():
    """Deep copy of the whole registry (test support: the telemetry
    suite must reset the process-global registry without destroying the
    session-wide data a CST_TELEMETRY CI run is accumulating)."""
    with _lock:
        return (dict(_counters),
                {k: dict(v) for k, v in _hists.items()},
                {k: dict(v) for k, v in _spans.items()},
                [dict(e) for e in _events],
                dict(_meta),
                set(_first_keys),
                _events_dropped,
                {k: dict(v) for k, v in _gauges.items()},
                [dict(e) for e in _gauge_events],
                _gauge_events_dropped)


def _restore_state(state) -> None:
    global _events_dropped, _gauge_events_dropped
    (counters, hists, spans, events, meta, first_keys, dropped,
     gauges, gauge_events, gauge_dropped) = state
    with _lock:
        _counters.clear()
        _counters.update(counters)
        _hists.clear()
        _hists.update(hists)
        _spans.clear()
        _spans.update(spans)
        _events.clear()
        _events.extend(events)
        _meta.clear()
        _meta.update(meta)
        _first_keys.clear()
        _first_keys.update(first_keys)
        _events_dropped = dropped
        _gauges.clear()
        _gauges.update(gauges)
        _gauge_events.clear()
        _gauge_events.extend(gauge_events)
        _gauge_events_dropped = gauge_dropped
