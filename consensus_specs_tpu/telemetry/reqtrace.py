"""Request-scoped tracing — per-request lifecycle + tail-latency
attribution across the serve pipeline.

The serve path carries nine request kinds through queue → batch fold →
device dispatch → settle → retry/breaker/oracle-fallback, and the
production claim is gated on per-request tail latency (`serve-p99`).
Kernel/batch telemetry (PRs 2/5) cannot say WHERE a p99 miss lives:
queue wait, batch formation, device wall, settle, or a resilience
detour.  This module closes that gap — the request→batch lineage
problem every batched-inference server solves:

- `RequestContext`: minted at every `ServeExecutor.submit_*` (analyzer
  rule `reqtrace-uncovered-submit` makes that a lint invariant),
  carried on the request AND its `DeviceFuture` handle, stamped at
  every pipeline phase transition.  Timestamps: submit / enqueue /
  dispatch (first) / complete; cumulative per-component wall in
  `components` — the phases are CONTIGUOUS (each stamp closes the
  interval since the previous one), so the components sum to the
  end-to-end latency exactly:

      queue_wait   submit → first dispatch attempt
      batch_form   dispatch entry → batch in flight (host prep:
                   point→limb conversion, RLC draws, transfers)
      device_wall  in flight → device answer fetched
      settle       answer → handle settled (verdict split, mask split)
      detour       everything the resilience ladder adds: failed
                   attempts, retry backoff, per-statement recheck,
                   oracle-fallback compute

- outcome ∈ {ok, recheck, retry, fallback, shed, poisoned, timeout}:
  the request's final disposition.  `timeout` is PROVISIONAL and
  handle-level only: a bounded wait that ran out leaves the handle
  pending (read it via `fut.ctx.outcome`), and the eventual settle
  overwrites it — so completed-record aggregates (`records()`,
  `attribution()` outcome counts, `raw_snapshot()`) never contain it;
  the vocabulary keeps the value so schemas stay stable if an
  abandoned-handle publisher ever lands.
- batch spans: every device dispatch gets a batch id linking its member
  trace ids (N queued → 1 dispatch → N contexts share the id) — the
  lineage the Chrome-trace flow events render as arrows.
- `attribution()`: per-kind p50/p90/p99 decomposed into the five
  components, worst-N exemplar traces retained — the serve block's
  `latency_attribution` sub-object.
- `chrome_events()`: request lifecycle 'X' spans + 's'/'t'/'f' flow
  events (submit → batch → settle arrows) + batch 'X' spans, appended
  to the existing Perfetto export by `telemetry.export.chrome_trace`.
- `rolling_summary()`: per-kind rolling p50/p99 + mean components over
  the freshest records — the live `ServeExecutor.status()` surface.

Gating contract (the telemetry pattern): OFF unless `CST_TRACE_REQUESTS`
is set non-"0" (or `configure(enabled=True)`), `mint()` while disabled
is ONE module-global read returning None — the no-op bound is pinned by
tests/test_reqtrace.py.  Registry capped at `_MAX_RECORDS` completed
records / `_MAX_BATCHES` batch spans; drops are counted, never silent.

Stdlib-only; never imports jax or numpy — safe from anywhere, including
before backend pinning (same discipline as the rest of `telemetry/`).
"""

from __future__ import annotations

import collections
import itertools
import os
import threading
import time

COMPONENTS = ("queue_wait", "batch_form", "device_wall", "settle",
              "detour")
OUTCOMES = ("ok", "recheck", "retry", "fallback", "shed", "poisoned",
            "timeout")

# bounded registries: ~200 bytes/record keeps the worst case ~20 MB on
# a sustained round; drops are counted, never silent
_MAX_RECORDS = 100_000
_MAX_BATCHES = 50_000

# the live-summary ring: `rolling_summary()` (the ServeExecutor.status()
# dump and the SLO watchdog tick) reads ONLY this fixed-size window of
# the freshest completions, so its cost is O(window) however large the
# full registry grows — and it keeps rolling after the registry cap
# stops admitting records
_WINDOW_CAP = 4096

_lock = threading.Lock()


def _env_enabled() -> bool:
    return os.environ.get("CST_TRACE_REQUESTS", "0") not in ("", "0")


_enabled = _env_enabled()
# id counters are itertools.count — next() is atomic under the GIL, so
# the enabled per-request path takes NO lock (the registry lock guards
# only copies/resets; list.append is likewise atomic)
_trace_seq = itertools.count(1)
_batch_seq = itertools.count(1)
# the record registry stores completed RequestContext OBJECTS; the
# dict view materializes at read time (`records()`), keeping the
# per-request completion cost to an append
_records: list = []
_records_dropped = 0
_batches: list[dict] = []
_batches_dropped = 0
# the rolling live window + monotone completion totals (never reset by
# the registry cap; the watchdog's throughput signal is a delta of
# these).  deque.append with maxlen is atomic under the GIL, so the
# per-completion path stays lock-free like `_publish`.
_window: collections.deque = collections.deque(maxlen=_WINDOW_CAP)
_completed_total = 0
_completed_by_kind: dict[str, int] = {}
_completed_by_outcome: dict[str, int] = {}


def enabled() -> bool:
    """True when request contexts are being minted (CST_TRACE_REQUESTS
    or an explicit `configure(enabled=True)`)."""
    return _enabled


def configure(enabled: bool | None = None) -> None:
    """Programmatic override of the env gate (benches, chaos rounds,
    tests)."""
    global _enabled
    if enabled is not None:
        _enabled = enabled


def reset() -> None:
    """Clear completed records and batch spans (id counters keep
    monotone so records from before/after a reset can never collide).
    How the loadgen scopes a measured run's records to itself."""
    global _records_dropped, _batches_dropped, _completed_total
    with _lock:
        _records.clear()
        _batches.clear()
        _records_dropped = 0
        _batches_dropped = 0
        _window.clear()
        _completed_total = 0
        _completed_by_kind.clear()
        _completed_by_outcome.clear()


def _reset_state() -> None:
    """Full test-isolation reset (telemetry.reset(full=True) hook):
    records AND the id counters."""
    global _trace_seq, _batch_seq
    reset()
    with _lock:
        _trace_seq = itertools.count(1)
        _batch_seq = itertools.count(1)


def _publish(ctx: "RequestContext") -> None:
    # lock-free: append is atomic, and the cap check racing a
    # concurrent append can overshoot by at most a few records — the
    # bound is a memory guard, not an exact count
    global _records_dropped, _completed_total
    if len(_records) < _MAX_RECORDS:
        _records.append(ctx)
    else:
        _records_dropped += 1
    # the live window and the monotone totals admit EVERY completion
    # (capped registry or not) — the rolling summary and the watchdog's
    # throughput delta must track the service, not the memory guard
    _window.append(ctx)
    _completed_total += 1
    _completed_by_kind[ctx.kind] = _completed_by_kind.get(ctx.kind, 0) + 1
    if ctx.outcome is not None:
        _completed_by_outcome[ctx.outcome] = \
            _completed_by_outcome.get(ctx.outcome, 0) + 1


class RequestContext:
    """One request's lifecycle through the serve pipeline.  Created via
    `mint()`; the serve executor drives the `mark_*`/`note_*`/`complete`
    transitions (see the module docstring for the phase → component
    mapping).  All timestamps are `time.perf_counter()` values."""

    # the five component accumulators live as PLAIN FLOAT SLOTS (not a
    # dict) — the enabled path runs per request on the serve hot loop,
    # and slot adds keep the per-event cost to a perf_counter() call
    # plus an attribute write.  `components` materializes the dict view.
    __slots__ = ("trace_id", "kind", "batch_id", "outcome", "attempts",
                 "faulted", "rechecked", "t_submit", "t_enqueue",
                 "t_dispatch", "t_complete", "_mark", "done") \
        + COMPONENTS

    def __init__(self, trace_id: int, kind: str):
        now = time.perf_counter()
        self.trace_id = trace_id
        self.kind = kind
        self.batch_id = None
        self.outcome = None
        self.attempts = 0
        self.faulted = False
        self.rechecked = False
        self.t_submit = now
        self.t_enqueue = now
        self.t_dispatch = None
        self.t_complete = None
        self.queue_wait = 0.0
        self.batch_form = 0.0
        self.device_wall = 0.0
        self.settle = 0.0
        self.detour = 0.0
        self._mark = now
        self.done = False

    @property
    def components(self) -> dict:
        return {c: getattr(self, c) for c in COMPONENTS}

    # --- phase accounting ----------------------------------------------------

    def _advance(self, component: str) -> float:
        """Close the interval since the previous stamp into `component`;
        contiguity is what makes the components sum to end-to-end."""
        now = time.perf_counter()
        setattr(self, component, getattr(self, component)
                + (now - self._mark))
        self._mark = now
        return now

    def mark_enqueue(self) -> None:
        """Queued on the executor (the submit→enqueue sliver lands in
        queue_wait at the next stamp)."""
        self.t_enqueue = time.perf_counter()

    def mark_dispatch(self, batch_id) -> None:
        """A dispatch attempt begins.  First attempt closes queue_wait;
        re-dispatches (retry ladder) close the failure+backoff interval
        into detour."""
        now = self._advance("queue_wait" if self.attempts == 0
                            else "detour")
        self.attempts += 1
        self.batch_id = batch_id
        if self.t_dispatch is None:
            self.t_dispatch = now

    def mark_inflight(self) -> None:
        """Host prep done, batch handed to the device (first attempt →
        batch_form; a retry's re-prep is detour)."""
        self._advance("batch_form" if self.attempts <= 1 else "detour")

    def mark_device_done(self) -> None:
        """The batch's device answer arrived (the successful attempt's
        in-flight wait + blocking fetch is device_wall)."""
        self._advance("device_wall")

    def mark_attempt_failed(self, faulted: bool = False) -> None:
        """This attempt raised (host prep or device settle); the failed
        wait is a detour.  `faulted` marks an injected-fault victim —
        the chaos harness's blast-radius correlation."""
        self._advance("detour")
        if faulted:
            self.faulted = True

    def mark_fallback_begin(self) -> None:
        """Entering the oracle-fallback path: close the preceding phase
        (queue if the breaker short-circuited dispatch, detour after a
        failure)."""
        self._advance("queue_wait" if self.attempts == 0 else "detour")

    def note_recheck(self) -> None:
        """The batch verdict was False and per-statement rechecks ran;
        the recheck wall is a detour and the outcome label upgrades."""
        self._advance("detour")
        self.rechecked = True

    def note_timeout(self) -> None:
        """A bounded wait on this handle ran out.  Provisional — the
        handle is still pending and a later settle overwrites it."""
        if not self.done:
            self.outcome = "timeout"

    # --- completion ----------------------------------------------------------

    def complete(self, outcome: str | None = None,
                 final_component: str = "settle") -> None:
        """Settle the context: close the last interval into
        `final_component`, resolve the outcome label (None = auto:
        recheck > retry > ok), publish the lifecycle record."""
        if self.done:
            return
        self.t_complete = self._advance(final_component)
        if outcome is None:
            outcome = ("recheck" if self.rechecked
                       else "retry" if self.attempts > 1 else "ok")
        self.outcome = outcome
        self.done = True
        _publish(self)

    def end_to_end_s(self) -> float | None:
        if self.t_complete is None:
            return None
        return self.t_complete - self.t_submit

    def record(self) -> dict:
        """The compact lifecycle record (what the registry keeps)."""
        rec = {
            "trace_id": self.trace_id,
            "kind": self.kind,
            "outcome": self.outcome,
            "batch": self.batch_id,
            "attempts": self.attempts,
            "t_submit": self.t_submit,
            "t_enqueue": self.t_enqueue,
            "t_dispatch": self.t_dispatch,
            "t_complete": self.t_complete,
            "e2e_s": self.end_to_end_s(),
            "components": self.components,
        }
        if self.faulted:
            rec["faulted"] = True
        return rec


def mint(kind: str) -> RequestContext | None:
    """A fresh context, or None while tracing is off (the executor's
    stamp sites all guard on None — disabled cost is this one global
    read)."""
    if not _enabled:
        return None
    return RequestContext(next(_trace_seq), kind)


def new_batch_id() -> int:
    return next(_batch_seq)


def note_batch(batch_id: int, kind: str, trace_ids: list[int],
               attempt: int, requests: int) -> None:
    """Record one dispatched batch's span + member lineage (lock-free,
    like `_publish` — the cap is a memory guard)."""
    global _batches_dropped
    rec = {"batch_id": batch_id, "kind": kind, "attempt": attempt,
           "requests": requests, "trace_ids": list(trace_ids),
           "t_dispatch": time.perf_counter()}
    if len(_batches) < _MAX_BATCHES:
        _batches.append(rec)
    else:
        _batches_dropped += 1


def records() -> list[dict]:
    """The completed lifecycle records as dicts, materialized at read
    time (does not clear — use `reset()` to scope a run)."""
    with _lock:
        done = list(_records)
    return [c.record() for c in done]


def batches() -> list[dict]:
    with _lock:
        return [dict(b) for b in _batches]


def dropped() -> tuple[int, int]:
    with _lock:
        return _records_dropped, _batches_dropped


# --- tail-latency attribution ------------------------------------------------


ANSWERED = frozenset({"ok", "recheck", "retry", "fallback"})


def _percentile(sorted_vals: list[float], q: float) -> float:
    """Nearest-rank percentile on an already-sorted sample (the loadgen
    convention)."""
    idx = min(len(sorted_vals) - 1,
              int(round(q * (len(sorted_vals) - 1))))
    return sorted_vals[idx]


def _component_means(recs: list[dict]) -> dict:
    out = dict.fromkeys(COMPONENTS, 0.0)
    for r in recs:
        for c in COMPONENTS:
            out[c] += r["components"].get(c, 0.0)
    n = len(recs) or 1
    return {c: round(v / n * 1e3, 3) for c, v in out.items()}


def _tail(recs: list[dict], q: float = 0.99) -> list[dict]:
    """The slowest ceil((1-q) * n) records — the exemplar set the p99
    decomposition averages over (at least one record)."""
    ordered = sorted(recs, key=lambda r: r["e2e_s"], reverse=True)
    n = max(1, len(ordered) - int(round(q * (len(ordered) - 1))))
    return ordered[:n]


def _exemplar(rec: dict) -> dict:
    return {
        "trace_id": rec["trace_id"],
        "kind": rec["kind"],
        "outcome": rec["outcome"],
        "batch": rec["batch"],
        "attempts": rec["attempts"],
        "e2e_ms": round(rec["e2e_s"] * 1e3, 3),
        "components_ms": {c: round(rec["components"].get(c, 0.0) * 1e3, 3)
                          for c in COMPONENTS},
    }


def attribution(trace_records: list[dict] | None = None,
                worst_n: int = 5) -> dict:
    """The tail-latency attribution block (the serve block's
    `latency_attribution` sub-object): per-kind p50/p90/p99 with mean
    and p99-tail component decompositions, outcome counts, the overall
    p99 queue-wait fraction, and the worst-N exemplar traces.

    Only ANSWERED requests (ok/recheck/retry/fallback) enter the
    percentile base — shed and poisoned requests failed, and a deadline
    shed's latency measures the deadline, not the service."""
    recs = trace_records if trace_records is not None else records()
    done = [r for r in recs if r.get("e2e_s") is not None]
    answered = [r for r in done if r.get("outcome") in ANSWERED]
    by_kind: dict[str, list[dict]] = {}
    for r in answered:
        by_kind.setdefault(r["kind"], []).append(r)

    kinds = {}
    for kind, krecs in sorted(by_kind.items()):
        e2e = sorted(r["e2e_s"] for r in krecs)
        tail = _tail(krecs)
        outcomes: dict[str, int] = {}
        for r in krecs:
            outcomes[r["outcome"]] = outcomes.get(r["outcome"], 0) + 1
        tail_e2e = sum(r["e2e_s"] for r in tail) or 1e-12
        tail_queue = sum(r["components"].get("queue_wait", 0.0)
                         for r in tail)
        kinds[kind] = {
            "count": len(krecs),
            "p50_ms": round(_percentile(e2e, 0.50) * 1e3, 3),
            "p90_ms": round(_percentile(e2e, 0.90) * 1e3, 3),
            "p99_ms": round(_percentile(e2e, 0.99) * 1e3, 3),
            "mean_components_ms": _component_means(krecs),
            "p99_components_ms": _component_means(tail),
            "p99_queue_frac": round(tail_queue / tail_e2e, 4),
            "outcomes": outcomes,
        }

    worst = [_exemplar(r) for r in sorted(
        answered, key=lambda r: r["e2e_s"], reverse=True)[:worst_n]]
    overall_frac = None
    if answered:
        tail = _tail(answered)
        tail_e2e = sum(r["e2e_s"] for r in tail) or 1e-12
        overall_frac = round(sum(r["components"].get("queue_wait", 0.0)
                                 for r in tail) / tail_e2e, 4)
    return {
        "kinds": kinds,
        "requests": len(done),
        "answered": len(answered),
        "p99_queue_frac": overall_frac,
        "worst": worst,
        "records_dropped": dropped()[0],
    }


def completed_totals() -> tuple[int, dict, dict]:
    """(total, by_kind, by_outcome) completion counts: monotone past
    the registry cap (every completion counts, admitted or dropped),
    zeroed by `reset()` so a measured run owns its counts.  The
    exposition endpoint's lifetime series and the watchdog's
    throughput-delta baseline."""
    with _lock:
        return (_completed_total, dict(_completed_by_kind),
                dict(_completed_by_outcome))


def rolling_summary(window: int = 2048) -> dict:
    """Per-kind rolling p50/p99 + mean components over the freshest
    `window` completed records — the live `ServeExecutor.status()`
    surface and the SLO watchdog's latency signal.  Reads the fixed
    `_WINDOW_CAP` ring, never the full registry: O(window) per call
    under sustained load (bound pinned by tests/test_monitor.py)."""
    with _lock:
        tail_ctxs = list(_window)
    if window < len(tail_ctxs):
        tail_ctxs = tail_ctxs[-window:]
    tail = [c.record() for c in tail_ctxs]
    by_kind: dict[str, list[dict]] = {}
    for r in tail:
        if r.get("e2e_s") is not None and r.get("outcome") in ANSWERED:
            by_kind.setdefault(r["kind"], []).append(r)
    out = {}
    for kind, krecs in sorted(by_kind.items()):
        e2e = sorted(r["e2e_s"] for r in krecs)
        out[kind] = {
            "count": len(krecs),
            "p50_ms": round(_percentile(e2e, 0.50) * 1e3, 3),
            "p99_ms": round(_percentile(e2e, 0.99) * 1e3, 3),
            "mean_components_ms": _component_means(krecs),
        }
    return out


# --- exports -----------------------------------------------------------------


def raw_snapshot() -> dict:
    """The `reqtrace` sub-object of `telemetry.snapshot()`: summary
    counts + the current attribution (bounded — per-request records
    stay in the registry / the Chrome trace, not the snapshot)."""
    with _lock:
        ctxs = list(_records)
        n_batches = len(_batches)
        rd, bd = _records_dropped, _batches_dropped
    recs = [c.record() for c in ctxs]
    by_outcome: dict[str, int] = {}
    by_kind: dict[str, int] = {}
    for r in recs:
        by_outcome[r["outcome"]] = by_outcome.get(r["outcome"], 0) + 1
        by_kind[r["kind"]] = by_kind.get(r["kind"], 0) + 1
    return {
        "enabled": _enabled,
        "completed": len(recs),
        "batches": n_batches,
        "records_dropped": rd,
        "batches_dropped": bd,
        "by_kind": by_kind,
        "by_outcome": by_outcome,
        "attribution": attribution(recs, worst_n=3) if recs else None,
    }


def chrome_events(pid: int, t0: float) -> list[dict]:
    """Trace-event JSON for the Perfetto export: one 'X' span per
    completed request (submit → complete) and per dispatched batch,
    plus the 's'/'t'/'f' flow triplet drawing the submit → batch →
    settle arrow for each request.  `t0` is the process trace origin
    (`telemetry.core._T0`); timestamps convert to µs relative to it.
    Requests ride per-kind synthetic tids so the request tracks stack
    by kind instead of interleaving one row."""
    out: list[dict] = []
    with _lock:
        ctxs = list(_records)
        brecs = [dict(b) for b in _batches]
    recs = [c.record() for c in ctxs]

    def us(t: float) -> float:
        return round((t - t0) * 1e6, 3)

    kind_tid = {}

    def tid_for(kind: str) -> int:
        if kind not in kind_tid:
            kind_tid[kind] = 0x52510000 + len(kind_tid)   # 'RQ' tracks
        return kind_tid[kind]

    for b in brecs:
        out.append({
            "name": f"batch.{b['kind']}", "ph": "X", "cat": "req",
            "pid": pid, "tid": 0x42510000,                # batch track
            "ts": us(b["t_dispatch"]), "dur": 1.0,
            "args": {"batch": b["batch_id"], "requests": b["requests"],
                     "attempt": b["attempt"],
                     "trace_ids": b["trace_ids"][:32]},
        })
    for r in recs:
        if r.get("t_complete") is None:
            continue
        tid = tid_for(r["kind"])
        name = f"req.{r['kind']}"
        out.append({
            "name": name, "ph": "X", "cat": "req", "pid": pid,
            "tid": tid, "ts": us(r["t_submit"]),
            "dur": round(r["e2e_s"] * 1e6, 3),
            "args": {"trace_id": r["trace_id"], "outcome": r["outcome"],
                     "batch": r["batch"], "attempts": r["attempts"],
                     "components_ms": {
                         c: round(r["components"].get(c, 0.0) * 1e3, 3)
                         for c in COMPONENTS}},
        })
        # the flow arrow: submit -> dispatch (on the batch track) ->
        # settle, tied by the trace id
        flow = {"cat": "req", "name": name, "id": r["trace_id"],
                "pid": pid}
        out.append(dict(flow, ph="s", tid=tid, ts=us(r["t_submit"])))
        if r.get("t_dispatch") is not None:
            out.append(dict(flow, ph="t", tid=0x42510000,
                            ts=us(r["t_dispatch"])))
        out.append(dict(flow, ph="f", bp="e", tid=tid,
                        ts=us(r["t_complete"])))
    return out
