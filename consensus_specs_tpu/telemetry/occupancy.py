"""Device-occupancy ledger — pipeline-bubble attribution for the serve
fleet.

The depth-pipelined serve design exists to keep the device busy: host
prep of batch N+1 is supposed to hide under device execution of batch
N.  Request tracing (`reqtrace.py`) says where a REQUEST's wall went;
nothing says whether the DEVICE was busy, and when it was not, why not.
This module closes that gap with a per-device interval ledger fed from
the existing sanctioned seams:

- `ServeExecutor._dispatch_one` / `_settle_batch` mint a `BatchSpan`
  per dispatched batch: host-prep begin → in flight (device busy
  opens) → device answer (busy closes) → settle end.
- `ops.bls_batch._dispatch` stamps kernel-level busy: a blocking
  dispatch records its [t0, t1] directly; a `block=False` enqueue
  opens a span that `serve.futures._settle_from_device` closes
  (`note_settled` — the device stream executes in order, so a settle
  means everything enqueued before it has finished; a span truncated
  early by a pipelined neighbour's settle is recovered by the
  union-merge with the executor-level interval for the same batch).

`block(window)` merges the busy intervals per device (union across
sources, so the two seams never double-count), computes `busy_frac`
and per-kind device-seconds, scores pipeline overlap (how much host
prep actually hid under device busy), and attributes every idle gap in
the union-busy timeline to exactly one cause:

    host_prep          the gap overlaps recorded host-prep intervals —
                       prep that did NOT hide under device work
                       (pipeline depth too shallow, or serialized)
    settle_serialized  the remaining gap overlaps recorded settle
                       intervals — result distribution blocking the
                       next dispatch
    drain              residual idle after the LAST busy span — the
                       tail where in-flight work finished and nothing
                       was dispatched again
    queue_starved      everything else — the device sat idle with no
                       host work recorded: arrivals were too slow

The partition is exact interval arithmetic, so `busy_s` plus the four
bubble components sums to the measured wall to float round-off (the
same contiguity contract as reqtrace's five latency components; pinned
to 1e-6 relative by tests/test_occupancy.py).

Read sides: the serve block's `"occupancy"` sub-object
(`telemetry.export.validate_occupancy_block`), Chrome-trace
per-device busy counter tracks, the
`cst_serve_device_busy_frac` / `cst_serve_bubble_seconds_total{cause=}`
exposition families, `ServeExecutor.status()["occupancy"]`, and the
watchdog's `serve.busy_frac` signal.

Gating contract (the telemetry pattern): OFF unless `CST_OCCUPANCY` is
set non-"0" (or `configure(enabled=True)`); every note-site guards on
ONE module-global read (no-op bound pinned by tests).  Registry capped
at `_MAX_EVENTS`; drops are counted, never silent.  Stdlib-only; never
imports jax or numpy (same discipline as the rest of `telemetry/`).
"""

from __future__ import annotations

import os
import threading
import time

BUBBLE_CAUSES = ("host_prep", "queue_starved", "settle_serialized",
                 "drain")

# interval classes the ledger stores (one flat event list keeps the
# note-site cost to a tuple append)
_BUSY, _PREP, _SETTLE = 0, 1, 2

_MAX_EVENTS = 200_000

_lock = threading.Lock()


def _env_enabled() -> bool:
    return os.environ.get("CST_OCCUPANCY", "0") not in ("", "0")


_enabled = _env_enabled()
# completed intervals: (class, device, label, t0, t1); appends are
# atomic under the GIL so the enabled note path takes no lock (the
# lock guards reads/resets, like reqtrace's registry)
_events: list[tuple] = []
_events_dropped = 0
# open kernel busy spans per device: [(label, t0), ...] — closed by
# `note_settled` (FIFO device stream) or clamped to the window end by
# `block()` for work still executing at read time
_open: dict[str, list] = {}


def enabled() -> bool:
    """True while the ledger is recording (CST_OCCUPANCY or an explicit
    `configure(enabled=True)`)."""
    return _enabled


def configure(enabled: bool | None = None) -> None:
    """Programmatic override of the env gate (benches, smoke, tests)."""
    global _enabled
    if enabled is not None:
        _enabled = enabled


def reset() -> None:
    """Clear the ledger (how loadgen scopes a measured window to
    itself).  Open kernel spans clear too — work dispatched before the
    window re-enters through its executor-level interval."""
    global _events_dropped
    with _lock:
        _events.clear()
        _open.clear()
        _events_dropped = 0


def _reset_state() -> None:
    """Full test-isolation reset (telemetry.reset(full=True) hook):
    ledger AND the env-derived gate."""
    global _enabled
    reset()
    _enabled = _env_enabled()


def _push(cls: int, device: str, label: str, t0: float,
          t1: float) -> None:
    global _events_dropped
    if t1 <= t0:
        return
    if len(_events) < _MAX_EVENTS:
        _events.append((cls, device, label, t0, t1))
    else:
        _events_dropped += 1


# --- the executor seam -------------------------------------------------------


class BatchSpan:
    """One dispatched serve batch's occupancy lifecycle.  Minted by
    `begin_batch()` at `_dispatch_one` entry; the executor drives the
    transitions.  Publishes three intervals on completion: host prep
    [mint, dispatch], device busy [dispatch, answer], settle [answer,
    settled]."""

    __slots__ = ("kind", "device", "t_prep0", "t_dispatch", "t_answer",
                 "done")

    def __init__(self, kind: str, device: str = "0"):
        self.kind = kind
        self.device = device
        self.t_prep0 = time.perf_counter()
        self.t_dispatch = None
        self.t_answer = None
        self.done = False

    def mark_dispatch(self) -> None:
        """Host prep done, batch handed to the device — busy opens."""
        now = time.perf_counter()
        if self.t_dispatch is None:
            self.t_dispatch = now
            _push(_PREP, self.device, self.kind, self.t_prep0, now)

    def mark_answer(self) -> None:
        """The batch's device answer arrived — busy closes."""
        now = time.perf_counter()
        if self.t_answer is None and self.t_dispatch is not None:
            self.t_answer = now
            _push(_BUSY, self.device, self.kind, self.t_dispatch, now)

    def mark_settled(self) -> None:
        """Results distributed to the member handles — settle closes.
        Idempotent; an answerless settle (prep failed after dispatch
        bookkeeping) closes what it has."""
        if self.done:
            return
        self.done = True
        now = time.perf_counter()
        if self.t_answer is not None:
            _push(_SETTLE, self.device, self.kind, self.t_answer, now)

    def abandon(self) -> None:
        """Host prep failed before dispatch: record the prep wall (work
        that hid nothing) and finish the span."""
        if self.done:
            return
        self.done = True
        now = time.perf_counter()
        if self.t_dispatch is None:
            _push(_PREP, self.device, self.kind, self.t_prep0, now)
        elif self.t_answer is None:
            # failed between dispatch and answer: the wait was still
            # device wall from the ledger's point of view
            _push(_BUSY, self.device, self.kind, self.t_dispatch, now)


def begin_batch(kind: str, device: str = "0") -> BatchSpan | None:
    """A fresh batch span, or None while the ledger is off (stamp
    sites guard on None — disabled cost is this one global read).
    `device` is a caller-supplied label (telemetry never imports jax);
    the single-stream serve path uses the default "0"."""
    if not _enabled:
        return None
    return BatchSpan(kind, device)


# --- the kernel seam ---------------------------------------------------------


def note_kernel_busy(kernel: str, t0: float, t1: float,
                     device: str = "0") -> None:
    """A blocking kernel dispatch's measured device wall [t0, t1] (the
    `_dispatch` first-call / `block=True` path)."""
    if not _enabled:
        return
    _push(_BUSY, device, f"kernel:{kernel}", t0, t1)


def note_kernel_dispatched(kernel: str, t0: float | None = None,
                           device: str = "0") -> None:
    """A non-blocking kernel enqueue: opens a busy span closed by the
    next `note_settled` on the same device (the device stream executes
    in order)."""
    if not _enabled:
        return
    t = time.perf_counter() if t0 is None else t0
    with _lock:
        _open.setdefault(device, []).append((f"kernel:{kernel}", t))


def note_settled(device: str = "0") -> None:
    """A device→host settle completed: everything enqueued on this
    device before it has finished executing — close every open span.
    (A pipelined neighbour's span closed early here is recovered by the
    union-merge with its executor-level busy interval.)"""
    if not _enabled:
        return
    now = time.perf_counter()
    with _lock:
        spans = _open.pop(device, [])
    for label, t0 in spans:
        _push(_BUSY, device, label, t0, now)


# --- interval arithmetic -----------------------------------------------------


def _merge(intervals: list) -> list:
    """Sorted disjoint union of [t0, t1) intervals."""
    out: list = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(intervals: list, w0: float, w1: float) -> list:
    out = []
    for a, b in intervals:
        a, b = max(a, w0), min(b, w1)
        if b > a:
            out.append((a, b))
    return out


def _intersect(xs: list, ys: list) -> list:
    """Intersection of two sorted disjoint interval lists."""
    out, i, j = [], 0, 0
    while i < len(xs) and j < len(ys):
        a = max(xs[i][0], ys[j][0])
        b = min(xs[i][1], ys[j][1])
        if b > a:
            out.append((a, b))
        if xs[i][1] <= ys[j][1]:
            i += 1
        else:
            j += 1
    return out


def _subtract(xs: list, ys: list) -> list:
    """xs minus ys, both sorted disjoint."""
    out = []
    j = 0
    for a, b in xs:
        cur = a
        while j < len(ys) and ys[j][1] <= cur:
            j += 1
        k = j
        while k < len(ys) and ys[k][0] < b:
            ya, yb = ys[k]
            if ya > cur:
                out.append((cur, ya))
            cur = max(cur, yb)
            k += 1
        if cur < b:
            out.append((cur, b))
    return out


def _total(intervals: list) -> float:
    return sum(b - a for a, b in intervals)


def _snapshot_events(clamp_open_to: float | None):
    with _lock:
        events = list(_events)
        dropped = _events_dropped
        if clamp_open_to is not None:
            for dev, spans in _open.items():
                for label, t0 in spans:
                    if clamp_open_to > t0:
                        events.append((_BUSY, dev, label, t0,
                                       clamp_open_to))
    return events, dropped


def _attribute(busy_u: list, prep_m: list, settle_m: list,
               w0: float, w1: float) -> dict:
    """Partition the idle gaps of one busy timeline over [w0, w1] into
    the four bubble causes.  Exact: busy + bubbles == w1 - w0."""
    gaps = _subtract([(w0, w1)], busy_u)
    host_prep = _intersect(gaps, prep_m)
    rem = _subtract(gaps, host_prep)
    settle = _intersect(rem, settle_m)
    rem = _subtract(rem, settle)
    last_busy_end = busy_u[-1][1] if busy_u else w0
    drain = _intersect(rem, [(last_busy_end, w1)]) \
        if last_busy_end < w1 else []
    starved = _subtract(rem, drain)
    return {
        "host_prep": _total(host_prep),
        "queue_starved": _total(starved),
        "settle_serialized": _total(settle),
        "drain": _total(drain),
    }


# --- read sides --------------------------------------------------------------


def block(window: tuple | None = None, depth: int | None = None) -> dict:
    """The `"occupancy"` serve-block sub-object over `window`
    (perf_counter (W0, W1); default = the ledger's own extent, end
    clamped to now).  `depth` is the caller's pipeline depth knob,
    carried for the overlap-score read side."""
    now = time.perf_counter()
    events, dropped = _snapshot_events(clamp_open_to=(
        window[1] if window is not None else now))
    if window is not None:
        w0, w1 = float(window[0]), float(window[1])
    elif events:
        w0 = min(e[3] for e in events)
        w1 = min(now, max(e[4] for e in events))
    else:
        w0 = w1 = now
    out = {
        "enabled": _enabled,
        "wall_s": max(0.0, w1 - w0),
        "depth": depth,
        "events": len(events),
        "events_dropped": dropped,
        "busy_s": 0.0,
        "busy_frac": 0.0,
        "bubbles_s": dict.fromkeys(BUBBLE_CAUSES, 0.0),
        "devices": {},
        "device_seconds_by_kind": {},
        "overlap": {"prep_s": 0.0, "hidden_s": 0.0, "score": None},
    }
    if w1 <= w0:
        out["wall_s"] = 0.0
        out["bubbles_s"]["queue_starved"] = 0.0
        return out
    wall = w1 - w0

    busy_by_dev: dict[str, list] = {}
    preps, settles = [], []
    by_kind: dict[str, float] = {}
    for cls, dev, label, t0, t1 in events:
        a, b = max(t0, w0), min(t1, w1)
        if b <= a:
            continue
        if cls == _BUSY:
            busy_by_dev.setdefault(dev, []).append((a, b))
            by_kind[label] = by_kind.get(label, 0.0) + (b - a)
        elif cls == _PREP:
            preps.append((a, b))
        else:
            settles.append((a, b))

    prep_m = _merge(preps)
    settle_m = _merge(settles)
    all_busy: list = []
    for dev, iv in sorted(busy_by_dev.items()):
        dev_busy = _merge(iv)
        all_busy.extend(dev_busy)
        out["devices"][dev] = {
            "busy_s": round(_total(dev_busy), 9),
            "busy_frac": round(_total(dev_busy) / wall, 6),
            "spans": len(dev_busy),
            "bubbles_s": {c: round(v, 9) for c, v in _attribute(
                dev_busy, prep_m, settle_m, w0, w1).items()},
        }
    busy_u = _merge(all_busy)
    busy_s = _total(busy_u)
    out["busy_s"] = busy_s
    out["busy_frac"] = round(busy_s / wall, 6)
    out["bubbles_s"] = _attribute(busy_u, prep_m, settle_m, w0, w1)
    out["device_seconds_by_kind"] = {
        k: round(v, 9) for k, v in sorted(by_kind.items())}
    prep_s = _total(prep_m)
    hidden = _total(_intersect(prep_m, busy_u))
    out["overlap"] = {
        "prep_s": round(prep_s, 9),
        "hidden_s": round(hidden, 9),
        "score": round(hidden / prep_s, 6) if prep_s > 0 else None,
    }
    return out


def live_summary(window_s: float | None = None) -> dict | None:
    """A compact live view for `ServeExecutor.status()`, the watchdog's
    `serve.busy_frac` signal, and the exposition families: busy_frac +
    per-cause bubble seconds over the trailing `window_s` (default: the
    ledger's whole extent).  None while disabled or empty."""
    if not _enabled:
        return None
    now = time.perf_counter()
    events, _ = _snapshot_events(clamp_open_to=now)
    if not events:
        return None
    w1 = now
    w0 = (w1 - window_s) if window_s else min(e[3] for e in events)
    if w1 <= w0:
        return None
    b = block(window=(w0, w1))
    return {
        "busy_frac": b["busy_frac"],
        "bubbles_s": {c: round(v, 6)
                      for c, v in b["bubbles_s"].items()},
        "devices": {d: v["busy_frac"]
                    for d, v in b["devices"].items()},
        "window_s": round(w1 - w0, 6),
    }


def live_busy_frac(window_s: float | None = None) -> float | None:
    """The watchdog signal: union-busy fraction, or None while the
    ledger is off / empty (None holds a rule's streak, per monitor's
    hysteresis contract)."""
    s = live_summary(window_s)
    return None if s is None else s["busy_frac"]


def raw_snapshot() -> dict:
    """The `occupancy` sub-object of `telemetry.snapshot()`: summary
    counts + the live view (bounded — intervals stay in the ledger)."""
    with _lock:
        n, dropped = len(_events), _events_dropped
        n_open = sum(len(v) for v in _open.values())
    return {
        "enabled": _enabled,
        "events": n,
        "open_spans": n_open,
        "events_dropped": dropped,
        "live": live_summary(),
    }


def chrome_events(pid: int, t0: float) -> list[dict]:
    """Per-device busy counter tracks for the Perfetto export: a 'C'
    sample rising to 1 at each merged busy-span start and falling to 0
    at its end.  `t0` is the process trace origin (`core._T0`)."""
    now = time.perf_counter()
    events, _ = _snapshot_events(clamp_open_to=now)
    busy_by_dev: dict[str, list] = {}
    for cls, dev, _label, a, b in events:
        if cls == _BUSY:
            busy_by_dev.setdefault(dev, []).append((a, b))
    out = []

    def us(t: float) -> float:
        return round((t - t0) * 1e6, 3)

    for dev, iv in sorted(busy_by_dev.items()):
        name = f"pipeline.device_busy.{dev}"
        for a, b in _merge(iv):
            out.append({"name": name, "ph": "C", "cat": "cst",
                        "pid": pid, "tid": 0, "ts": us(a),
                        "args": {"busy": 1}})
            out.append({"name": name, "ph": "C", "cat": "cst",
                        "pid": pid, "tid": 0, "ts": us(b),
                        "args": {"busy": 0}})
    return out
