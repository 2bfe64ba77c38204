"""Recovery policies: retry, circuit breaker, deadline types.

The serve executor consumes these (opt-in — a plain `ServeExecutor`
keeps PR 6's fail-fast poisoning semantics):

- `RetryPolicy`: per-batch retry with capped exponential backoff.  A
  failed device batch re-dispatches up to `max_attempts` times before
  the failure is final; backoff is deterministic (no jitter — chaos
  rounds must replay).
- `CircuitBreaker` / `BreakerRegistry`: per-(kind, rung) breaker.
  `threshold` consecutive failures trip CLOSED→OPEN; while OPEN the
  executor routes matching batches to the pure-Python oracle fallback
  (correct-but-slow degraded mode) instead of the device.  After
  `cooldown_s` the next `allow()` transitions OPEN→HALF_OPEN and admits
  exactly ONE device probe; the probe's outcome re-closes
  (HALF_OPEN→CLOSED) or re-trips (HALF_OPEN→OPEN).  Every transition is
  logged (the `resilience` record's breaker-transition surface) and
  counted in telemetry.
- `DeadlineExceeded`: the typed error a shed request settles with when
  it ages past the executor's per-request deadline
  (`CST_SERVE_DEADLINE_MS`) — the queue fails its oldest entries
  instead of growing unboundedly.

Stdlib-only (+ telemetry): importable from the executor without pulling
numpy/jax.
"""

from __future__ import annotations

import time

from .. import telemetry
from ..telemetry import flightrec

CLOSED = "closed"
OPEN = "open"
HALF_OPEN = "half_open"


class DeadlineExceeded(RuntimeError):
    """A request aged past the executor's per-request deadline and was
    shed before dispatch.  Typed so callers can tell load shedding from
    a device failure.  On traced rounds (CST_TRACE_REQUESTS) the error
    carries the shed request's `trace_id`, so a caller holding the
    exception can find its lifecycle record in the reqtrace registry."""

    def __init__(self, kind: str, age_s: float, deadline_s: float,
                 trace_id: int | None = None):
        super().__init__(
            f"{kind} request shed: queued {age_s:.3f}s, deadline "
            f"{deadline_s:.3f}s"
            + (f" (trace {trace_id})" if trace_id is not None else ""))
        self.kind = kind
        self.age_s = age_s
        self.deadline_s = deadline_s
        self.trace_id = trace_id


class RetryPolicy:
    """Capped exponential backoff: attempt k (1-based) that fails waits
    `min(max_backoff_s, base_backoff_s * 2**(k-1))` before re-dispatch,
    up to `max_attempts` total attempts."""

    __slots__ = ("max_attempts", "base_backoff_s", "max_backoff_s")

    def __init__(self, max_attempts: int = 3, base_backoff_s: float = 0.05,
                 max_backoff_s: float = 1.0):
        assert max_attempts >= 1 and base_backoff_s >= 0
        self.max_attempts = int(max_attempts)
        self.base_backoff_s = float(base_backoff_s)
        self.max_backoff_s = float(max_backoff_s)

    def should_retry(self, attempt: int) -> bool:
        return attempt < self.max_attempts

    def backoff_s(self, attempt: int) -> float:
        return min(self.max_backoff_s,
                   self.base_backoff_s * (2 ** (attempt - 1)))


class CircuitBreaker:
    """One key's breaker; see the module docstring for the state
    machine.  `clock` is injectable so tests drive the cooldown without
    sleeping."""

    __slots__ = ("key", "threshold", "cooldown_s", "_clock", "_state",
                 "_failures", "_opened_at", "_probe_inflight",
                 "_on_transition", "trips")

    def __init__(self, key: str, threshold: int = 3,
                 cooldown_s: float = 1.0, clock=time.monotonic,
                 on_transition=None):
        assert threshold >= 1 and cooldown_s >= 0
        self.key = key
        self.threshold = int(threshold)
        self.cooldown_s = float(cooldown_s)
        self._clock = clock
        self._state = CLOSED
        self._failures = 0
        self._opened_at = 0.0
        self._probe_inflight = False
        self._on_transition = on_transition
        self.trips = 0

    @property
    def state(self) -> str:
        return self._state

    def _transition(self, to: str) -> None:
        frm, self._state = self._state, to
        if to == OPEN:
            self.trips += 1
            self._opened_at = self._clock()
            self._probe_inflight = False
        telemetry.count(f"resilience.breaker.{to}")
        flightrec.record("breaker_transition", key=self.key,
                         frm=frm, to=to)
        if self._on_transition is not None:
            self._on_transition({"key": self.key, "from": frm, "to": to,
                                 "t": self._clock()})

    def allow(self) -> bool:
        """May the next batch for this key go to the DEVICE?  False
        means degrade (oracle fallback).  OPEN past its cooldown admits
        exactly one half-open probe."""
        if self._state is CLOSED:
            return True
        if self._state is OPEN:
            if self._clock() - self._opened_at >= self.cooldown_s:
                self._transition(HALF_OPEN)
                self._probe_inflight = True
                return True
            return False
        # HALF_OPEN: one probe at a time
        if not self._probe_inflight:
            self._probe_inflight = True
            return True
        return False

    def record_success(self) -> None:
        self._failures = 0
        if self._state is not CLOSED:
            self._transition(CLOSED)
        self._probe_inflight = False

    def record_failure(self) -> None:
        self._failures += 1
        if self._state is HALF_OPEN:
            self._transition(OPEN)
        elif self._state is CLOSED and self._failures >= self.threshold:
            self._transition(OPEN)


class BreakerRegistry:
    """Per-key breakers sharing one config and one transition log (the
    `resilience` record's `breaker` block)."""

    def __init__(self, threshold: int = 3, cooldown_s: float = 1.0,
                 clock=time.monotonic):
        self.threshold = threshold
        self.cooldown_s = cooldown_s
        self._clock = clock
        self._breakers: dict[str, CircuitBreaker] = {}
        self.transitions: list[dict] = []

    def get(self, key: str) -> CircuitBreaker:
        br = self._breakers.get(key)
        if br is None:
            br = self._breakers[key] = CircuitBreaker(
                key, threshold=self.threshold, cooldown_s=self.cooldown_s,
                clock=self._clock, on_transition=self.transitions.append)
        return br

    def states(self) -> dict[str, str]:
        return {k: b.state for k, b in sorted(self._breakers.items())}

    def trips(self) -> int:
        return sum(b.trips for b in self._breakers.values())

    def summary(self) -> dict:
        """JSON-able block for the `resilience` sub-object."""
        return {
            "states": self.states(),
            "trips": self.trips(),
            "transitions": [
                {"key": t["key"], "from": t["from"], "to": t["to"]}
                for t in self.transitions],
        }
