"""Mesh-resilient sharded verification — per-shard loss recovery.

`ops.bls_batch.batch_verify_sharded` spreads one RLC statement batch
over the device mesh; before this module, a single dead device (a real
`XlaRuntimeError` from a lost chip, or the injected `MeshDeviceLost` of
a chaos round) killed the whole batch with no recovery story — the one
un-recovered execution surface the ROADMAP's resilience item named.
This module closes it:

    detect      `MeshVerifier.verify` settles the sharded future and
                classifies failures: a device failure (`MeshDeviceLost`
                or an `XlaRuntimeError`) enters the recovery ladder,
                anything else propagates untouched (a malformed batch
                must not masquerade as a dead chip).
    degrade     the lost shard is marked (`MeshState`), and the SAME
                statements re-dispatch over the surviving devices — the
                per-shard bucket ladder re-buckets them automatically,
                so degraded n-1 (n-2, ...) mode loses capacity, never
                statements.  A one-device remainder degrades to the
                single-chip `batch_verify` path; zero survivors is the
                only case that surfaces the failure.
    re-admit    after `readmit_cooldown_s` the next verify becomes a
                HALF-OPEN probe on the full original mesh: success
                re-admits every lost device (one transition, like the
                breaker's half-open close), failure re-trips and
                restarts the cooldown.

Accounting: `mesh::recovery_latency_s` (first failure → recovered
verdict), `mesh.device_lost` / `mesh.readmitted` counters, and
`block()` — the `"mesh"` sub-object of the chaos round's resilience
block and of `ServeExecutor.stats()`.

Zero wrong or dropped statements is the contract the chaos mesh
segment (`resilience.chaos._mesh_segment`) measures against the
host-oracle expectation, exactly like the serve chaos rounds.

Which physical lane died: XLA does not attribute a dead-executable
error to a device index, so `MeshState.mark_lost` retires the
highest-index surviving device by default (deterministic; correctness
never depends on WHICH lane is dropped — every statement re-buckets
over whatever survives).  Callers with better attribution may pass the
index explicitly.

Stdlib-only at import (the resilience contract): jax and the ops
modules load lazily inside the dispatch path.
"""

from __future__ import annotations

import time

from .. import telemetry
from ..telemetry import flightrec
from .faults import MeshDeviceLost

# exception class names that mean "the device/runtime died", as opposed
# to a caller bug — jaxlib's XlaRuntimeError is matched by name so this
# module never imports jax at module scope
_DEVICE_ERROR_TYPES = ("XlaRuntimeError", "JaxRuntimeError")


def is_device_failure(exc: BaseException) -> bool:
    """Does this exception mean a mesh device failed (recoverable by
    re-bucketing onto the survivors), rather than a caller bug?"""
    if isinstance(exc, MeshDeviceLost):
        return True
    return any(t.__name__ in _DEVICE_ERROR_TYPES
               for t in type(exc).__mro__)


class MeshState:
    """Which logical devices of an n-wide mesh are currently trusted,
    plus the half-open re-admission state machine.  `clock` is
    injectable so tests drive the cooldown without sleeping."""

    __slots__ = ("n_devices", "readmit_cooldown_s", "_clock", "lost",
                 "_tripped_at", "lost_events", "readmissions", "retrips")

    def __init__(self, n_devices: int, readmit_cooldown_s: float = 1.0,
                 clock=time.monotonic):
        assert n_devices >= 1
        self.n_devices = int(n_devices)
        self.readmit_cooldown_s = float(readmit_cooldown_s)
        self._clock = clock
        self.lost: set[int] = set()
        self._tripped_at = 0.0
        self.lost_events = 0
        self.readmissions = 0
        self.retrips = 0

    def surviving(self) -> tuple[int, ...]:
        return tuple(i for i in range(self.n_devices)
                     if i not in self.lost)

    def degraded(self) -> bool:
        return bool(self.lost)

    def probe_due(self) -> bool:
        """May the next dispatch probe the FULL mesh again?"""
        return (self.degraded()
                and self._clock() - self._tripped_at
                >= self.readmit_cooldown_s)

    def mark_lost(self, device: int | None = None) -> None:
        """Retire one device (highest surviving index when the failure
        carries no attribution) and restart the re-admission cooldown."""
        survivors = self.surviving()
        if not survivors:
            return
        device = int(device) if device is not None else survivors[-1]
        self.lost.add(device)
        self._tripped_at = self._clock()
        self.lost_events += 1
        telemetry.count("mesh.device_lost")
        telemetry.gauge("mesh.degraded_lanes", len(self.lost))
        flightrec.record("mesh_device_lost", device=device,
                         degraded_lanes=len(self.lost))

    def record_probe(self, ok: bool) -> None:
        """Outcome of a full-mesh half-open probe: success re-admits
        every lost device, failure re-trips and restarts the cooldown."""
        if ok:
            if self.lost:
                self.readmissions += 1
                telemetry.count("mesh.readmitted", len(self.lost))
                flightrec.record("mesh_device_back",
                                 devices=sorted(self.lost))
            self.lost.clear()
            telemetry.gauge("mesh.degraded_lanes", 0)
        else:
            self.retrips += 1
            self._tripped_at = self._clock()
            telemetry.count("mesh.probe_retrip")


class MeshVerifier:
    """`batch_verify_sharded` wrapped in the recovery ladder (module
    docstring).  `dispatch_fn(tasks, rng, device_ids)` is injectable so
    the tier-1 state-machine tests run without compiling mesh
    executables; the default is the real sharded entry point."""

    def __init__(self, n_devices: int | None = None,
                 readmit_cooldown_s: float = 1.0, clock=time.monotonic,
                 dispatch_fn=None, available_fn=None, result_cast=bool):
        self._requested = n_devices
        self._clock = clock
        self._cooldown = float(readmit_cooldown_s)
        self._dispatch_fn = dispatch_fn
        self._available_fn = available_fn
        # what a settled verdict is coerced to: bool for the RLC verify
        # path (the default), identity (None) for payload dispatchers
        # whose result is structured — e.g. the sharded epoch step's
        # (balances, eff, roots) tuple via `sharded_epoch_verifier`
        self._result_cast = result_cast if result_cast is not None \
            else (lambda out: out)
        self._state: MeshState | None = None
        self.redispatches = 0
        self.verified_statements = 0
        self.lost_statements = 0
        self.max_degraded_lanes = 0
        self.recovery_latencies: list[float] = []

    # --- lazies (no jax before the first verify) -----------------------------

    def _available(self) -> int:
        if self._available_fn is not None:
            return int(self._available_fn())
        from ..parallel.partition import available_devices

        return available_devices()

    @property
    def state(self) -> MeshState:
        if self._state is None:
            n = self._requested or self._available()
            self._state = MeshState(min(n, self._available()),
                                    readmit_cooldown_s=self._cooldown,
                                    clock=self._clock)
        return self._state

    def _dispatch(self, tasks, rng, device_ids):
        if self._dispatch_fn is not None:
            return self._dispatch_fn(tasks, rng, device_ids)
        from ..ops import bls_batch

        return bls_batch.batch_verify_sharded_async(
            tasks, rng=rng, device_ids=device_ids)

    # --- the recovery ladder -------------------------------------------------

    def verify_async(self, tasks, rng=None):
        """Dispatch over the current (possibly shrunken) mesh and return
        a `DeviceFuture` whose settle runs the recovery ladder: device
        failures re-bucket the SAME statements over the survivors until
        a verdict lands or no device remains.  A due re-admission
        cooldown turns this dispatch into the full-mesh probe."""
        from ..serve.futures import DeviceFuture, FutureTimeout

        state = self.state
        probing = state.probe_due()
        ids = (tuple(range(state.n_devices)) if probing
               else state.surviving())
        if not ids:
            # every device is lost and the re-admission cooldown has
            # not elapsed: these statements are dropped, and that must
            # be COUNTED (the mesh-lost-statements gate) and surfaced
            # as the typed device failure, not a dispatch-layer assert
            self.lost_statements += len(tasks)
            telemetry.count("mesh.lost_statements", len(tasks))
            return DeviceFuture.failed(MeshDeviceLost(
                "dispatch", "mesh-exhausted", "device_loss"))
        attempt = {"fut": None, "ids": ids, "probing": probing,
                   "t_fail0": None}
        try:
            attempt["fut"] = self._dispatch(tasks, rng, ids)
        except Exception as exc:
            if not is_device_failure(exc):
                return DeviceFuture.failed(exc)
            self._on_device_failure(attempt, exc)

        def settle(fut, timeout=None):
            # bounded-wait contract: the remaining budget is threaded
            # into each inner settle; an exhausted budget returns with
            # `fut` still pending (the future raises the typed
            # FutureTimeout, the attempt state survives for a retry)
            deadline = (None if timeout is None
                        else time.perf_counter() + float(timeout))
            while True:
                if attempt["fut"] is None:      # re-dispatch after a loss
                    ids2 = attempt["ids"]
                    if not ids2:
                        self.lost_statements += len(tasks)
                        telemetry.count("mesh.lost_statements",
                                        len(tasks))
                        fut.set_exception(attempt["exc"])
                        return
                    self.redispatches += 1
                    telemetry.count("mesh.redispatch")
                    try:
                        attempt["fut"] = self._dispatch(tasks, rng, ids2)
                    except Exception as exc:
                        if not is_device_failure(exc):
                            fut.set_exception(exc)
                            return
                        self._on_device_failure(attempt, exc)
                        continue
                remaining = None
                if deadline is not None:
                    remaining = deadline - time.perf_counter()
                    if remaining <= 0:
                        return              # budget gone, still pending
                try:
                    ok = attempt["fut"].result(timeout=remaining)
                except FutureTimeout:
                    # inner wait ran out — re-loop so an early inner
                    # timeout still consumes the caller's full budget
                    # (the outer future then raises the typed
                    # FutureTimeout, still pending; retry is legal)
                    continue
                except Exception as exc:
                    if not is_device_failure(exc):
                        fut.set_exception(exc)
                        return
                    attempt["fut"] = None
                    self._on_device_failure(attempt, exc)
                    continue
                self._on_success(attempt, len(tasks))
                fut.set_result(self._result_cast(ok))
                return

        return DeviceFuture(waiter=settle)

    def verify(self, tasks, rng=None) -> bool:
        """Synchronous facade over `verify_async`."""
        return self.verify_async(tasks, rng=rng).result()

    def dispatch(self, payload):
        """Payload-shaped facade over the same recovery ladder for
        non-RLC dispatchers (the sharded epoch step): `payload` is
        whatever the injected `dispatch_fn` consumes, and the settled
        value passes through `result_cast` (identity for structured
        results).  Statement accounting counts payload items."""
        return self.verify_async(payload, rng=None).result()

    def _on_device_failure(self, attempt: dict, exc: BaseException) -> None:
        state = self.state
        now = self._clock()
        if attempt["t_fail0"] is None:
            attempt["t_fail0"] = now
        if attempt["probing"]:
            state.record_probe(False)
            attempt["probing"] = False
        else:
            state.mark_lost()
        self.max_degraded_lanes = max(self.max_degraded_lanes,
                                      len(state.lost))
        attempt["ids"] = state.surviving()
        attempt["fut"] = None
        attempt["exc"] = exc

    def _on_success(self, attempt: dict, n_tasks: int) -> None:
        state = self.state
        if attempt["probing"]:
            state.record_probe(True)
        if attempt["t_fail0"] is not None:
            dt = self._clock() - attempt["t_fail0"]
            self.recovery_latencies.append(dt)
            telemetry.observe("mesh.recovery_latency_s", dt)
            # cost seam presence for the recovery arc: the re-dispatch
            # lands on a fresh (n_devices, per_shard) executable, so a
            # CST_COSTMODEL round should see the post-loss memory state
            from ..telemetry import costmodel

            costmodel.sample_watermark("mesh.recovered")
        self.verified_statements += n_tasks

    # --- accounting (the "mesh" resilience sub-block) ------------------------

    def block(self) -> dict:
        """JSON-able `"mesh"` sub-object for the resilience block.
        `recovered` is the 0/1 gate surface: every observed loss
        produced a recovered verdict and nothing was dropped."""
        state = self.state
        last = (self.recovery_latencies[-1]
                if self.recovery_latencies else None)
        recovered = (self.lost_statements == 0
                     and (state.lost_events == 0
                          or len(self.recovery_latencies) >= 1))
        return {
            "recovered": recovered,
            "devices": state.n_devices,
            "degraded_lanes": len(state.lost),
            "max_degraded_lanes": self.max_degraded_lanes,
            "device_lost_events": state.lost_events,
            "readmissions": state.readmissions,
            "retrips": state.retrips,
            "redispatches": self.redispatches,
            "recoveries": len(self.recovery_latencies),
            "recovery_latency_s": (round(last, 6)
                                   if last is not None else None),
            "verified_statements": self.verified_statements,
            "lost_statements": self.lost_statements,
        }


def sharded_epoch_verifier(params, n_devices: int | None = None,
                           axis: str = "data", **kw) -> MeshVerifier:
    """`MeshVerifier` over the partition-registry sharded epoch step:
    the `device_ids`-subset fallback covers the flagship step, not just
    the RLC batch.  `verify_async`/`dispatch` takes the epoch-step
    payload `(reg, sc, length, pubkey_root, credentials)` (host/global
    arrays) and settles to the host `(new_bal, new_eff, balances_root,
    registry_root)` tuple; a lost device re-shards the SAME state over
    the surviving `mesh_rung` power-of-two subset
    (`parallel.partition.epoch_step_dispatcher`)."""
    from ..parallel.partition import epoch_step_dispatcher

    return MeshVerifier(n_devices=n_devices,
                        dispatch_fn=epoch_step_dispatcher(params,
                                                          axis=axis),
                        result_cast=None, **kw)
