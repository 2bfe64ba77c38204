"""Batched block executor — the TPU-first state-transition entry point.

`state_transition(spec, state, block)` in the executable spec verifies
every aggregate signature inline (pure host pairings).  This executor
restructures that for the device: the spec runs with its
FastAggregateVerify pairings *deferred* (inputs still validated eagerly),
and all recorded statements — attestations, sync aggregates, indexed
attestations from slashings — settle afterwards in ONE random-linear-
combination batch on the accelerator (`ops.bls_batch.batch_verify`: B+1
pairings folded through a shared Fq12 Miller accumulator, one final
exponentiation, and the 32-byte signing-root hash-to-curve also on
device — the whole batch is device-resident end to end).  Semantics match the inline path for
every valid block; an invalid aggregate signature surfaces as the batch
check failing (AssertionError), the same acceptance boundary as the spec.

Individual signatures (proposer, randao, exits, deposits) stay eager:
deposits with bad signatures are *valid* blocks per the spec, so their
checks must resolve before affecting control flow.

Breaker-driven degradation (the resilience layer, PR 8's serve-only
recovery extended here): `settle_deferred` guards the batch-settle
phase with a per-phase circuit breaker (`arm_breakers` /
`state_transition_batched(breakers=...)`).  A device-batch failure
records into the breaker and the SAME statements settle on the
pure-Python spec oracle (per-statement host pairing checks —
bit-identical verdicts, just slow); while the breaker is OPEN the
settle skips the device entirely, and the half-open probe re-closes it
once the device answers again.  Degraded settles are counted
(`flagship.degraded_steps` / `degraded_steps()`), surfaced as the
chaos round's `"flagship"` block.  Unarmed (the default) the settle
path is one None check — block-import semantics are unchanged.

Reference seam being replaced: `eth2spec/utils/bls.py:141-296`'s native
milagro calls inside `state_transition` (specs/phase0/beacon-chain.md
:1358-1381).
"""

from __future__ import annotations

from . import telemetry
from .telemetry import costmodel
from .ops import bls

# --- breaker-guarded settle (the flagship's recovery ladder) -----------------

# armed registry (None == plain fail-fast settle) + degraded accounting;
# the last device failure is kept for introspection/the chaos block
_breakers = None
_degraded_steps = 0
last_degraded_error: BaseException | None = None

SETTLE_BREAKER_KEY = "flagship::batch_settle"


def arm_breakers(registry=None):
    """Arm (or replace) the module-level breaker registry every
    `state_transition_batched` call consumes; `registry=None` builds a
    default `resilience.BreakerRegistry()`.  Returns the armed
    registry.  `disarm_breakers()` restores fail-fast semantics."""
    global _breakers
    if registry is None:
        from .resilience.policies import BreakerRegistry

        registry = BreakerRegistry()
    _breakers = registry
    return registry


def disarm_breakers() -> None:
    global _breakers
    _breakers = None


def armed_breakers():
    return _breakers


def degraded_steps() -> int:
    """Settles answered by the spec oracle instead of the device since
    the last reset — the `flagship::degraded_steps` surface."""
    return _degraded_steps


def reset_degraded_steps() -> None:
    global _degraded_steps
    _degraded_steps = 0


def _oracle_settle_tasks(tasks) -> bool:
    """The pure-Python spec oracle for a deferred batch: per-statement
    host pairing checks, bit-identical to the device RLC verdict.
    Routed through the serve executor's MEMOIZED oracle — one pairing
    check per DISTINCT statement, not per settle: consecutive blocks
    re-settling overlapping attestations during a breaker-open stretch
    must not re-pay the seconds-per-statement pure-Python cost."""
    from .serve.executor import _oracle_verify

    return all(_oracle_verify(t) for t in tasks)


def _count_degraded(n_statements: int) -> None:
    global _degraded_steps
    _degraded_steps += 1
    telemetry.count("flagship.degraded_steps")
    telemetry.count("flagship.degraded_statements", n_statements)


def settle_deferred(batch, device: bool | None = None,
                    breakers=None) -> bool:
    """Settle a `DeferredBatch` through the per-phase breaker ladder.

    CLOSED: settle on the device as always (successes re-close /
    reset).  A device failure records into the breaker and the same
    statements re-settle on the spec oracle — degraded, counted, still
    correct.  OPEN: skip the device, answer on the oracle.  HALF_OPEN:
    `allow()` admits this settle as the probe; its outcome re-closes or
    re-trips.  `breakers=None` uses the module-armed registry; with
    neither, this is exactly `batch.verify(device=...)`."""
    global last_degraded_error
    registry = breakers if breakers is not None else _breakers
    br = None
    if registry is not None and batch.tasks and not batch.failed:
        br = registry.get(SETTLE_BREAKER_KEY)
    if br is not None and not br.allow():
        _count_degraded(len(batch.tasks))
        with telemetry.span("executor.degraded_settle",
                            statements=len(batch.tasks), reason="open"):
            return batch.verify(device=False)
    try:
        ok = batch.verify(device=device)
    except Exception as exc:
        # ANY settle exception (a False verdict is a return, never a
        # raise) walks the ladder — special-casing AssertionError here
        # would leave a HALF_OPEN probe's `_probe_inflight` set forever
        # and wedge the flagship onto the oracle permanently
        if br is None:
            raise
        br.record_failure()
        last_degraded_error = exc
        telemetry.count("flagship.settle_failures")
        _count_degraded(len(batch.tasks))
        # batch.verify already settled its handles with the exception;
        # the block verdict still resolves on the oracle so the import
        # completes correctly in degraded mode
        with telemetry.span("executor.degraded_settle",
                            statements=len(batch.tasks),
                            reason="device_failure"):
            return _oracle_settle_tasks(batch.tasks)
    if br is not None:
        br.record_success()
    return ok


def state_transition_batched(spec, state, signed_block,
                             validate_result: bool = True,
                             device: bool | None = None,
                             breakers=None):
    """Run `spec.state_transition` with aggregate pairings batched on the
    device.  Raises AssertionError exactly where the spec would (plus at
    the end if the signature batch fails); on failure the state is
    partially advanced — run on a copy, as `on_block` does.

    Each phase (slot advance, block body, batch settle, state-root
    check) runs under a telemetry span, so a `CST_TRACE_FILE` capture of
    a block import decomposes into per-phase wall time; on
    CST_COSTMODEL rounds each phase boundary also samples the
    per-device live-buffer watermark (`costmodel.sample_watermark`), so
    the same capture shows where device-memory pressure peaks inside a
    block import."""
    block = signed_block.message
    with telemetry.span("executor.state_transition_batched",
                        slot=int(block.slot)):
        costmodel.sample_watermark("executor.start")
        with telemetry.span("executor.process_slots"):
            spec.process_slots(state, block.slot)
        costmodel.sample_watermark("executor.process_slots")
        if validate_result:
            with telemetry.span("executor.verify_block_signature"):
                assert spec.verify_block_signature(state, signed_block)
        with bls.deferred_batch_verification() as batch:
            with telemetry.span("executor.process_block"):
                spec.process_block(state, block)
        costmodel.sample_watermark("executor.process_block")
        # settle is once-only (DeferredBatch caches the verdict and
        # resolves every recorded handle); the gauge mirrors the serve
        # executor's queue-depth track in block-import traces
        telemetry.gauge("executor.deferred_statements", len(batch.tasks))
        with telemetry.span("executor.batch_settle",
                            statements=len(batch.tasks)):
            ok = settle_deferred(batch, device=device, breakers=breakers)
        costmodel.sample_watermark("executor.batch_settle")
        assert ok, "batched aggregate-signature verification failed"
        if validate_result:
            with telemetry.span("executor.state_root_check"):
                assert block.state_root == spec.hash_tree_root(state)
            costmodel.sample_watermark("executor.state_root_check")
    return state
