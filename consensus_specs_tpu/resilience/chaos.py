"""Chaos rounds: the mainnet arrival mix under an active fault plan.

`run_chaos_load` (armed by `CST_SERVE_CHAOS=1` in `serve.loadgen`)
drives the serve executor through three phases and requires the service
to come back:

    baseline    closed-loop windows until throughput is steady — the
                healthy-rate reference.
    chaos       the fault plan (CST_FAULTS, or the canned
                `DEFAULT_CHAOS_SPEC` injecting dispatch failures into
                the RLC kernel) is installed; the executor runs with
                the recovery policies armed (retry + per-(kind, rung)
                breakers + oracle fallback), so every request still
                answers CORRECTLY — degraded throughput is measured,
                wrong answers are counted (and must be zero).
    recovery    the plan is cleared; the run continues until throughput
                is steady again.  `recovery_latency_s` — fault stop to
                steady-state re-detection — is the round's recovery
                figure.

Every submitted request is tracked with its EXPECTED outcome (the pool
statements are valid → True; sha256/fr expectations precomputed on the
host oracle), so "zero wrong verification results" is a measured
property of the whole round, not an assumption.

Request tracing is armed for the whole round (telemetry.reqtrace,
regardless of CST_TRACE_REQUESTS): the serve block carries per-request
p50/p99 + the `latency_attribution` decomposition, and the
`"resilience"` block's `fault_victims` correlates every injected fault
with the trace ids it hit and their final outcomes — pinning the blast
radius to exactly the retried/fallback-answered/poisoned handles (a
fault victim can never settle with a clean `ok`).

The SLO watchdog (`telemetry.monitor`) is armed for the whole round on
a deterministic per-window tick: the canned `CHAOS_SLO_RULE` watches
the fired-fault count (plus any `CST_SLO_RULES` the operator set), and
the round ASSERTS the arc both ways — the rule breaches inside the
fault window and clears after recovery — so every chaos run regression-
tests the watchdog itself.  The evidence rides the block as the `"slo"`
sub-object plus `resilience["slo_arc"]`.

Deterministic closing segments (each oracle-checked, each feeding its
own sub-block of the `"resilience"` object):

    heal        corrupts a `MerkleForest` update under a corrupt fault
                and drives detect→quarantine→recover
                (`healing.heal_forest`) — now through CHECKPOINT
                RESTORE when a valid snapshot exists (`heal["path"]`
                records which recovery ran).
    checkpoint  kills and resurrects a forest mid-round: snapshot →
                journaled updates → drop the live stack → restore
                (snapshot + journal replay, checksum-verified) vs a
                full rebuild, root parity against the independent
                host-oracle rebuild — the `checkpoint-restore`
                threshold row's measurement (≥5x at ≤1% journal
                depth).
    flagship    the block executor's breaker ladder
                (`executor.settle_deferred`): a dispatch fault trips
                the settle breaker to the pure-Python spec oracle,
                degraded steps are counted, the half-open probe
                re-closes — `flagship::degraded_steps`.
    mesh        (CST_CHAOS_MESH=1, needs ≥2 devices — the simulated
                8-host-device CI lane or a real mesh) `device_loss`
                into `batch_verify_sharded`: the lost shard's
                statements re-bucket over the surviving devices
                (`resilience.mesh.MeshVerifier`), an invalid statement
                still rejects while degraded, and the re-admission
                probe restores the full mesh.

Returns `serve.loadgen.run_load`'s block shape (schema:
`telemetry.export.validate_serve_block`) plus a `"resilience"`
sub-object (schema: `validate_resilience_block`).
"""

from __future__ import annotations

import time

from .. import telemetry
from ..telemetry import metrics_export, monitor, reqtrace
from . import faults, healing
from .policies import BreakerRegistry, RetryPolicy

# the canned plan (used when CST_FAULTS is unset): four dispatch
# failures into the RLC verify kernel — enough to trip a threshold-2
# breaker through retry, exercise the oracle-fallback degraded mode,
# fail at least one half-open probe, and then let the device recover
DEFAULT_CHAOS_SPEC = "seed=1234;dispatch:raise:key=rlc_*:count=4"

# the chaos round's canned SLO rule: the fired-fault count is the one
# signal that is 1:1 with the plan being live (whatever traffic shape
# the round measured), so the watchdog arc — breach INSIDE the fault
# window, clear after recovery — is deterministic.  The round asserts
# both directions, which regression-tests the watchdog itself.
CHAOS_SLO_RULE = {"metric": "counter.faults.injected", "op": "<=",
                  "threshold": 0.0, "for": 1, "clear": 2,
                  "name": "chaos-fault-injections"}

# chaos-round policy shape: trip fast, probe fast — the smoke must see
# the full open→half-open→closed arc inside a handful of windows
CHAOS_RETRY = dict(max_attempts=2, base_backoff_s=0.01, max_backoff_s=0.1)
CHAOS_BREAKER = dict(threshold=2, cooldown_s=0.5)

_TRACK_CAP = 200_000     # correctness-tracking memory bound
_VICTIM_IDS_CAP = 64     # trace ids listed verbatim in the block


def _fault_victims() -> dict:
    """Blast-radius correlation (request tracing): the trace ids whose
    dispatch/settle hit an injected fault, with their final outcomes.
    The pin the chaos smoke asserts — a fault-hit request may recover
    (retry) or degrade (fallback) or poison, but it can never settle
    with a clean 'ok': the executor marks every member of a
    FaultInjected batch, so blast radius == exactly these handles."""
    victims = [r for r in reqtrace.records() if r.get("faulted")]
    outcomes: dict[str, int] = {}
    for r in victims:
        outcomes[r["outcome"]] = outcomes.get(r["outcome"], 0) + 1
    return {
        "count": len(victims),
        "trace_ids": [r["trace_id"] for r in victims[:_VICTIM_IDS_CAP]],
        "outcomes": outcomes,
        "clean_ok": outcomes.get("ok", 0),   # must stay 0
    }


def _expectations(payloads):
    """Host-oracle expected values for the checkable request kinds."""
    import numpy as np

    from ..ops.sha256_np import merkleize_words
    from ..serve.executor import _oracle_barycentric

    words, limit_depth = payloads["sha256"]
    return {
        "sha256": merkleize_words(np.asarray(words, dtype=np.uint32),
                                  limit_depth),
        "fr": _oracle_barycentric(*payloads["fr"]),
    }


def _check_results(tracked, expected) -> dict:
    """Settle accounting over the tracked (kind, future) pairs: wrong
    values vs the oracle expectations, and exception-settled requests
    (typed failures — visible, but not wrong answers)."""
    import numpy as np

    wrong = 0
    failed = 0
    checked = 0
    for kind, fut in tracked:
        exc = fut.exception()
        if exc is not None:
            failed += 1
            continue
        value = fut.result()
        checked += 1
        if kind in ("verify", "pairing"):
            if value is not True:
                wrong += 1
        elif kind == "sha256":
            if not np.array_equal(np.asarray(value),
                                  expected["sha256"]):
                wrong += 1
        elif kind == "fr":
            if int(value) != expected["fr"]:
                wrong += 1
        elif kind == "proof":
            if not isinstance(value, list) or not value:
                wrong += 1
        elif kind == "das":
            # every lane sample is a valid closed-form column: the
            # only correct verdict is True (the oracle fallback's
            # host route included)
            if value is not True:
                wrong += 1
        elif kind == "fc_atts":
            # an accepted-count outside [0, batch] is impossible on
            # both routes (the store mutates under load, so the exact
            # count is schedule-dependent, not a fixed expectation)
            if not isinstance(value, int) or value < 0:
                wrong += 1
        elif kind == "head":
            # both routes answer a 32-byte block root out of the store
            if not (isinstance(value, bytes) and len(value) == 32):
                wrong += 1
    return {"wrong": wrong, "failed": failed, "checked": checked}


def _heal_segment() -> dict:
    """The self-healing Merkle arc, run deterministically: one update
    under a corrupt fault diverges a small forest; the detector
    quarantines it and recovery re-serves — via CHECKPOINT RESTORE
    (snapshot taken before the corruption, the corrupt update's honest
    delta in the journal) when the snapshot is valid, else the full
    rebuild.  The taken path is recorded (`heal["path"]`)."""
    import tempfile

    import numpy as np

    from ..parallel.incremental import MerkleForest
    from . import checkpoint as ckpt

    rng = np.random.RandomState(97)
    n = 256
    words = rng.randint(0, 2**32, (n, 8), dtype=np.uint64).astype(np.uint32)
    forest = MerkleForest(words, 10, n)
    with tempfile.TemporaryDirectory(prefix="cst_heal_ckpt_") as tmp:
        mgr = ckpt.CheckpointManager(ckpt.env_dir() or tmp, name="heal")
        forest.checkpoint = mgr
        mgr.snapshot(forest)
        faults.install({"seed": 5, "faults": [
            {"site": "merkle_update", "kind": "corrupt", "count": 1}]})
        try:
            # the corrupt fault damages the dispatched interior layers;
            # the journal records the HONEST delta, so the checkpoint
            # path restores exactly the reference state
            forest.update([3], rng.randint(
                0, 2**32, (1, 8), dtype=np.uint64).astype(np.uint32))
        finally:
            faults.clear()
        detected = healing.forest_diverged(forest)
        report = healing.heal_forest(forest)
    return {
        "detected": bool(detected),
        "diverged": bool(report.diverged),
        "recovery_s": (round(report.recovery_s, 6)
                       if report.recovery_s is not None else None),
        "path": report.path,
        "n_chunks": n,
    }


def _checkpoint_segment(n_log2: int = 20, update_chunks: int = 256,
                        updates: int = 2) -> dict:
    """Kill-and-resurrect: snapshot a forest, journal a ≤1% dirty
    stream, drop the live layer stack, then race checkpoint restore
    (snapshot load + journal replay, zero full re-hash) against the
    full O(N) rebuild.  Root parity is asserted against both the live
    pre-kill root and the independent pure-host oracle rebuild.  Reports
    speedup = rebuild/restore, best-of-2 each so first-touch I/O noise
    cancels.

    2^20 chunks is the acceptance shape: big
    enough that the O(N) rebuild dominates restore's fixed I/O +
    root-fetch floor — the CPU smoke measures ~8x there, vs ~4x at
    2^17 where a rebuild is only ~0.7s."""
    import tempfile

    import numpy as np

    from ..parallel.incremental import MerkleForest
    from . import checkpoint as ckpt

    rng = np.random.RandomState(53)
    n = 1 << n_log2
    limit_depth = n_log2 + 2
    words = rng.randint(0, 2**32, (n, 8), dtype=np.uint64).astype(np.uint32)
    with telemetry.span("resilience.chaos.checkpoint_segment", n=n):
        forest = MerkleForest(words, limit_depth, n)
        with tempfile.TemporaryDirectory(prefix="cst_ckpt_") as tmp:
            mgr = ckpt.CheckpointManager(ckpt.env_dir() or tmp,
                                         name="chaos")
            forest.checkpoint = mgr
            mgr.snapshot(forest)
            for _ in range(updates):
                idx = np.unique(rng.choice(n, update_chunks,
                                           replace=False))
                leaves = rng.randint(0, 2**32, (idx.shape[0], 8),
                                     dtype=np.uint64).astype(np.uint32)
                forest.update(idx, leaves)      # journaled via the hook
            expected = forest.root_bytes()
            reference = healing._reference_root_bytes(forest)
            final_leaves = np.asarray(forest.layers[0])[:n]
            journal_frac = mgr.journal_depth_frac(n)
            del forest                          # the "process death"

            restore_s = None
            parity = True
            replayed = 0
            for _ in range(2):                  # best-of-2
                t0 = time.perf_counter()
                restored = mgr.restore()
                root = restored.root_bytes()
                dt = time.perf_counter() - t0
                restore_s = dt if restore_s is None else min(restore_s, dt)
                replayed = restored.restored_journal_entries
                parity = parity and root == expected == reference
            rebuild_s = None
            for _ in range(2):
                t0 = time.perf_counter()
                rebuilt = MerkleForest(final_leaves, limit_depth, n)
                root = rebuilt.root_bytes()
                dt = time.perf_counter() - t0
                rebuild_s = dt if rebuild_s is None else min(rebuild_s, dt)
                parity = parity and root == expected
            speedup = rebuild_s / restore_s if restore_s else None
    telemetry.observe("checkpoint.restore_s", restore_s)
    return {
        "n_chunks": n,
        "journal_entries": mgr.journal_entries,
        "journal_replayed": replayed,
        "journal_frac": round(journal_frac, 5),
        "snapshot_bytes": mgr.snapshot_bytes,
        "restore_s": round(restore_s, 6),
        "rebuild_s": round(rebuild_s, 6),
        "speedup": round(speedup, 2) if speedup is not None else None,
        "parity": bool(parity),
    }


def _flagship_segment() -> dict:
    """The block executor's breaker arc: a healthy device settle, a
    dispatch fault that trips the settle breaker onto the pure-Python
    spec oracle (verdicts stay correct), an OPEN-breaker settle served
    entirely by the oracle, then the half-open probe re-closing on the
    recovered device.  Counts `flagship::degraded_steps`."""
    from .. import executor as flagship
    from ..ops import bls
    from ..serve.loadgen import build_statement_pool
    from .policies import BreakerRegistry

    pool = build_statement_pool(2, 2, seed_base=9100)
    # injected clock: the pure-Python oracle settle takes seconds, so a
    # wall-clock cooldown would silently elapse mid-arc and turn the
    # OPEN-breaker settle into the probe — the arc must be deterministic
    clk = [0.0]
    registry = BreakerRegistry(threshold=1, cooldown_s=0.5,
                               clock=lambda: clk[0])
    flagship.reset_degraded_steps()
    wrong = 0
    checked = 0

    def one_settle(expect: bool = True) -> None:
        nonlocal wrong, checked
        batch = bls.DeferredBatch()
        batch.tasks = list(pool)
        ok = flagship.settle_deferred(batch, device=True,
                                      breakers=registry)
        checked += 1
        if bool(ok) is not expect:
            wrong += 1

    with telemetry.span("resilience.chaos.flagship_segment"):
        one_settle()                    # healthy: device settle
        faults.install({"seed": 9, "faults": [
            {"site": "dispatch", "kind": "raise", "key": "rlc_*",
             "count": 1}]})
        try:
            one_settle()                # device fails → trip → oracle
            one_settle()                # breaker OPEN → oracle directly
        finally:
            faults.clear()
        clk[0] = 1.0                    # past the cooldown
        one_settle()                    # half-open probe → re-close
    states = registry.states()
    return {
        "degraded_steps": flagship.degraded_steps(),
        "wrong_results": wrong,
        "checked_settles": checked,
        "breaker": registry.summary(),
        "recovered": all(s == "closed" for s in states.values()),
    }


def mesh_enabled() -> bool:
    """The CST_CHAOS_MESH knob: arm the simulated-mesh shard-loss
    segment (needs ≥2 devices; the chaos-mesh CI lane forces 8 host
    devices via XLA_FLAGS)."""
    import os

    return os.environ.get("CST_CHAOS_MESH", "0") not in ("", "0")


def _mesh_segment() -> dict:
    """The shard-loss recovery arc on a real (or simulated) mesh:
    healthy full-mesh verifies, one injected `device_loss` at the
    sharded dispatch seam → the verifier re-buckets the SAME statements
    over the surviving n-1 devices (degraded mode, zero wrong/dropped),
    an INVALID statement still rejects while degraded, and after the
    cooldown the half-open probe re-admits the full mesh.  Every
    verdict is checked against the host-oracle expectation."""
    import jax

    from ..serve.loadgen import build_statement_pool
    from .mesh import MeshVerifier

    available = len(jax.devices())
    if available < 2:
        return {"skipped": f"{available} device(s) — mesh segment "
                           f"needs >= 2", "devices": available}

    pool = build_statement_pool(4, 2, seed_base=8200)
    # an invalid statement: statement 0's message with statement 1's
    # signature — FastAggregateVerify must reject it, degraded or not
    bad = (pool[0][0], pool[0][1], pool[1][2])
    # offset clock: recovery latency must be REAL wall (the n-1
    # re-dispatch compiles a fresh executable — that IS the recovery
    # cost), but the re-admission probe must fire exactly when the
    # segment says so — a wall-clock cooldown would elapse during that
    # same compile and silently turn the degraded-mode checks below
    # into full-mesh ones
    offset = [0.0]

    def clock():
        return time.monotonic() + offset[0]

    verifier = MeshVerifier(n_devices=available,
                            readmit_cooldown_s=3600.0, clock=clock)
    wrong = 0
    dropped = 0
    checked = 0

    def check(tasks, expect: bool) -> None:
        nonlocal wrong, dropped, checked
        try:
            ok = verifier.verify(list(tasks))
        # cst: allow(exc-swallow-device): the segment's contract IS counting dropped statements; the verifier already classified and recorded the failure
        except Exception:
            dropped += len(tasks)
            return
        checked += len(tasks)
        if bool(ok) is not expect:
            wrong += len(tasks)

    with telemetry.span("resilience.chaos.mesh_segment",
                        devices=available):
        check(pool, True)               # healthy full-mesh baseline
        faults.install({"seed": 77, "faults": [
            {"site": "dispatch", "kind": "device_loss",
             "key": "rlc_sharded@*", "count": 1}]})
        try:
            check(pool, True)           # loss fires → recover on n-1
        finally:
            faults.clear()
        check(pool, True)               # still degraded (cooldown held)
        check(pool + [bad], False)      # invalid rejects while degraded
        assert verifier.state.degraded(), (
            "degraded-mode checks must run on the shrunken mesh")
        offset[0] += 3600.0             # cooldown elapses, on our terms
        check(pool, True)               # probe re-admits the full mesh
    block = verifier.block()
    block.update({
        "wrong_results": wrong,
        "dropped_statements": dropped,
        "checked_statements": checked,
        "readmitted": not verifier.state.degraded(),
    })
    return block


def _chaos_slo_rules(window_s: float) -> dict:
    """The chaos watchdog's rule set: the canned injection-rate rule
    (rate window spanning a few load windows, so the breach clears
    within the recovery phase) plus any `CST_SLO_RULES` the operator
    armed — those are evaluated on the same deterministic ticks.  A
    malformed env set is skipped with the counted warning
    (`install_from_env`'s contract), never killing the round; an env
    rule whose name collides with an already-merged one is dropped."""
    import os
    import sys

    rule = dict(CHAOS_SLO_RULE)
    rule["window_s"] = max(3.0 * window_s, 0.5)
    rules = {"rules": [rule]}
    source = os.environ.get("CST_SLO_RULES")
    if source:
        try:
            extra = monitor.load_rules(source)
        except ValueError as exc:       # json.JSONDecodeError included
            telemetry.count("slo.rules_invalid")
            print(f"slo: ignoring invalid CST_SLO_RULES: {exc}",
                  file=sys.stderr)
            return rules
        seen = {r.get("name") or monitor._default_name(r["metric"],
                                                       r.get("kind"))
                for r in rules["rules"]}
        for r in extra["rules"]:
            name = r.get("name") or monitor._default_name(r["metric"],
                                                          r.get("kind"))
            if name not in seen:
                seen.add(name)
                rules["rules"].append(r)
        if "tick_s" in extra:
            rules["tick_s"] = extra["tick_s"]
    return rules


def run_chaos_load(cfg=None, plan=None) -> dict:
    """See the module docstring.  `cfg` is a `serve.loadgen.LoadConfig`
    (env defaults otherwise); chaos rounds are always closed-loop (an
    open-loop clock under faults measures the clock, not the service).
    `plan` overrides CST_FAULTS / the canned default."""
    from ..serve.loadgen import config_from_env

    cfg = cfg if cfg is not None else config_from_env()
    if plan is None:
        plan = faults.plan_from_env_source() or DEFAULT_CHAOS_SPEC
    plan = faults.load_plan(plan)

    # request tracing is part of the chaos contract: the blast-radius
    # correlation (which trace ids a fault hit, and how each settled)
    # needs per-request contexts, so the round arms them regardless of
    # CST_TRACE_REQUESTS and restores the prior state afterwards
    was_tracing = reqtrace.enabled()
    reqtrace.configure(enabled=True)
    try:
        return _run_chaos_load(cfg, plan)
    finally:
        reqtrace.configure(enabled=was_tracing)


def _run_chaos_load(cfg, plan) -> dict:
    from ..serve.executor import ServeExecutor
    from ..serve.loadgen import (
        DAS_SAMPLES_PER_SLOT,
        FC_ATTS_PER_SLOT,
        _das_payloads,
        _fc_payload,
        _fr_payload,
        _pairing_payload,
        _proof_payload,
        _sha_payload,
        _warm_kernels,
        build_statement_pool,
        drive_closed_loop,
        latency_block,
        make_submitter,
        steady_state,
    )

    pool = build_statement_pool(cfg.pool, cfg.committee)
    payloads = {"pairing": _pairing_payload(pool[0]),
                "fr": _fr_payload(), "sha256": _sha_payload(),
                "proof": _proof_payload(),
                "das": (_das_payloads() if DAS_SAMPLES_PER_SLOT
                        else []),
                "fc": (_fc_payload() if FC_ATTS_PER_SLOT else None)}
    expected = _expectations(payloads)
    warm_s = _warm_kernels(cfg, pool, payloads)
    # scope the lifecycle records to THIS round's three phases (warmup
    # settles are setup, not served traffic)
    reqtrace.reset()

    breakers = BreakerRegistry(**CHAOS_BREAKER)
    ex = ServeExecutor(max_batch=cfg.max_batch, depth=cfg.depth,
                       retry=RetryPolicy(**CHAOS_RETRY),
                       breakers=breakers)
    tracked: list[tuple] = []

    def track(kind, fut):
        if len(tracked) < _TRACK_CAP:
            tracked.append((kind, fut))

    # the shared mainnet arrival mix + closed-loop drive (loadgen owns
    # both — the chaos round must measure the same traffic shape
    # run_load does, just phased around the fault plan)
    submit_next, kinds_submitted = make_submitter(ex, pool, payloads,
                                                  track=track)
    target_outstanding = cfg.max_batch * (cfg.depth + 1)
    window_s = cfg.duration_s / cfg.windows
    rates: list[float] = []
    settled_prev = 0

    # the SLO watchdog is part of the chaos contract: ticked once per
    # load window (the daemon's wall-clock cadence would race the phase
    # boundaries), it must breach while the plan is live and clear
    # during recovery — asserted below, on the same clock the ticks use.
    # The exposition endpoint is armed too, so a chaos pod round is
    # scrapeable mid-fault.
    metrics_export.start_from_env()
    metrics_export.set_status_provider(ex.status)

    def injected_total(name: str) -> float:
        # the chaos rule's signal: fired faults so far (site-agnostic —
        # a CST_FAULTS override may target any seam)
        if name == "faults.injected":
            return float(len(faults.injections()))
        return telemetry.counter_value(name)

    wd = monitor.install(_chaos_slo_rules(window_s), autostart=False,
                         status_provider=ex.status,
                         counter_provider=injected_total,
                         profile_dir=monitor.profile_dir_from_env())

    def run_window():
        nonlocal settled_prev
        win_t0 = time.perf_counter()
        drive_closed_loop(ex, submit_next, target_outstanding,
                          win_t0 + window_s)
        settled_now = ex.stats()["settled"]
        rates.append((settled_now - settled_prev)
                     / (time.perf_counter() - win_t0))
        settled_prev = settled_now
        wd.tick()

    t0 = time.perf_counter()
    with telemetry.span("resilience.chaos_round"):
        # phase 1: healthy baseline, until steady (≤3x extension)
        for _ in range(3 * cfg.windows):
            run_window()
            if len(rates) >= 3 and steady_state(rates):
                break
        baseline_rate = (sum(rates[-3:]) / 3.0 if len(rates) >= 3
                         else (rates[-1] if rates else 0.0))
        baseline_windows = len(rates)

        # phase 2: the fault plan is live
        t_fault0 = time.monotonic()
        faults.install(plan)
        try:
            for _ in range(cfg.windows):
                run_window()
        finally:
            injected = faults.injections()
            faults.clear()
        t_fault1 = time.monotonic()
        chaos_rates = rates[baseline_windows:]
        degraded_rate = (min(chaos_rates) if chaos_rates else None)

        # phase 3: recovery — run until steady again
        t_clear = time.perf_counter()
        recovery_latency_s = None
        for _ in range(3 * cfg.windows):
            run_window()
            if steady_state(rates):
                recovery_latency_s = time.perf_counter() - t_clear
                break
    measured_s = time.perf_counter() - t0
    ex.drain()
    # let the clear hysteresis drain: the recovery loop may have hit
    # its steady-state break before `clear` consecutive healthy ticks
    # ran (the plan is gone, so every extra tick is healthy)
    for _ in range(2 * CHAOS_SLO_RULE["clear"] + 2):
        if not wd.breaching():
            break
        wd.tick()
    metrics_export.set_status_provider(None)
    slo_block = monitor.clear()

    # the watchdog arc, asserted both ways (it is only required when
    # faults actually fired — a CST_FAULTS plan keyed off this round's
    # traffic never breaches, correctly)
    arc_name = CHAOS_SLO_RULE["name"]
    breached_in_window = any(
        e["phase"] == "breach" and t_fault0 <= e["ts"] <= t_fault1
        for e in slo_block["events"] if e["rule"] == arc_name)
    arc_cleared = arc_name not in slo_block["breaching_now"]
    if injected:
        assert breached_in_window, (
            f"{len(injected)} fault(s) fired but the "
            f"{arc_name!r} SLO rule never breached inside the fault "
            f"window — the watchdog missed a live incident")
        assert arc_cleared, (
            f"the {arc_name!r} SLO rule is still breaching after "
            f"recovery — the clear hysteresis never released")

    # per-request latency basis + tail attribution + the fault→victim
    # correlation, all from the round's lifecycle records (before the
    # closing segments run — they own their own fault plans)
    p50_ms, p99_ms, latency_attribution = latency_block(ex)
    victims = _fault_victims()

    heal = _heal_segment()
    ckpt_block = _checkpoint_segment()
    flagship = _flagship_segment()
    mesh = _mesh_segment() if mesh_enabled() else None
    check = _check_results(tracked, expected)
    st = ex.stats()
    recovered = recovery_latency_s is not None
    steady = recovered and steady_state(rates)
    steady_rate = (sum(rates[-3:]) / 3.0 if len(rates) >= 3 else 0.0)

    by_site: dict[str, int] = {}
    for rec in injected:
        by_site[rec["site"]] = by_site.get(rec["site"], 0) + 1

    block = {
        "verifies_per_s": round(steady_rate, 2),
        "p50_ms": p50_ms,
        "p99_ms": p99_ms,
        "latency_source": "reqtrace",
        "steady": steady,
        "windows": [round(r, 2) for r in rates],
        "window_s": round(window_s, 3),
        "duration_s": round(measured_s, 3),
        "warmup_s": round(warm_s, 3),
        "mode": "closed",
        "rate_multiple": 0.0,
        "offered_per_s": None,
        "pool": cfg.pool,
        "committee": cfg.committee,
        "max_batch": cfg.max_batch,
        "depth": cfg.depth,
        "kinds": kinds_submitted,
        "submitted": st["submitted"],
        "settled": st["settled"],
        "failed": st["failed"],
        "rechecks": st["rechecks"],
        "batches": st["batches"],
        "queue_depth": st["queue_depth"],
        "inflight_max": st["inflight_max"],
        "resilience": {
            "chaos": True,
            "plan": plan.describe(),
            "faults_injected": len(injected),
            "injected_sites": by_site,
            "fault_victims": victims,
            "wrong_results": check["wrong"],
            "failed_requests": check["failed"],
            "checked_results": check["checked"],
            "baseline_verifies_per_s": round(baseline_rate, 2),
            "degraded_verifies_per_s": (round(degraded_rate, 2)
                                        if degraded_rate is not None
                                        else None),
            "recovery_latency_s": (round(recovery_latency_s, 3)
                                   if recovered else None),
            "recovered": recovered,
            "breaker": breakers.summary(),
            "retries": st["retries"],
            "fallbacks": st["fallbacks"],
            "shed": st["shed"],
            "heal": heal,
            "checkpoint": ckpt_block,
            "flagship": flagship,
            "slo_arc": {
                "rule": arc_name,
                "breached_in_fault_window": breached_in_window,
                "cleared_after_recovery": arc_cleared,
            },
        },
    }
    block["slo"] = slo_block
    if latency_attribution is not None:
        block["latency_attribution"] = latency_attribution
    if mesh is not None:
        block["resilience"]["mesh"] = mesh
    return block
