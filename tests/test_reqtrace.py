"""Request-scoped tracing (`telemetry/reqtrace.py`) + its serve-pipeline
wiring: context lifecycle across every outcome, batch lineage,
attribution arithmetic, Chrome-trace flow events, the disabled no-op
bound, the serve-block `latency_attribution` schema, the live
`status()` contract, and the analyzer's
`reqtrace-uncovered-submit` rule.

Executor tests run against stubbed dispatchers (the test_serve.py
pattern — no jax, no kernels), so the lifecycle contracts are pinned
cheaply inside the tier-1 budget.
"""

from __future__ import annotations

import json
import time

import pytest

from consensus_specs_tpu.serve.executor import ServeExecutor
from consensus_specs_tpu.serve.futures import DeviceFuture, FutureTimeout
from consensus_specs_tpu.telemetry import (
    reqtrace,
    validate_latency_attribution,
    validate_serve_block,
)

COMPONENT_SUM_EPS = 1e-6      # components are contiguous: exact to fp


# --- fixtures ---------------------------------------------------------------


@pytest.fixture()
def traced(monkeypatch):
    """Request tracing ON with a clean registry; restores the prior
    enabled state and wipes the test's records afterwards."""
    was = reqtrace.enabled()
    reqtrace.configure(enabled=True)
    reqtrace.reset()
    yield reqtrace
    reqtrace.reset()
    reqtrace.configure(enabled=was)


class _StubOps:
    """Stand-in for ops.bls_batch (the test_serve.py pattern): scripted
    verdict queue, True by default; an Exception verdict fails the
    batch, a DeviceFuture verdict is returned as-is."""

    def __init__(self):
        self.batches: list[int] = []
        self.verdicts: list[object] = []

    def _next(self, default=True):
        return self.verdicts.pop(0) if self.verdicts else default

    def batch_verify_async(self, tasks, block=True):
        self.batches.append(len(tasks))
        v = self._next()
        if isinstance(v, DeviceFuture):
            return v
        if isinstance(v, Exception):
            return DeviceFuture.failed(v)
        return DeviceFuture.settled(v)

    def pairing_check_device_async(self, pairs, block=True):
        return DeviceFuture.settled(self._next())


@pytest.fixture()
def stub_ops(monkeypatch):
    from consensus_specs_tpu.serve import executor as ex_mod

    stub = _StubOps()
    monkeypatch.setattr(ex_mod, "_ops_bls_batch", lambda: stub)
    return stub


def _task():
    return ("pk", b"m", "sig")


# --- context lifecycle across outcomes ---------------------------------------


def test_ok_lifecycle_and_component_sum(traced, stub_ops):
    ex = ServeExecutor(max_batch=4, depth=1)
    fut = ex.submit_verify_task(_task())
    ctx = fut.ctx
    assert ctx is not None and ctx.kind == "verify"
    assert not ctx.done and ctx.outcome is None
    ex.drain()
    assert fut.result() is True
    assert ctx.done and ctx.outcome == "ok" and ctx.attempts == 1
    # timestamps are ordered and the contiguous components sum to e2e
    assert ctx.t_submit <= ctx.t_enqueue <= ctx.t_dispatch \
        <= ctx.t_complete
    total = sum(ctx.components.values())
    assert abs(total - ctx.end_to_end_s()) < COMPONENT_SUM_EPS
    assert ctx.components["detour"] == 0.0
    recs = traced.records()
    assert len(recs) == 1 and recs[0]["trace_id"] == ctx.trace_id


def test_recheck_outcome(traced, stub_ops, monkeypatch):
    monkeypatch.setattr(ServeExecutor, "_verify_single",
                        lambda self, task: task[0] == "good")
    ex = ServeExecutor(max_batch=2, depth=1)
    f_good = ex.submit_verify_task(("good", b"m", "sig"))
    f_bad = ex.submit_verify_task(("bad", b"m", "sig"))
    stub_ops.verdicts = [False]
    ex.drain()
    assert f_good.result() is True and f_bad.result() is False
    for fut in (f_good, f_bad):
        assert fut.ctx.outcome == "recheck"
        assert fut.ctx.components["detour"] >= 0.0
        total = sum(fut.ctx.components.values())
        assert abs(total - fut.ctx.end_to_end_s()) < COMPONENT_SUM_EPS


def test_retry_outcome_accrues_detour(traced, stub_ops):
    from consensus_specs_tpu.resilience.policies import RetryPolicy

    ex = ServeExecutor(max_batch=4, depth=1,
                       retry=RetryPolicy(max_attempts=2,
                                         base_backoff_s=0.002))
    stub_ops.verdicts = [RuntimeError("flake"), True]
    fut = ex.submit_verify_task(_task())
    ex.drain()
    assert fut.result() is True
    ctx = fut.ctx
    assert ctx.outcome == "retry" and ctx.attempts == 2
    # the failed attempt + backoff landed in detour
    assert ctx.components["detour"] >= 0.002
    assert abs(sum(ctx.components.values()) - ctx.end_to_end_s()) \
        < COMPONENT_SUM_EPS


def test_fallback_outcome(traced, stub_ops, monkeypatch):
    from consensus_specs_tpu.resilience.policies import BreakerRegistry
    from consensus_specs_tpu.serve import executor as ex_mod

    monkeypatch.setattr(ex_mod, "_oracle_compute",
                        lambda kind, payload: True)
    ex = ServeExecutor(max_batch=4, depth=1,
                       breakers=BreakerRegistry(threshold=1,
                                                cooldown_s=60.0))
    stub_ops.verdicts = [RuntimeError("device sick")]
    f1 = ex.submit_verify_task(_task())
    ex.drain()                       # fails -> breaker trips -> oracle
    assert f1.result() is True
    assert f1.ctx.outcome == "fallback"
    # while OPEN, the next request short-circuits to the oracle without
    # ever dispatching — queue_wait then detour, zero device_wall
    f2 = ex.submit_verify_task(_task())
    ex.drain()
    assert f2.result() is True
    assert f2.ctx.outcome == "fallback" and f2.ctx.attempts == 0
    assert f2.ctx.components["device_wall"] == 0.0
    for ctx in (f1.ctx, f2.ctx):
        assert abs(sum(ctx.components.values()) - ctx.end_to_end_s()) \
            < COMPONENT_SUM_EPS


def test_poisoned_outcome(traced, stub_ops):
    ex = ServeExecutor(max_batch=2, depth=1)
    stub_ops.verdicts = [RuntimeError("batch died")]
    fut = ex.submit_verify_task(_task())
    ex.drain()
    with pytest.raises(RuntimeError, match="batch died"):
        fut.result()
    assert fut.ctx.outcome == "poisoned"
    assert fut.ctx.components["detour"] >= 0.0
    rec = traced.records()[0]
    assert rec["outcome"] == "poisoned"


def test_shed_outcome_carries_trace_id(traced, stub_ops):
    from consensus_specs_tpu.resilience.policies import DeadlineExceeded

    ex = ServeExecutor(max_batch=4, depth=1, deadline_ms=1.0)
    fut = ex.submit_verify_task(_task())
    time.sleep(0.005)
    ex.pump()
    exc = fut.exception()
    assert isinstance(exc, DeadlineExceeded)
    assert exc.trace_id == fut.ctx.trace_id
    ctx = fut.ctx
    assert ctx.outcome == "shed"
    # a shed request never dispatched: its whole life is queue wait
    assert ctx.components["queue_wait"] == pytest.approx(
        ctx.end_to_end_s())
    assert ctx.components["device_wall"] == 0.0


def test_timeout_outcome_is_provisional(traced, stub_ops):
    # a batch future whose waiter burns the whole budget without
    # settling: the bounded wait raises FutureTimeout and stamps the
    # context; a later (untimed) settle attempt overwrites the outcome
    def slow_waiter(f, timeout=None):
        time.sleep((timeout or 0.0) + 0.005)

    stub_ops.verdicts = [DeviceFuture(waiter=slow_waiter)]
    ex = ServeExecutor(max_batch=4, depth=1)
    fut = ex.submit_verify_task(_task())
    with pytest.raises(FutureTimeout):
        fut.result(timeout=0.01)
    assert fut.ctx.outcome == "timeout" and not fut.ctx.done
    assert traced.records() == []        # still pending, not published
    # the wedged batch eventually fails for real -> poisoned overwrites
    ex.drain()
    assert fut.ctx.outcome == "poisoned" and fut.ctx.done


# --- batch lineage -----------------------------------------------------------


def test_batch_lineage_n_requests_one_dispatch(traced, stub_ops):
    ex = ServeExecutor(max_batch=8, depth=1)
    futs = [ex.submit_verify_task(_task()) for _ in range(5)]
    ex.drain()
    assert stub_ops.batches == [5]
    batch_ids = {f.ctx.batch_id for f in futs}
    assert len(batch_ids) == 1 and None not in batch_ids
    bats = traced.batches()
    assert len(bats) == 1
    assert bats[0]["requests"] == 5 and bats[0]["attempt"] == 1
    assert sorted(bats[0]["trace_ids"]) == \
        sorted(f.ctx.trace_id for f in futs)
    # two kinds never share a batch id
    stub_ops.verdicts = [True, True]
    fv = ex.submit_verify_task(_task())
    fp = ex.submit_pairing([("p", "q")])
    ex.drain()
    assert fv.ctx.batch_id != fp.ctx.batch_id


# --- attribution engine ------------------------------------------------------


def test_attribution_arithmetic_and_schema(traced, stub_ops):
    ex = ServeExecutor(max_batch=4, depth=1)
    futs = [ex.submit_verify_task(_task()) for _ in range(10)]
    for _ in range(3):
        futs.append(ex.submit_pairing([("p", "q")]))
    ex.drain()
    recs = traced.records()
    assert len(recs) == 13
    for r in recs:
        assert abs(sum(r["components"].values()) - r["e2e_s"]) \
            < COMPONENT_SUM_EPS
    att = traced.attribution(recs, worst_n=4)
    assert validate_latency_attribution(att) == []
    assert set(att["kinds"]) == {"verify", "pairing"}
    v = att["kinds"]["verify"]
    assert v["count"] == 10
    assert v["p50_ms"] <= v["p90_ms"] <= v["p99_ms"]
    assert sum(v["outcomes"].values()) == v["count"]
    assert len(att["worst"]) == 4
    # worst list is sorted slowest-first
    e2es = [w["e2e_ms"] for w in att["worst"]]
    assert e2es == sorted(e2es, reverse=True)
    assert 0.0 <= att["p99_queue_frac"] <= 1.0
    json.dumps(att)     # JSON-able end to end


def test_attribution_excludes_failed_requests(traced, stub_ops):
    ex = ServeExecutor(max_batch=1, depth=1)
    ok = ex.submit_verify_task(_task())
    stub_ops.verdicts = [True, RuntimeError("dead")]
    bad = ex.submit_verify_task(_task())
    ex.drain()
    assert ok.result() is True and bad.exception() is not None
    att = traced.attribution()
    # the poisoned request is visible in the registry but not in the
    # percentile base (its latency measures the failure, not service)
    assert att["requests"] == 2 and att["answered"] == 1
    assert att["kinds"]["verify"]["count"] == 1


# --- chrome-trace flow events ------------------------------------------------


def test_chrome_trace_flow_events(traced, stub_ops):
    from consensus_specs_tpu import telemetry
    from consensus_specs_tpu.telemetry import core

    saved = core._save_state()
    was_enabled = telemetry.enabled()
    telemetry.configure(enabled=True)
    try:
        ex = ServeExecutor(max_batch=4, depth=1)
        futs = [ex.submit_verify_task(_task()) for _ in range(3)]
        ex.drain()
        trace = telemetry.chrome_trace()
        events = trace["traceEvents"]
        req_spans = [e for e in events
                     if e.get("ph") == "X" and e["name"] == "req.verify"]
        assert len(req_spans) == 3
        for e in req_spans:
            assert e["cat"] == "req" and e["dur"] > 0
            comp = e["args"]["components_ms"]
            assert set(comp) == set(reqtrace.COMPONENTS)
        batch_spans = [e for e in events
                       if e.get("ph") == "X"
                       and e["name"] == "batch.verify"]
        assert len(batch_spans) == 1
        assert batch_spans[0]["args"]["requests"] == 3
        # the flow triplet: one 's' and one 'f' per request, tied by
        # trace id, with the 't' step on the batch track in between
        flows = {}
        for e in events:
            if e.get("ph") in ("s", "t", "f"):
                assert e["cat"] == "req"
                flows.setdefault(e["id"], []).append(e)
        assert set(flows) == {f.ctx.trace_id for f in futs}
        for fid, evs in flows.items():
            phases = [e["ph"] for e in evs]
            assert phases == ["s", "t", "f"], phases
            ts = [e["ts"] for e in evs]
            assert ts == sorted(ts)
        json.dumps(trace)
    finally:
        telemetry.configure(enabled=was_enabled)
        core._restore_state(saved)


# --- disabled no-op bound ----------------------------------------------------


def test_disabled_mint_is_none_and_pipeline_unaffected(stub_ops):
    was = reqtrace.enabled()
    reqtrace.configure(enabled=False)
    try:
        reqtrace.reset()
        ex = ServeExecutor(max_batch=2, depth=1)
        fut = ex.submit_verify_task(_task())
        assert fut.ctx is None
        ex.drain()
        assert fut.result() is True
        assert reqtrace.records() == [] and reqtrace.batches() == []
    finally:
        reqtrace.configure(enabled=was)


def test_disabled_overhead_bound():
    """Disabled `mint()` must stay one module-global read: 50k calls
    well under 1.5s — the same pattern and budget as the telemetry and
    fault-injection disabled-path bounds."""
    was = reqtrace.enabled()
    reqtrace.configure(enabled=False)
    try:
        n = 50_000
        t0 = time.perf_counter()
        for _ in range(n):
            reqtrace.mint("verify")
        assert time.perf_counter() - t0 < 1.5
    finally:
        reqtrace.configure(enabled=was)


# --- serve-block schema ------------------------------------------------------


def _good_attribution():
    comp = {"queue_wait": 1.0, "batch_form": 0.1, "device_wall": 2.0,
            "settle": 0.1, "detour": 0.0}
    return {
        "kinds": {"verify": {
            "count": 10, "p50_ms": 2.0, "p90_ms": 3.0, "p99_ms": 4.0,
            "mean_components_ms": dict(comp),
            "p99_components_ms": dict(comp),
            "p99_queue_frac": 0.3,
            "outcomes": {"ok": 9, "retry": 1},
        }},
        "requests": 10, "answered": 10, "p99_queue_frac": 0.3,
        "worst": [{"trace_id": 7, "kind": "verify", "outcome": "ok",
                   "batch": 3, "attempts": 1, "e2e_ms": 4.0,
                   "components_ms": dict(comp)}],
        "records_dropped": 0,
    }


def test_validate_latency_attribution_accepts_good():
    assert validate_latency_attribution(_good_attribution()) == []


@pytest.mark.parametrize("mutate, needle", [
    (lambda a: a.update(kinds="fast"), "kinds"),
    (lambda a: a["kinds"]["verify"].update(count=0), "count"),
    (lambda a: a["kinds"]["verify"].update(p99_ms=1.0), "p99_ms"),
    (lambda a: a["kinds"]["verify"]["p99_components_ms"].pop("detour"),
     "p99_components_ms"),
    (lambda a: a["kinds"]["verify"].update(outcomes={"bogus": 1}),
     "outcomes"),
    (lambda a: a.update(p99_queue_frac=1.5), "p99_queue_frac"),
    (lambda a: a.update(worst=[{"kind": "verify"}]), "worst"),
])
def test_validate_latency_attribution_rejects_bad(mutate, needle):
    att = _good_attribution()
    mutate(att)
    problems = validate_latency_attribution(att)
    assert problems and any(needle in p for p in problems), problems


def test_serve_block_latency_source_contract():
    block = {
        "verifies_per_s": 10.0, "p50_ms": 1.0, "p99_ms": 2.0,
        "steady": True, "windows": [10.0, 10.0, 10.0],
        "submitted": 5, "settled": 5, "failed": 0,
        "queue_depth": {"max": 1, "hist": {"1": 5}}, "mode": "closed",
    }
    assert validate_serve_block(block) == []            # pre-tracing OK
    block["latency_source"] = "executor"
    assert validate_serve_block(block) == []
    block["latency_source"] = "reqtrace"                # needs the block
    problems = validate_serve_block(block)
    assert any("latency_attribution" in p for p in problems), problems
    block["latency_attribution"] = _good_attribution()
    assert validate_serve_block(block) == []
    block["latency_source"] = "sundial"
    problems = validate_serve_block(block)
    assert any("latency_source" in p for p in problems), problems


# --- live status -------------------------------------------------------------


def test_status_snapshot_contract(traced, stub_ops):
    ex = ServeExecutor(max_batch=2, depth=8)
    for _ in range(3):
        ex.submit_verify_task(_task())
    st = ex.status()
    assert st["queue"]["depth"] == 3
    assert st["queue"]["by_kind"] == {"verify": 3}
    assert st["queue"]["oldest_age_s"] >= 0
    assert st["counters"]["submitted"] == 3
    assert st["tracing"] is True
    ex.pump()           # dispatch; depth=8 keeps batches in flight
    st = ex.status()
    assert st["queue"]["depth"] == 0
    assert st["inflight"]["batches"] == 2       # ceil(3 / max_batch 2)
    assert st["inflight"]["requests"] == 3
    ex.drain()
    st = ex.status()
    assert st["counters"]["settled"] == 3
    assert st["latency"]["verify"]["count"] == 3
    assert st["latency"]["verify"]["p50_ms"] <= \
        st["latency"]["verify"]["p99_ms"]
    assert set(st["latency"]["verify"]["mean_components_ms"]) == \
        set(reqtrace.COMPONENTS)
    json.dumps(st)      # JSON-able end to end (the dump contract)


def test_status_periodic_dump(traced, stub_ops, monkeypatch, capfd):
    monkeypatch.setenv("CST_SERVE_STATUS_EVERY", "0.01")
    ex = ServeExecutor(max_batch=2, depth=1)
    ex.submit_verify_task(_task())
    time.sleep(0.02)
    ex.pump()
    err = capfd.readouterr().err
    lines = [ln for ln in err.splitlines()
             if ln.startswith("serve_status: ")]
    assert lines, err
    st = json.loads(lines[-1][len("serve_status: "):])
    assert st["counters"]["submitted"] == 1


def test_status_dump_off_by_default(traced, stub_ops, capfd):
    ex = ServeExecutor(max_batch=2, depth=1)
    ex.submit_verify_task(_task())
    ex.drain()
    assert "serve_status:" not in capfd.readouterr().err


# --- analyzer rule -----------------------------------------------------------


def test_reqtrace_uncovered_submit_fires():
    from consensus_specs_tpu.analysis import analyze_source

    src = (
        "class ServeExecutor:\n"
        "    def submit_widget(self, payload):\n"
        "        self._queue.append(payload)\n"
    )
    report = analyze_source(src, "fixture.py")
    rules = [f.rule for f in report.unsuppressed]
    assert "reqtrace-uncovered-submit" in rules, rules


def test_reqtrace_coverage_propagates_via_local_call_graph():
    from consensus_specs_tpu.analysis import analyze_source

    src = (
        "from ..telemetry import reqtrace\n"
        "\n"
        "class ServeExecutor:\n"
        "    def _submit(self, kind, payload):\n"
        "        ctx = reqtrace.mint(kind)\n"
        "        return ctx\n"
        "    def submit_widget(self, payload):\n"
        "        return self._submit('widget', payload)\n"
        "    def submit_facade(self, payload):\n"
        "        return self.submit_widget(payload)\n"
    )
    report = analyze_source(src, "fixture.py")
    assert not [f for f in report.unsuppressed
                if f.rule == "reqtrace-uncovered-submit"], \
        report.unsuppressed


def test_real_executor_passes_reqtrace_rule():
    from pathlib import Path

    from consensus_specs_tpu.analysis import analyze_source
    from consensus_specs_tpu.analysis.core import PKG_ROOT, ROLE_SERVE

    path = Path(PKG_ROOT) / "serve" / "executor.py"
    report = analyze_source(path.read_text(), "serve/executor.py",
                            roles=frozenset({ROLE_SERVE}))
    assert report.unsuppressed == [], [
        f.render() for f in report.unsuppressed]


# --- fault-victim correlation (the chaos satellite's unit surface) -----------


def test_fault_victims_marked_and_correlated(traced, stub_ops):
    from consensus_specs_tpu.resilience import chaos, faults
    from consensus_specs_tpu.resilience.policies import RetryPolicy

    ex = ServeExecutor(max_batch=4, depth=1,
                       retry=RetryPolicy(max_attempts=2,
                                         base_backoff_s=0.0))
    stub_ops.verdicts = [faults.FaultInjected("dispatch", "rlc@4",
                                              "raise"), True]
    hit = ex.submit_verify_task(_task())
    ex.drain()
    clean = ex.submit_verify_task(_task())
    ex.drain()
    assert hit.result() is True and clean.result() is True
    assert hit.ctx.faulted and not clean.ctx.faulted
    victims = chaos._fault_victims()
    assert victims["count"] == 1
    assert victims["trace_ids"] == [hit.ctx.trace_id]
    assert victims["outcomes"] == {"retry": 1}
    assert victims["clean_ok"] == 0
