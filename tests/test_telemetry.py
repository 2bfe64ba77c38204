"""Telemetry layer contract tests (`consensus_specs_tpu/telemetry/`).

Pins the properties the instrumented hot path relies on: disabled mode
is a true no-op with a measured overhead bound, spans nest and unwind
through exceptions, the snapshot schema is stable, the registry is
thread-safe, the Chrome-trace export is valid trace-event JSON, and the
bench `"telemetry"` sub-object schema is enforced both ways.
"""

from __future__ import annotations

import json
import threading
import time

import pytest

from consensus_specs_tpu import telemetry
from consensus_specs_tpu.telemetry import core


@pytest.fixture(autouse=True)
def _clean_registry():
    """Each test starts disabled with an empty registry and an empty
    span-nesting stack, and restores EXACTLY what it found: on
    CST_TELEMETRY=1 runs the process registry is accumulating
    session-wide data (per-test spans, deferred-batch counters) that the
    end-of-session snapshot must keep, and the conftest per-test wrapper
    span sits on the nesting stack."""
    saved = core._save_state()
    was_enabled = telemetry.enabled()
    stack = core._span_stack()
    saved_stack = stack[:]
    stack.clear()
    telemetry.configure(enabled=False)
    telemetry.reset(full=True)
    yield
    telemetry.configure(enabled=was_enabled)
    core._restore_state(saved)
    stack[:] = saved_stack


# --- disabled mode ----------------------------------------------------------


def test_disabled_records_nothing():
    assert not telemetry.enabled()
    with telemetry.span("s", k=1):
        telemetry.count("c")
        telemetry.observe("h", 2.5)
        telemetry.set_meta("m", "v")
    snap = telemetry.snapshot()
    assert snap["counters"] == {}
    assert snap["histograms"] == {}
    assert snap["spans"] == {}
    assert snap["meta"] == {}
    assert snap["events"] == 0


def test_disabled_span_is_shared_noop_object():
    a = telemetry.span("a")
    b = telemetry.span("b", attr=1)
    assert a is b   # no per-call allocation on the disabled path


def test_disabled_overhead_bound():
    """The disabled hot path (a span + a counter per iteration, the
    shape of one instrumented kernel dispatch) must stay cheap: 50k
    iterations under 1.5s is ~30µs per op pair, two orders above the
    expected cost but low enough to catch an accidentally-eager
    implementation (e.g. building attr dicts or locking while off)."""
    n = 50_000
    t0 = time.perf_counter()
    for i in range(n):
        with telemetry.span("hot", i=i):
            telemetry.count("c")
    dt = time.perf_counter() - t0
    assert dt < 1.5, f"disabled telemetry overhead too high: {dt:.3f}s"
    assert telemetry.snapshot()["events"] == 0


# --- spans ------------------------------------------------------------------


def test_span_nesting_parent_attribution():
    telemetry.configure(enabled=True)
    with telemetry.span("outer"):
        with telemetry.span("inner"):
            pass
    events, _ = core._events_copy()
    by_name = {e["name"]: e for e in events}
    assert by_name["inner"]["args"]["parent"] == "outer"
    assert "parent" not in by_name["outer"]["args"]
    # inner closed first and sits inside outer's window
    assert by_name["inner"]["ts"] >= by_name["outer"]["ts"]
    assert (by_name["inner"]["ts"] + by_name["inner"]["dur"]
            <= by_name["outer"]["ts"] + by_name["outer"]["dur"] + 1e-3)


def test_span_exception_unwinds_and_propagates():
    telemetry.configure(enabled=True)
    with pytest.raises(ValueError):
        with telemetry.span("outer"):
            with telemetry.span("boom"):
                raise ValueError("x")
    snap = telemetry.snapshot()
    assert snap["spans"]["boom"]["count"] == 1
    assert snap["spans"]["outer"]["count"] == 1
    events, _ = core._events_copy()
    by_name = {e["name"]: e for e in events}
    assert by_name["boom"]["args"]["error"] == "ValueError"
    # the nesting stack fully unwound: a fresh span has no parent
    with telemetry.span("after"):
        pass
    events, _ = core._events_copy()
    after = [e for e in events if e["name"] == "after"][0]
    assert "parent" not in after["args"]


def test_span_aggregation():
    telemetry.configure(enabled=True)
    for _ in range(3):
        with telemetry.span("s"):
            pass
    agg = telemetry.snapshot()["spans"]["s"]
    assert agg["count"] == 3
    assert 0 <= agg["min_s"] <= agg["max_s"] <= agg["total_s"]


# --- counters / histograms / meta / first_call ------------------------------


def test_counters_and_histograms():
    telemetry.configure(enabled=True)
    telemetry.count("c")
    telemetry.count("c", 4)
    for v in (2.0, 1.0, 3.0):
        telemetry.observe("h", v)
    snap = telemetry.snapshot()
    assert snap["counters"]["c"] == 5
    assert snap["histograms"]["h"] == {
        "count": 3, "total": 6.0, "min": 1.0, "max": 3.0}


def test_first_call_per_key():
    telemetry.configure(enabled=True)
    assert telemetry.first_call("k")
    assert not telemetry.first_call("k")
    assert telemetry.first_call("k2")
    telemetry.reset()            # aggregate reset keeps first-call keys
    assert not telemetry.first_call("k")
    telemetry.reset(full=True)   # full reset clears them
    assert telemetry.first_call("k")


def test_reset_keeps_process_level_state():
    telemetry.configure(enabled=True)
    with telemetry.span("s"):
        telemetry.count("c")
    telemetry.set_meta("compile_cache.dir", "/x")
    telemetry.reset()
    snap = telemetry.snapshot()
    assert snap["counters"] == {} and snap["spans"] == {}
    assert snap["events"] == 1   # CST_TRACE_FILE timeline survives
    # meta is process-level (cache dir etc.) — per-config resets keep it
    assert snap["meta"] == {"compile_cache.dir": "/x"}
    telemetry.reset(full=True)
    assert telemetry.snapshot()["meta"] == {}


# --- snapshot schema --------------------------------------------------------


def test_snapshot_schema_stable():
    telemetry.configure(enabled=True)
    with telemetry.span("s", a=1):
        telemetry.count("c")
        telemetry.observe("h", 1.0)
        telemetry.set_meta("m", "v")
        telemetry.gauge("g", 3)
    snap = telemetry.snapshot()
    assert set(snap) == {"enabled", "meta", "counters", "histograms",
                         "spans", "gauges", "events", "events_dropped",
                         "costmodel", "reqtrace", "occupancy"}
    assert snap["enabled"] is True
    assert set(snap["histograms"]["h"]) == {"count", "total", "min", "max"}
    assert set(snap["gauges"]["g"]) == {"last", "min", "max", "count"}
    assert set(snap["spans"]["s"]) == {"count", "total_s", "min_s",
                                       "max_s"}
    assert set(snap["costmodel"]) == {"kernels", "watermarks",
                                      "wm_events", "wm_events_dropped"}
    assert set(snap["reqtrace"]) >= {"enabled", "completed", "batches",
                                     "by_kind", "by_outcome"}
    assert set(snap["occupancy"]) >= {"enabled", "events", "open_spans",
                                      "events_dropped", "live"}
    json.dumps(snap)   # JSON-able end to end


# --- thread safety ----------------------------------------------------------


def test_thread_safety():
    telemetry.configure(enabled=True)
    n_threads, per_thread = 8, 500
    errors = []

    def work(tid):
        try:
            for i in range(per_thread):
                with telemetry.span(f"t{tid}"):
                    telemetry.count("shared")
                    telemetry.observe("lat", float(i))
        except Exception as e:   # pragma: no cover
            errors.append(e)

    threads = [threading.Thread(target=work, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    snap = telemetry.snapshot()
    assert snap["counters"]["shared"] == n_threads * per_thread
    assert snap["histograms"]["lat"]["count"] == n_threads * per_thread
    for t in range(n_threads):
        assert snap["spans"][f"t{t}"]["count"] == per_thread


# --- exporters --------------------------------------------------------------


def test_chrome_trace_export_valid(tmp_path):
    telemetry.configure(enabled=True)
    with telemetry.span("outer", phase="x"):
        with telemetry.span("inner"):
            pass
    path = tmp_path / "trace.json"
    telemetry.write_chrome_trace(str(path))
    trace = json.loads(path.read_text())   # valid JSON, not just a file
    assert "traceEvents" in trace
    spans = [e for e in trace["traceEvents"] if e.get("ph") == "X"]
    assert {e["name"] for e in spans} == {"outer", "inner"}
    for e in spans:
        # the trace-event fields Perfetto requires of complete events
        assert {"name", "ph", "ts", "dur", "pid", "tid"} <= set(e)
        assert isinstance(e["ts"], (int, float)) and e["ts"] >= 0
        assert isinstance(e["dur"], (int, float)) and e["dur"] >= 0
    meta = [e for e in trace["traceEvents"] if e.get("ph") == "M"]
    assert meta and meta[0]["args"]["name"] == "consensus_specs_tpu"


def test_jsonl_export(tmp_path):
    telemetry.configure(enabled=True)
    with telemetry.span("a"):
        pass
    with telemetry.span("b"):
        pass
    path = tmp_path / "events.jsonl"
    assert telemetry.write_jsonl(str(path)) == 2
    lines = [json.loads(ln) for ln in path.read_text().splitlines()]
    assert [e["name"] for e in lines] == ["a", "b"]


# --- bench block contract ---------------------------------------------------


def test_bench_block_schema_valid():
    telemetry.configure(enabled=True)
    telemetry.count("bls.lanes.live", 10)
    telemetry.count("bls.lanes.padded", 32)
    telemetry.count("msm.route.host", 3)
    telemetry.observe("kernel.compile_first_s", 1.5)
    telemetry.observe("kernel.run_s", 0.1)
    telemetry.set_meta("compile_cache.dir", "/x")
    block = telemetry.bench_block()
    assert telemetry.validate_bench_block(block) == []
    assert block["compile_s"] == 1.5
    assert block["run_s"] == 0.1
    assert block["padding"]["waste_frac"] == round(1 - 10 / 32, 4)
    assert block["routing"]["msm_host"] == 3
    assert block["meta"] == {"compile_cache.dir": "/x"}


def test_embed_bench_block_protocol():
    telemetry.configure(enabled=True)
    telemetry.count("bls.lanes.live", 1)
    rec = telemetry.embed_bench_block({"metric": "m"})
    assert telemetry.validate_bench_block(rec["telemetry"]) == []
    # aggregates were reset for the next config
    assert telemetry.snapshot()["counters"] == {}
    # disabled: pass-through untouched
    telemetry.configure(enabled=False)
    assert telemetry.embed_bench_block({"metric": "m"}) == {"metric": "m"}


def test_bench_block_explicit_split():
    telemetry.configure(enabled=True)
    block = telemetry.bench_block(compile_s=81.0, run_s=0.31)
    assert telemetry.validate_bench_block(block) == []
    assert block["compile_s"] == 81.0 and block["run_s"] == 0.31


def test_validate_bench_block_rejects_malformed():
    assert telemetry.validate_bench_block(None)
    assert telemetry.validate_bench_block({})
    good = telemetry.bench_block(compile_s=1.0, run_s=1.0)
    for breakage in (
        lambda b: b.pop("padding"),
        lambda b: b["routing"].pop("msm_host"),
        lambda b: b.__setitem__("compile_s", "fast"),
        lambda b: b["padding"].__setitem__("waste_frac", 2.0),
        lambda b: b["routing"].__setitem__("h2c_device", -1),
    ):
        broken = json.loads(json.dumps(good))
        breakage(broken)
        assert telemetry.validate_bench_block(broken), breakage


# --- add_event / span_seconds (benchwatch phase attribution) ----------------


def test_add_event_aggregates_like_a_span():
    telemetry.configure(enabled=True)
    telemetry.add_event("t::x [spec-build]", 1.5, phase="spec-build")
    telemetry.add_event("t::x [spec-build]", 0.5, phase="spec-build")
    snap = telemetry.snapshot()
    agg = snap["spans"]["t::x [spec-build]"]
    assert agg["count"] == 2
    assert agg["total_s"] == 2.0
    assert agg["min_s"] == 0.5 and agg["max_s"] == 1.5
    # the buffered trace events carry the attrs (Chrome-trace args)
    events, _ = core._events_copy()
    assert [e["args"] for e in events] == [{"phase": "spec-build"}] * 2
    assert all(e["dur"] > 0 for e in events)


def test_add_event_clamps_negative_and_respects_disabled():
    telemetry.add_event("off", 1.0)        # disabled: no-op
    assert telemetry.snapshot()["spans"] == {}
    telemetry.configure(enabled=True)
    telemetry.add_event("neg", -3.0)       # derived deltas can misfire
    assert telemetry.snapshot()["spans"]["neg"]["total_s"] == 0.0


def test_span_seconds_point_read():
    telemetry.configure(enabled=True)
    assert telemetry.span_seconds("spec.build") == 0.0
    assert telemetry.span_seconds("spec.build", default=7.0) == 7.0
    telemetry.add_event("spec.build", 1.25)
    telemetry.add_event("spec.build", 0.25)
    assert telemetry.span_seconds("spec.build") == 1.5


# --- the profiler half ------------------------------------------------------


def _wire_statement():
    from consensus_specs_tpu.ops.bls import ciphersuite

    msg = b"\x07" * 32
    return ciphersuite.SkToPk(5), msg, ciphersuite.Sign(5, msg)


def _cst_host_events(logdir) -> list:
    """(name, thread line, start_ns, end_ns) of the `cst.*` host events of
    the one trace under `logdir`."""
    from jax.profiler import ProfileData

    (path,) = list(logdir.rglob("*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("cst."):
                    out.append((ev.name, line.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns))
    return out


def test_profiler_captures_parse_phases_with_registry_off(tmp_path):
    """A profiler session sees the wire parse and its four phases, nested,
    on the profiler's clock, with the registry off; the program's own
    count of those spans agrees with the trace."""
    import jax

    from consensus_specs_tpu.serve import ServeExecutor

    pk, msg, sig = _wire_statement()
    ex = ServeExecutor()
    with jax.profiler.trace(str(tmp_path)):
        ex.submit_fast_aggregate_verify([pk], msg, sig)
    events = _cst_host_events(tmp_path)
    (parse,) = [e for e in events if e[0] == "cst.serve.parse"]
    phases = {e[0]: e for e in events if e[0].startswith("cst.bls.")}
    assert set(phases) == {"cst.bls.decompress_g1", "cst.bls.subgroup_g1",
                           "cst.bls.decompress_g2", "cst.bls.subgroup_g2"}
    for name, line, start, end in phases.values():
        assert line == parse[1], name
        assert parse[2] <= start <= end <= parse[3], name
    counted = telemetry.profiled_spans()
    assert {k: v["count"] for k, v in counted.items()} == {
        "serve.parse": 1, "bls.decompress_g1": 1, "bls.subgroup_g1": 1,
        "bls.decompress_g2": 1, "bls.subgroup_g2": 1}
    for name, line, start, end in events:
        total_s = counted[name[len("cst."):]]["total_s"]
        assert total_s == pytest.approx((end - start) / 1e9, abs=1e-4)
    # the registry half stayed off
    assert telemetry.snapshot()["spans"] == {}


def test_no_session_and_registry_off_span_is_the_noop():
    import jax  # noqa: F401  the profiler's state is read once jax is in

    assert telemetry.span("serve.parse") is core._NULL_SPAN
    with telemetry.span("serve.parse"):
        pass
    assert telemetry.profiled_spans() == {}


def test_registry_span_also_reaches_the_profiler(tmp_path):
    """With the registry on, a span is recorded there as before (name,
    parent) and, inside a profiler session, annotated as `cst.<name>`."""
    import jax

    telemetry.configure(enabled=True)
    with jax.profiler.trace(str(tmp_path)):
        with telemetry.span("bls.batch_verify", tasks=2):
            with telemetry.span("bls.prepare"):
                pass
    events, _ = core._events_copy()
    by_name = {e["name"]: e for e in events}
    assert by_name["bls.prepare"]["args"]["parent"] == "bls.batch_verify"
    assert {e[0] for e in _cst_host_events(tmp_path)} == {
        "cst.bls.batch_verify", "cst.bls.prepare"}
    assert set(telemetry.profiled_spans()) == {"bls.batch_verify",
                                               "bls.prepare"}


def test_span_left_open_when_the_session_stops_is_not_counted(tmp_path):
    import jax

    jax.profiler.start_trace(str(tmp_path))
    span = telemetry.span("serve.parse")
    with span:
        jax.profiler.stop_trace()
    assert telemetry.profiled_spans() == {}
    # and with the session gone, spans are the no-op again
    assert telemetry.span("serve.parse") is core._NULL_SPAN
