"""The kernel cost model (`consensus_specs_tpu/telemetry/costmodel.py`):
XLA cost/memory capture on a jitted toy kernel (exact flops for a known
matmul), peak-registry classification boundaries, watermark high-water
monotonicity, snapshot / bench-block / history schemas, the benchwatch
report's Utilization section over a synthetic costmodel round, and the
measured no-op bound when CST_COSTMODEL is off."""

import json
import time

import pytest

from consensus_specs_tpu import telemetry
from consensus_specs_tpu.telemetry import costmodel, history
from consensus_specs_tpu.telemetry import core as tcore


@pytest.fixture(autouse=True)
def _isolated_registry():
    """Enable telemetry+costmodel against a saved/restored registry so
    a CST_TELEMETRY CI session keeps its session-wide data."""
    state = tcore._save_state()
    cm_state = (dict(costmodel._costs), dict(costmodel._watermarks),
                list(costmodel._wm_events), costmodel._wm_events_dropped)
    prev_enabled = telemetry.enabled()
    telemetry.configure(enabled=True)
    costmodel.configure(enabled=True)
    tcore.reset(full=True)
    yield
    telemetry.configure(enabled=prev_enabled)
    costmodel.configure(enabled=None)
    tcore._restore_state(state)
    with costmodel._lock:
        costmodel._costs.clear()
        costmodel._costs.update(cm_state[0])
        costmodel._watermarks.clear()
        costmodel._watermarks.update(cm_state[1])
        costmodel._wm_events.clear()
        costmodel._wm_events.extend(cm_state[2])
        costmodel._wm_events_dropped = cm_state[3]


# --- capture ----------------------------------------------------------------


def _toy_matmul():
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda a, b: a @ b)
    x = jnp.ones((8, 8), jnp.float32)
    return f, x


def test_capture_exact_flops_for_known_matmul():
    f, x = _toy_matmul()
    f(x, x)
    rec = costmodel.capture("toy_matmul@8", f, (x, x))
    # 8x8x8 matmul: 2*M*N*K = 1024 flops, 3 x 256-byte buffers touched
    assert rec["flops"] == 1024.0
    assert rec["bytes_accessed"] == 768.0
    assert rec["platform"] == "cpu"
    assert rec["run_s_probe"] > 0
    mem = rec.get("memory")
    if mem is not None:   # backend-dependent; exact when present
        assert mem["argument_size_in_bytes"] == 512
        assert mem["output_size_in_bytes"] == 256


def test_capture_is_once_per_kernel_key():
    f, x = _toy_matmul()
    rec1 = costmodel.capture("once@8", f, (x, x))
    rec2 = costmodel.capture("once@8", f, (x, x))
    assert rec1 is not None and rec2 == rec1
    assert telemetry.snapshot()["costmodel"]["kernels"]["once@8"][
        "flops"] == 1024.0


def test_capture_failure_stores_error_record_never_raises():
    rec = costmodel.capture("broken@1", object(), (1,))
    assert "error" in rec and rec["kernel"] == "broken@1"
    assert telemetry.snapshot()["counters"][
        "costmodel.capture_errors"] == 1
    # an error record is still schema-valid inside the bench block
    blk = telemetry.bench_block()
    assert telemetry.validate_bench_block(blk) == []


def test_record_cost_direct_injection():
    costmodel.record_cost("synthetic@4", flops=100.0,
                          bytes_accessed=10.0, platform="tpu",
                          device_kind="TPU v5 lite", run_s_probe=1.0)
    blk = costmodel.block()
    rec = blk["kernels"]["synthetic@4"]
    assert rec["bound"] in ("compute", "memory", "launch")
    assert rec["peak_source"] == "TPU v5 lite"


# --- classification boundaries ----------------------------------------------


PEAK = {"flops_per_s": 100.0, "bytes_per_s": 10.0}


def test_classify_compute_bound():
    out = costmodel.classify(flops=100.0, bytes_accessed=1.0,
                             run_s=1.0, peak=PEAK)
    # t_compute = 1.0 >= t_memory = 0.1, and not launch
    assert out["bound"] == "compute"
    assert out["util_flops_pct"] == 100.0
    assert out["arithmetic_intensity"] == 100.0


def test_classify_memory_bound():
    out = costmodel.classify(flops=1.0, bytes_accessed=10.0,
                             run_s=1.0, peak=PEAK)
    # t_memory = 1.0 > t_compute = 0.01
    assert out["bound"] == "memory"
    assert out["util_bw_pct"] == 100.0


def test_classify_launch_bound():
    # both roofline legs explain < LAUNCH_BOUND_FRAC of the wall
    out = costmodel.classify(flops=1.0, bytes_accessed=1.0,
                             run_s=100.0, peak=PEAK)
    assert out["bound"] == "launch"


def test_classify_launch_boundary_is_exclusive():
    # exactly at the threshold: max leg == LAUNCH_BOUND_FRAC * run_s is
    # NOT launch-bound (strictly-less-than semantics)
    run_s = 1.0
    t_leg = costmodel.LAUNCH_BOUND_FRAC * run_s
    out = costmodel.classify(flops=PEAK["flops_per_s"] * t_leg,
                             bytes_accessed=0.0, run_s=run_s, peak=PEAK)
    assert out["bound"] == "compute"


def test_classify_without_peak_or_run_is_unknown():
    assert costmodel.classify(1.0, 1.0, None, PEAK)["bound"] == "unknown"
    assert costmodel.classify(1.0, 1.0, 1.0, None)["bound"] == "unknown"


def test_peaks_registry_reads_baseline_json():
    reg = costmodel.peaks()
    assert reg["TPU v5 lite"]["flops_per_s"] > 0
    assert "Google Cloud" in reg["TPU v5 lite"]["source"]
    assert reg["cpu"]["advisory"] is True
    entry = costmodel.peaks_for("TPU v5 lite")
    assert entry and entry["backend"] == "TPU v5 lite"
    assert costmodel.peaks_for("cpu")["advisory"] is True


@pytest.mark.parametrize("kind", ["TPU v6 lite", "TPU v4", "tpu", "quantum"])
def test_peaks_for_unknown_device_kind_is_none_and_warns(kind, capsys):
    # no other chip's row stands in: a v5e prefix match would price a
    # different generation's kernels against the v5e roofline
    costmodel._warned_kinds.discard(kind)
    assert costmodel.peaks_for(kind) is None
    assert kind in capsys.readouterr().err
    costmodel.record_cost("unknown@1", flops=1.0, bytes_accessed=1.0,
                          platform="tpu", device_kind=kind,
                          run_s_probe=1.0)
    rec = costmodel.block()["kernels"]["unknown@1"]
    assert rec["bound"] == "unknown" and "peak_source" not in rec


# --- watermarks -------------------------------------------------------------


def test_watermark_high_water_is_monotone():
    import jax.numpy as jnp

    keep = [jnp.ones((1024,), jnp.float32)]
    costmodel.sample_watermark("t0")
    keep.append(jnp.ones((2048,), jnp.float32))
    costmodel.sample_watermark("t1")
    keep.append(jnp.ones((4096,), jnp.float32))
    costmodel.sample_watermark("t2")
    wms = costmodel.raw_snapshot()["watermarks"]
    assert wms, "no watermark devices sampled"
    for dev, wm in wms.items():
        assert wm["high_water_bytes"] >= wm["last_bytes"]
        assert wm["samples"] >= 3
    # high water never decreases even after buffers are freed
    high = {d: w["high_water_bytes"] for d, w in wms.items()}
    del keep
    costmodel.sample_watermark("t3")
    for dev, wm in costmodel.raw_snapshot()["watermarks"].items():
        assert wm["high_water_bytes"] >= high[dev]


def test_watermark_counter_events_in_chrome_trace():
    import jax.numpy as jnp

    _ = jnp.ones((16,), jnp.float32)
    costmodel.sample_watermark("phase")
    f, x = _toy_matmul()
    costmodel.capture("traced@8", f, (x, x))
    trace = telemetry.chrome_trace()
    counters = [e for e in trace["traceEvents"] if e["ph"] == "C"]
    names = {e["name"] for e in counters}
    assert "device_memory_bytes" in names
    assert "cost.traced@8" in names
    mem = [e for e in counters if e["name"] == "device_memory_bytes"]
    assert all(isinstance(v, int) and v >= 0
               for e in mem for v in e["args"].values())
    json.dumps(trace)


# --- snapshot / bench-block / history schemas --------------------------------


def test_block_joins_dispatch_run_hist_over_probe():
    costmodel.record_cost("joined@8", flops=10.0, bytes_accessed=10.0,
                          run_s_probe=9.9)
    telemetry.observe("kernel.joined@8.run_s", 0.5)
    telemetry.observe("kernel.joined@8.run_s", 1.5)
    rec = costmodel.block()["kernels"]["joined@8"]
    assert rec["run_s_mean"] == 1.0          # hist mean, not the probe
    assert rec["run_source"] == "dispatch"


def test_bench_block_costmodel_schema_and_json():
    f, x = _toy_matmul()
    costmodel.capture("schema@8", f, (x, x))
    costmodel.sample_watermark("schema")
    blk = telemetry.bench_block()
    assert telemetry.validate_bench_block(blk) == []
    assert telemetry.validate_costmodel_block(blk["costmodel"]) == []
    json.dumps(blk)


def test_validate_costmodel_block_rejects_malformed():
    assert telemetry.validate_costmodel_block([]) != []
    assert telemetry.validate_costmodel_block({"kernels": 3}) != []
    bad_bound = {"kernels": {"k": {"flops": 1.0, "bytes_accessed": 1.0,
                                   "bound": "weird"}},
                 "watermarks": {}}
    assert any("bound" in p
               for p in telemetry.validate_costmodel_block(bad_bound))
    bad_wm = {"kernels": {},
              "watermarks": {"cpu:0": {"high_water_bytes": 1,
                                       "last_bytes": 2}}}
    assert any("high water" in p
               for p in telemetry.validate_costmodel_block(bad_wm))


def test_history_records_round_trip(tmp_path):
    costmodel.record_cost("hist@8", flops=100.0, bytes_accessed=50.0,
                          run_s_probe=0.1)
    costmodel.record_cost("msm_tiny@8", flops=1.0, bytes_accessed=1.0,
                          run_s_probe=0.5)
    with costmodel._lock:
        costmodel._watermarks["cpu:0"] = {"last_bytes": 10,
                                          "high_water_bytes": 20,
                                          "samples": 2}
    blk = telemetry.bench_block()
    recs = history.costmodel_records("some_metric", blk, ts=123.0,
                                     platform="cpu")
    metrics = {r["metric"] for r in recs}
    assert {"costmodel::hist@8", "costmodel::msm_tiny@8",
            "device_mem_high_water::cpu:0"} <= metrics
    for r in recs:
        assert history.validate_record(r) == [], r
        assert r["source"] == "costmodel"
    store = tmp_path / "h.jsonl"
    assert history.append_records(store, recs) == len(recs)
    loaded, skipped, warns = history.load_history(store)
    assert (len(loaded), skipped, warns) == (len(recs), 0, [])
    assert {r["metric"] for r in loaded} == metrics


def test_malformed_costmodel_block_yields_no_records():
    assert history.costmodel_records("m", None) == []
    assert history.costmodel_records("m", {"costmodel": "nope"}) == []
    assert history.costmodel_records(
        "m", {"costmodel": {"kernels": {"k": {"error": "boom"}},
                            "watermarks": {}}}) == []


# --- report: the Utilization section -----------------------------------------


def _synthetic_round(tmp_path):
    """A checked-in-style synthetic costmodel round: one compute-bound
    kernel, one launch-bound small MSM, a watermark, and an attestation
    metric with an embedded compile/run split."""
    recs = [
        history.make_record(
            "costmodel", "costmodel::pairing_check@8", 0.2, unit="s",
            platform="tpu", ts=100.0,
            costmodel={"kernel": "pairing_check@8", "flops": 2.0e13,
                       "bytes_accessed": 1.0e10, "run_s_mean": 0.2,
                       "arithmetic_intensity": 2000.0,
                       "achieved_flops_per_s": 1.0e14,
                       "achieved_bytes_per_s": 5.0e10,
                       "util_flops_pct": 50.8, "util_bw_pct": 6.1,
                       "bound": "compute", "peak_source": "tpu"}),
        history.make_record(
            "costmodel", "costmodel::msm_pippenger@8w4", 0.01, unit="s",
            platform="tpu", ts=100.0,
            costmodel={"kernel": "msm_pippenger@8w4", "flops": 1.0e6,
                       "bytes_accessed": 1.0e5, "run_s_mean": 0.01,
                       "arithmetic_intensity": 10.0,
                       "achieved_flops_per_s": 1.0e8,
                       "achieved_bytes_per_s": 1.0e7,
                       "util_flops_pct": 0.0, "util_bw_pct": 0.0,
                       "bound": "launch", "peak_source": "tpu"}),
        history.make_record(
            "costmodel", "device_mem_high_water::tpu:0", 123456789,
            unit="bytes", samples=7, platform="tpu", ts=100.0),
        history.make_record(
            "bench_emit", "attestation_batch_128x64_verify_wall", 0.31,
            unit="s", vs_baseline=31.0, platform="tpu", ts=100.0,
            telemetry={"compile_s": 81.2, "run_s": 0.31}),
    ]
    store = tmp_path / "bench_history.jsonl"
    assert history.append_records(store, recs) == len(recs)
    return store


def test_report_utilization_golden(tmp_path):
    from consensus_specs_tpu.telemetry import report as rpt

    store = _synthetic_round(tmp_path)
    stored, _, _ = history.load_history(store)
    util = rpt.collect_utilization(stored)
    assert util["warnings"] == []
    assert util["kernels"]["pairing_check@8"]["bound"] == "compute"
    assert util["kernels"]["msm_pippenger@8w4"]["bound"] == "launch"
    assert util["watermarks"]["tpu:0"]["high_water_bytes"] == 123456789
    verdict = util["verdict"]
    assert verdict["kind"] == "compile-bound"
    assert verdict["compile_s"] == 81.2 and verdict["run_s"] == 0.31

    text = "\n".join(rpt.render_utilization(util, {"status": "keep"}))
    assert "## Utilization" in text
    assert "`pairing_check@8`" in text and "**compute**" in text
    assert "**launch**" in text
    assert "compile-bound" in text and "81.2" in text
    assert "msm_pippenger@8w4" in text     # the _MSM_DEVICE_MIN note
    assert "123.46 MB" in text             # watermark row


def test_report_utilization_no_data_renders():
    from consensus_specs_tpu.telemetry import report as rpt

    util = rpt.collect_utilization([])
    text = "\n".join(rpt.render_utilization(util, {"status": "no data"}))
    assert "## Utilization" in text and "No cost-model data" in text


def test_report_tpu_records_outrank_cpu(tmp_path):
    from consensus_specs_tpu.telemetry import report as rpt

    recs = [
        history.make_record(
            "costmodel", "costmodel::k@8", 0.1, unit="s",
            platform="tpu", ts=100.0,
            costmodel={"kernel": "k@8", "flops": 1.0,
                       "bytes_accessed": 1.0, "bound": "compute"}),
        history.make_record(
            "costmodel", "costmodel::k@8", 0.2, unit="s",
            platform="cpu", ts=200.0,
            costmodel={"kernel": "k@8", "flops": 2.0,
                       "bytes_accessed": 2.0, "bound": "launch"}),
    ]
    util = rpt.collect_utilization(recs)
    assert util["kernels"]["k@8"]["platform"] == "tpu"
    assert util["kernels"]["k@8"]["bound"] == "compute"


def test_report_verdict_prefers_tpu_over_later_cpu_smoke():
    # the CI CPU smoke round is appended before every report — a later
    # cpu attestation record must not override the TPU round's
    # compile-vs-execute verdict
    from consensus_specs_tpu.telemetry import report as rpt

    recs = [
        history.make_record(
            "bench_emit", "attestation_batch_128x64_verify_wall", 0.31,
            unit="s", platform="tpu", ts=100.0,
            telemetry={"compile_s": 81.2, "run_s": 0.31}),
        history.make_record(
            "bench_emit", "attestation_batch_2x2_verify_wall", 0.7,
            unit="s", platform="cpu", ts=200.0,
            telemetry={"compile_s": 40.0, "run_s": 0.7}),
    ]
    verdict = rpt.collect_utilization(recs)["verdict"]
    assert verdict["platform"] == "tpu"
    assert verdict["compile_s"] == 81.2


def test_emission_records_dedupe_cumulative_costmodel():
    # a bench process emits one metric line per config but the
    # costmodel block is a cumulative per-process fact: unchanged
    # kernel/watermark records must land in the store exactly once
    history._emitted_cost_payloads.clear()
    cm = {"kernels": {"k@8": {"kernel": "k@8", "flops": 10.0,
                              "bytes_accessed": 5.0, "run_s_mean": 0.1}},
          "watermarks": {"cpu:0": {"last_bytes": 4, "high_water_bytes": 8,
                                   "samples": 2}}}
    tel = {"compile_s": 1.0, "run_s": 0.1, "costmodel": cm}
    total = []
    for i, m in enumerate(("m_a", "m_b", "m_c")):
        total += history.emission_records(
            {"metric": m, "value": 1.0, "unit": "s", "vs_baseline": 1.0,
             "telemetry": tel}, ts=1000.0 + i)
    cost = [r for r in total if r["source"] == "costmodel"]
    assert sorted(r["metric"] for r in cost) == [
        "costmodel::k@8", "device_mem_high_water::cpu:0"]
    # a grown high-water IS new data — it re-emits
    cm["watermarks"]["cpu:0"]["high_water_bytes"] = 16
    more = history.emission_records(
        {"metric": "m_d", "value": 1.0, "unit": "s", "vs_baseline": 1.0,
         "telemetry": tel}, ts=1003.0)
    assert [r["metric"] for r in more if r["source"] == "costmodel"] \
        == ["device_mem_high_water::cpu:0"]
    history._emitted_cost_payloads.clear()


def test_round_file_costmodel_records_not_duplicated(tmp_path):
    # three metric lines in one round tail share the cumulative block:
    # one record per kernel/device, last line wins
    cm = {"kernels": {"k@8": {"kernel": "k@8", "flops": 10.0,
                              "bytes_accessed": 5.0, "run_s_mean": 0.1}},
          "watermarks": {"cpu:0": {"last_bytes": 4, "high_water_bytes": 8,
                                   "samples": 2}}}
    tel = {"compile_s": 1.0, "run_s": 0.1, "costmodel": cm}
    tail = "\n".join(
        json.dumps({"metric": m, "value": 1.0, "unit": "s",
                    "vs_baseline": 1.0, "telemetry": tel})
        for m in ("m_a", "m_b", "m_c"))
    p = tmp_path / "BENCH_r09.json"
    p.write_text(json.dumps({"n": 9, "rc": 0, "tail": tail}))
    recs, warns = history.parse_bench_round(p)
    assert not warns
    cost = [r for r in recs if r["source"] == "costmodel"]
    assert sorted(r["metric"] for r in cost) == [
        "costmodel::k@8", "device_mem_high_water::cpu:0"]


def test_report_malformed_costmodel_is_counted_warning():
    from consensus_specs_tpu.telemetry import report as rpt

    rec = history.make_record("costmodel", "costmodel::bad@1", 0.1,
                              unit="s", platform="cpu", ts=1.0,
                              costmodel={"kernel": "bad@1"})  # no flops
    util = rpt.collect_utilization([rec])
    assert util["kernels"] == {}
    assert len(util["warnings"]) == 1


def test_build_report_warns_on_missing_costmodel_round(tmp_path,
                                                       monkeypatch):
    from consensus_specs_tpu.telemetry import report as rpt

    monkeypatch.setenv("CST_COSTMODEL", "1")
    result = rpt.build_report(
        repo=tmp_path, history_path=tmp_path / "h.jsonl", snapshots=[],
        durations_path=None, top_n=5, strict=False,
        max_regress_pct=20.0, update_history=False)
    assert result["exit_code"] == 0      # a warning, never a crash/gate
    assert any("CST_COSTMODEL" in w for w in result["warnings"])
    assert "## Utilization" in rpt.render_report(result)


# --- disabled-path contract --------------------------------------------------


def test_costmodel_requires_both_gates():
    costmodel.configure(enabled=None)     # back to the env gate (off)
    assert not costmodel.enabled()
    costmodel.configure(enabled=True)
    telemetry.configure(enabled=False)
    assert not costmodel.enabled()        # telemetry gate still applies
    telemetry.configure(enabled=True)
    assert costmodel.enabled()


def test_disabled_capture_and_watermark_are_noops():
    costmodel.configure(enabled=False)
    assert costmodel.capture("k@1", object(), (1,)) is None
    assert costmodel.sample_watermark("t") == {}
    assert costmodel.raw_snapshot() == {
        "kernels": {}, "watermarks": {}, "wm_events": 0,
        "wm_events_dropped": 0}
    blk = telemetry.bench_block()
    assert "costmodel" not in blk


def test_disabled_noop_bound():
    """The off path must stay off the profile: a capture +
    sample_watermark pair under 6 microseconds amortized (flag checks,
    no lowering, no device walk) — same budget style as the telemetry
    no-op test."""
    costmodel.configure(enabled=False)
    n = 20_000
    t0 = time.perf_counter()
    for _ in range(n):
        costmodel.capture("k", None, ())
        costmodel.sample_watermark("t")
    per_pair = (time.perf_counter() - t0) / n
    assert per_pair < 6e-6, f"no-op pair cost {per_pair * 1e6:.2f}us"
