"""Benchwatch contract tests (`telemetry/history.py` + `report.py`).

Three layers:

- the INGESTER, run as goldens over small synthetic round files in the
  driver's wrapper shape (`rounds_repo`: BENCH_r01/r03/r04/r05 and
  MULTICHIP_r01/r05, including the rounds that FAILED — r03 timed out
  before printing JSON, r04 died in a traceback: both must skip with a
  counted warning, never crash) plus malformed/truncated wrappers and
  unknown-schema history lines;
- the TREND/GATE engine: a synthetic regression round (flagship
  `vs_baseline` halved) must make the reporter exit nonzero and NAME
  the offending metric, a clean round must exit zero, and the oracle-
  fingerprint guard must keep incomparable baselines from reading as
  regressions;
- the REPORTER CLI over those rounds: the markdown dashboard
  renders trend tables for the flagship + extras metrics, evaluates
  every ROADMAP threshold, and emits the `_MSM_DEVICE_MIN`
  recommendation (the acceptance criterion for this subsystem).

Everything here is stdlib-speed: no jax, no spec builds.
"""

from __future__ import annotations

import json
from pathlib import Path

import pytest

from consensus_specs_tpu.telemetry import history, report

REPO = Path(__file__).resolve().parents[1]

FLAGSHIP = "mainnet_epoch_sweep_1m_validators_wall"


def _flagship_line(value, vs_baseline, platform="tpu", extra=None):
    obj = {"metric": FLAGSHIP, "value": value, "unit": "s",
           "vs_baseline": vs_baseline}
    if platform is not None:
        obj["platform"] = platform
    if extra:
        obj["extra"] = extra
    return json.dumps(obj)


def _round_file(tmp_path, n, tail, rc=0):
    path = tmp_path / f"BENCH_r{n:02d}.json"
    path.write_text(json.dumps(
        {"n": n, "cmd": "python bench.py", "rc": rc, "tail": tail}))
    return path


# --- golden ingestion over synthetic rounds ----------------------------------

_R05_EXTRAS = {
    "attestation_batch_128x64_verify_wall":
        {"value": 4.578, "unit": "s", "vs_baseline": 9.9},
    "sync_aggregate_512_verify_wall":
        {"value": 0.1987, "unit": "s", "vs_baseline": 1.7},
    "blob_kzg_proof_batch_6_verify_wall":
        {"value": 1.1967, "unit": "s", "vs_baseline": 0.9},
    "minimal_phase0_state_transition_signed_block_wall":
        {"value": 0.8547, "unit": "s", "vs_baseline": 1.1},
}


@pytest.fixture(scope="module")
def rounds_repo(tmp_path_factory):
    """A repo root holding driver round files of every shape the ingester
    meets: a flagship round with a fresh oracle measure (r01), a round
    cut by the driver's timeout before any JSON (r03), one that died in
    a traceback (r04), a flagship + extras round (r05), a failed and a
    passing multichip dryrun, and the two checked-in oracle baselines."""
    root = tmp_path_factory.mktemp("rounds")
    _round_file(root, 1, "\n".join([
        "baseline: 77.622s @ 1024 validators (75802.3 us/validator)",
        "tpu: compile+first run 73.8s on TPU v5 lite0",
        "tpu: 3673.9 ms/step @ 1048576 validators",
        _flagship_line(3.6739, 21634.7, platform=None)]))
    _round_file(root, 3, "baseline: 95.099s @ 1024 validators "
                         "(92870.5 us/validator)\n", rc=124)
    _round_file(root, 4, "Traceback (most recent call last):\n"
                         "RuntimeError: Unable to initialize backend "
                         "'tpu': UNAVAILABLE\n", rc=1)
    _round_file(root, 5, "\n".join([
        "baseline (persisted 2026-07-29): 244.6 us/validator @ 1024 "
        "validators",
        _flagship_line(3.3903, 75.7),
        "--- bls extras (elapsed 43s) ---",
        "attestation batch compile+first: 81.1s",
        "sync aggregate compile+first: 16.6s",
        "--- kzg extras (elapsed 166s) ---",
        "kzg batch device compile+first: 16.9s",
        _flagship_line(3.3903, 75.7, extra=_R05_EXTRAS)]))
    for n, rc, tail in ((1, 1, "RuntimeError: need 8 devices, have 1"),
                        (5, 0, "")):
        (root / f"MULTICHIP_r{n:02d}.json").write_text(json.dumps(
            {"n_devices": 8, "rc": rc, "ok": rc == 0, "skipped": False,
             "tail": tail}))
    for name in ("bench_baseline.json", "bench_bls_baseline.json"):
        (root / name).write_text((REPO / name).read_text())
    return root


def test_golden_round_01_flagship_and_fingerprint(rounds_repo):
    records, warnings = history.parse_bench_round(
        rounds_repo / "BENCH_r01.json")
    assert not warnings
    by_metric = {r["metric"]: r for r in records}
    flag = by_metric[FLAGSHIP]
    assert flag["value"] == 3.6739
    assert flag["vs_baseline"] == 21634.7
    assert flag["round"] == 1
    assert flag["source"] == "bench_round"
    assert flag["baseline_us_per_validator"] == 75802.3
    # the epoch compile+first wall is mined from the stderr log line
    assert by_metric["epoch_sweep_compile_first_s"]["value"] == 73.8
    for rec in records:
        assert not history.validate_record(rec), rec


def test_golden_round_05_extras_flattened(rounds_repo):
    records, warnings = history.parse_bench_round(
        rounds_repo / "BENCH_r05.json")
    assert not warnings
    by_metric = {r["metric"]: r for r in records}
    assert by_metric[FLAGSHIP]["value"] == 3.3903
    att = by_metric["attestation_batch_128x64_verify_wall"]
    assert att["value"] == 4.578 and att["vs_baseline"] == 9.9
    # extras inherit the flagship line's platform
    assert att["platform"] == "tpu"
    assert by_metric["sync_aggregate_512_verify_wall"]["vs_baseline"] == 1.7
    assert by_metric["blob_kzg_proof_batch_6_verify_wall"][
        "vs_baseline"] == 0.9
    assert by_metric["minimal_phase0_state_transition_signed_block_wall"][
        "vs_baseline"] == 1.1
    # per-config compile+first log lines (the ROADMAP < 40s target data)
    assert by_metric["attestation_batch_compile_first_s"]["value"] == 81.1
    assert by_metric["sync_aggregate_compile_first_s"]["value"] == 16.6
    assert by_metric["blob_kzg_batch_compile_first_s"]["value"] == 16.9


@pytest.mark.parametrize("name,rc", [("BENCH_r03.json", 124),
                                     ("BENCH_r04.json", 1)])
def test_golden_failed_rounds_skip_with_warning(rounds_repo, name, rc):
    """r03 timed out before printing JSON, r04 died in a traceback —
    the exact inputs the ingester must survive."""
    records, warnings = history.parse_bench_round(rounds_repo / name)
    assert records == []
    assert len(warnings) == 1
    assert f"rc={rc}" in warnings[0] and "skipped" in warnings[0]


def test_golden_multichip_rounds(rounds_repo):
    recs1, w1 = history.parse_multichip_round(
        rounds_repo / "MULTICHIP_r01.json")
    recs5, w5 = history.parse_multichip_round(
        rounds_repo / "MULTICHIP_r05.json")
    assert not w1 and not w5
    assert recs1[0]["metric"] == "multichip_dryrun_ok"
    assert recs1[0]["value"] == 0.0 and recs1[0]["rc"] == 1
    assert recs5[0]["value"] == 1.0 and recs5[0]["round"] == 5
    assert recs5[0]["unit"] == "bool"


def test_golden_oracle_baselines():
    recs, warns = history.parse_baseline_file(REPO / "bench_baseline.json")
    assert not warns
    assert recs[0]["metric"] == "oracle_epoch_us_per_validator"
    assert recs[0]["value"] == pytest.approx(244.609, abs=0.01)
    recs, warns = history.parse_baseline_file(
        REPO / "bench_bls_baseline.json")
    assert {r["metric"] for r in recs} == {
        "oracle_fast_aggregate_verify_s", "oracle_sync_aggregate_verify_s"}


def test_ingest_repo_full_sweep(rounds_repo):
    records, warnings = history.ingest_repo(rounds_repo)
    # r03 + r04 are the only expected casualties
    assert len(warnings) == 2
    metrics = {r["metric"] for r in records}
    assert FLAGSHIP in metrics
    assert "attestation_batch_128x64_verify_wall" in metrics
    assert "multichip_dryrun_ok" in metrics
    assert "oracle_epoch_us_per_validator" in metrics
    for rec in records:
        assert not history.validate_record(rec), rec


# --- malformed / truncated / unknown-schema inputs ---------------------------


def test_non_json_round_file_warns(tmp_path):
    path = tmp_path / "BENCH_r07.json"
    path.write_text("this is not json {")
    records, warnings = history.parse_bench_round(path)
    assert records == [] and len(warnings) == 1
    assert "unreadable" in warnings[0]


def test_wrapper_not_an_object_warns(tmp_path):
    path = tmp_path / "BENCH_r07.json"
    path.write_text(json.dumps(["not", "a", "wrapper"]))
    records, warnings = history.parse_bench_round(path)
    assert records == [] and len(warnings) == 1


def test_truncated_tail_mid_json_line(tmp_path):
    """A driver timeout can cut the tail mid-metric-line: the partial
    JSON must be skipped (counted), not crash the parser."""
    path = _round_file(tmp_path, 7,
                       'some log line\n{"metric": "x_wall", "value": 1.2,',
                       rc=124)
    records, warnings = history.parse_bench_round(path)
    assert records == []
    assert len(warnings) == 1 and "no parseable metric line" in warnings[0]


def test_history_unknown_schema_version_skipped(tmp_path):
    store = tmp_path / "h.jsonl"
    good = history.make_record("bench_emit", "m_wall", 1.0, ts=1.0)
    future = dict(good, schema=99)
    store.write_text("\n".join([
        json.dumps(good), json.dumps(future), "{broken json",
        json.dumps({"schema": 1, "source": "bench_emit"}),   # invalid rec
    ]) + "\n")
    records, skipped, warnings = history.load_history(store)
    assert [r["metric"] for r in records] == ["m_wall"]
    assert skipped == 3 and len(warnings) == 3
    assert any("unknown schema version" in w for w in warnings)
    assert any("malformed" in w for w in warnings)


def test_sync_records_is_idempotent(tmp_path, rounds_repo):
    store = tmp_path / "h.jsonl"
    records, _ = history.ingest_repo(rounds_repo)
    n1 = history.sync_records(store, records)
    n2 = history.sync_records(store, records)
    assert n1 == len(records) and n2 == 0
    loaded, skipped, _ = history.load_history(store)
    assert len(loaded) == len(records) and skipped == 0


# --- live emission records ---------------------------------------------------


def test_emission_records_flatten_and_stamp(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    line = {"metric": FLAGSHIP, "value": 2.0, "unit": "s",
            "vs_baseline": 10.0,
            "extra": {"x_wall": {"value": 0.5, "unit": "s",
                                 "vs_baseline": 3.0}}}
    recs = history.emission_records(line, ts=123.456)
    by_metric = {r["metric"]: r for r in recs}
    assert set(by_metric) == {FLAGSHIP, "x_wall"}
    for rec in recs:
        assert rec["source"] == "bench_emit"
        assert rec["platform"] == "cpu"
        assert rec["ts"] == 123.5
        assert not history.validate_record(rec), rec


def test_append_emission_disabled_without_knob(tmp_path, monkeypatch):
    monkeypatch.delenv("CST_BENCHWATCH_HISTORY", raising=False)
    assert history.append_emission({"metric": "m", "value": 1.0}) == 0


def test_append_emission_writes_records(tmp_path, monkeypatch):
    store = tmp_path / "h.jsonl"
    monkeypatch.setenv("CST_BENCHWATCH_HISTORY", str(store))
    n = history.append_emission(
        {"metric": "m_wall", "value": 1.0, "unit": "s"}, ts=5.0)
    assert n == 1
    records, skipped, _ = history.load_history(store)
    assert skipped == 0 and records[0]["metric"] == "m_wall"


# --- pytest snapshot / durations ingestion -----------------------------------


def test_parse_telemetry_snapshot_phase_split(tmp_path):
    snap = {
        "enabled": True,
        "meta": {"tier1.session_wall_s": 123.4, "tier1.tests": 2},
        "counters": {}, "histograms": {},
        "spans": {
            "spec.build": {"count": 3, "total_s": 5.0, "min_s": 1.0,
                           "max_s": 3.0},
            "tests/a.py::t1": {"count": 1, "total_s": 2.0,
                               "min_s": 2.0, "max_s": 2.0},
            "tests/a.py::t1 [spec-build]": {"count": 1, "total_s": 1.5,
                                            "min_s": 1.5, "max_s": 1.5},
            "tests/a.py::t1 [test-body]": {"count": 1, "total_s": 0.5,
                                           "min_s": 0.5, "max_s": 0.5},
            "bls.batch_verify": {"count": 4, "total_s": 0.1,
                                 "min_s": 0.01, "max_s": 0.05},
        },
        "events": 9, "events_dropped": 0,
    }
    path = tmp_path / "snap.json"
    path.write_text(json.dumps(snap))
    records, attribution, warnings = history.parse_telemetry_snapshot(path)
    assert not warnings
    by_metric = {r["metric"]: r for r in records}
    assert by_metric["tier1_wall_s"]["value"] == 123.4
    assert by_metric["tier1_spec_build_total_s"]["value"] == 5.0
    # cpu-stamped: pytest walls must not group with TPU rounds in the
    # regression gate
    assert all(r["platform"] == "cpu" for r in records)
    assert len(attribution) == 1     # non-test spans are excluded
    row = attribution[0]
    assert row["test"] == "tests/a.py::t1"
    assert row["total_s"] == 2.0
    assert row["spec_build_s"] == 1.5 and row["test_body_s"] == 0.5


def test_parse_telemetry_snapshot_rejects_non_snapshot(tmp_path):
    path = tmp_path / "snap.json"
    path.write_text(json.dumps({"hello": 1}))
    records, attribution, warnings = history.parse_telemetry_snapshot(path)
    assert records == [] and attribution == [] and len(warnings) == 1


def test_parse_durations():
    text = ("12.03s call     tests/a.py::t1\n"
            "0.50s setup    tests/a.py::t1\n"
            "============ 2 passed ============\n")
    rows = history.parse_durations(text)
    assert rows == [
        {"test": "tests/a.py::t1", "phase": "call", "dur_s": 12.03},
        {"test": "tests/a.py::t1", "phase": "setup", "dur_s": 0.5},
    ]


# --- threshold evaluation ----------------------------------------------------


def test_thresholds_tpu_only_ignores_cpu_smoke():
    tpu = history.make_record(
        "bench_emit", "attestation_batch_128x64_verify_wall", 0.1,
        vs_baseline=31.0, platform="tpu", ts=2.0)
    cpu = history.make_record(
        "bench_emit", "attestation_batch_2x2_verify_wall", 0.1,
        vs_baseline=0.2, platform="cpu", ts=3.0)
    rows = {t["id"]: t for t in report.evaluate_thresholds([tpu, cpu])}
    att = rows["attestation-speedup"]
    assert att["status"] == "PASS" and att["observed"] == 31.0
    rows = {t["id"]: t for t in report.evaluate_thresholds([cpu])}
    assert rows["attestation-speedup"]["status"] == "no data"


def test_thresholds_evaluated_on_rounds(rounds_repo):
    records, _ = history.ingest_repo(rounds_repo)
    rows = {t["id"]: t for t in report.evaluate_thresholds(records)}
    # ROADMAP state as of round 5: all three speedups below target,
    # compile+first over budget, multichip healthy
    assert rows["attestation-speedup"]["status"] == "FAIL"
    assert rows["attestation-speedup"]["observed"] == 9.9
    assert rows["sync-aggregate-speedup"]["observed"] == 1.7
    assert rows["kzg-batch-speedup"]["observed"] == 0.9
    assert rows["attestation-compile-first"]["observed"] == 81.1
    assert rows["multichip"]["status"] == "PASS"
    assert rows["tier1-wall"]["status"] == "no data"


# --- regression gate ---------------------------------------------------------


def test_regression_on_vs_baseline_halved(tmp_path):
    _round_file(tmp_path, 1, _flagship_line(1.0, 100.0))
    _round_file(tmp_path, 2, _flagship_line(1.0, 50.0))
    records, _ = history.ingest_repo(tmp_path)
    regs = report.find_regressions(records, max_regress_pct=20.0)
    assert len(regs) == 1
    assert regs[0]["metric"] == FLAGSHIP
    assert regs[0]["kind"] == "vs_baseline"
    assert regs[0]["change_pct"] == -50.0


def test_no_regression_on_clean_round(tmp_path):
    _round_file(tmp_path, 1, _flagship_line(1.0, 100.0))
    _round_file(tmp_path, 2, _flagship_line(0.9, 110.0))
    records, _ = history.ingest_repo(tmp_path)
    assert report.find_regressions(records, max_regress_pct=20.0) == []


def test_incomparable_oracles_fall_back_to_wall(tmp_path):
    """r02->r05 in the real tree: the oracle was re-measured 300x
    cheaper, so vs_baseline collapsed while the wall IMPROVED — the
    fingerprint guard must compare wall seconds, not speedups."""
    _round_file(tmp_path, 1,
                "baseline: 77.6s @ 1024 validators (75802.3 us/validator)\n"
                + _flagship_line(4.67, 18275.2))
    _round_file(tmp_path, 2,
                "baseline (persisted): 244.6 us/validator @ 1024\n"
                + _flagship_line(3.39, 75.7))
    records, _ = history.ingest_repo(tmp_path)
    assert report.find_regressions(records, max_regress_pct=20.0) == []
    # and a wall blow-up IS caught through the same fallback
    _round_file(tmp_path, 3, _flagship_line(9.0, 80.0))
    records, _ = history.ingest_repo(tmp_path)
    regs = report.find_regressions(records, max_regress_pct=20.0)
    assert len(regs) == 1 and regs[0]["kind"] == "wall"


def test_rounds_have_no_regression(rounds_repo):
    records, _ = history.ingest_repo(rounds_repo)
    assert report.find_regressions(records, max_regress_pct=20.0) == []


# --- _MSM_DEVICE_MIN recommendation ------------------------------------------


def _probe_record(detail, current=16):
    return history.make_record(
        "bench_emit", "g1_msm_breakeven_probe_n6", 0.01,
        vs_baseline=1.0, platform="tpu", detail=detail,
        msm_device_min=current, ts=1.0)


def test_msm_recommendation_suggests_lower_threshold():
    msm = report.msm_recommendation([_probe_record({
        "6": {"host_s": 0.01, "device_s": 0.005, "host_over_device": 2.0,
              "routed": "host"},
        "16": {"host_s": 0.03, "device_s": 0.01, "host_over_device": 3.0,
               "routed": "device"},
    })])
    assert msm["status"] == "lower" and msm["suggested"] == 6
    assert "_MSM_DEVICE_MIN = 6" in msm["text"]


def test_msm_recommendation_keeps_threshold_without_device_win():
    msm = report.msm_recommendation([_probe_record({
        "6": {"host_over_device": 0.4, "routed": "host"},
        "16": {"host_over_device": 0.9, "routed": "device"},
    })])
    assert msm["status"] == "keep" and msm["suggested"] is None
    assert "keep 16" in msm["text"]


def test_msm_recommendation_no_data(rounds_repo):
    records, _ = history.ingest_repo(rounds_repo)   # no probe rows
    assert report.msm_recommendation(records)["status"] == "no data"


# --- the reporter CLI --------------------------------------------------------


def _run_cli(tmp_path, repo, *extra):
    return report.main([
        "--repo", str(repo),
        "--history", str(tmp_path / "h.jsonl"),
        "--out", str(tmp_path / "report.md"),
        *extra])


def test_cli_dashboard_on_rounds(tmp_path, rounds_repo, monkeypatch,
                                capsys):
    """The acceptance criterion: offline over the rounds, the
    dashboard renders trends for flagship + extras, evaluates every
    ROADMAP threshold, and exits zero (unmet targets are advisory; no
    round-over-round regression)."""
    monkeypatch.delenv("CST_BENCHWATCH_STRICT", raising=False)
    monkeypatch.delenv("CST_BENCHWATCH_MAX_REGRESS_PCT", raising=False)
    rc = _run_cli(tmp_path, rounds_repo, "--json", str(tmp_path / "r.json"))
    assert rc == 0
    text = (tmp_path / "report.md").read_text()
    for metric in (FLAGSHIP, "attestation_batch_128x64_verify_wall",
                   "sync_aggregate_512_verify_wall",
                   "blob_kzg_proof_batch_6_verify_wall",
                   "minimal_phase0_state_transition_signed_block_wall",
                   "multichip_dryrun_ok"):
        assert f"`{metric}`" in text, metric
    for th in report.THRESHOLDS:
        assert th["title"] in text, th["id"]
    assert "_MSM_DEVICE_MIN" in text
    assert "r01" in text and "r05" in text
    assert "BENCH_r03.json" in text     # skipped-with-warning is visible
    slim = json.loads((tmp_path / "r.json").read_text())
    assert slim["exit_code"] == 0
    assert {t["id"] for t in slim["thresholds"]} \
        == {t["id"] for t in report.THRESHOLDS}
    # second run: fully deduped against the store
    capsys.readouterr()
    assert _run_cli(tmp_path, rounds_repo) == 0
    assert "(0 new this run)" in capsys.readouterr().out


def test_cli_exits_nonzero_and_names_metric_on_regression(
        tmp_path, monkeypatch, capsys):
    monkeypatch.delenv("CST_BENCHWATCH_STRICT", raising=False)
    monkeypatch.delenv("CST_BENCHWATCH_MAX_REGRESS_PCT", raising=False)
    repo = tmp_path / "repo"
    repo.mkdir()
    _round_file(repo, 1, _flagship_line(1.0, 100.0))
    _round_file(repo, 2, _flagship_line(2.0, 50.0))
    rc = _run_cli(tmp_path, repo)
    assert rc == 1
    out = capsys.readouterr()
    assert "REGRESSION" in out.out
    assert FLAGSHIP in out.out
    text = (tmp_path / "report.md").read_text()
    assert "REGRESSION" in text and FLAGSHIP in text


def test_cli_clean_round_exits_zero(tmp_path, monkeypatch):
    monkeypatch.delenv("CST_BENCHWATCH_STRICT", raising=False)
    monkeypatch.delenv("CST_BENCHWATCH_MAX_REGRESS_PCT", raising=False)
    repo = tmp_path / "repo"
    repo.mkdir()
    _round_file(repo, 1, _flagship_line(1.0, 100.0))
    _round_file(repo, 2, _flagship_line(0.95, 105.0))
    assert _run_cli(tmp_path, repo) == 0


def test_cli_strict_mode_gates_on_thresholds(tmp_path, rounds_repo,
                                             monkeypatch):
    """--strict promotes the unmet ROADMAP targets (round 5 is below
    every speedup target) to exit-code failures."""
    monkeypatch.delenv("CST_BENCHWATCH_MAX_REGRESS_PCT", raising=False)
    assert _run_cli(tmp_path, rounds_repo, "--strict") == 1


def test_cli_attribution_from_snapshot(tmp_path, monkeypatch, capsys):
    monkeypatch.delenv("CST_BENCHWATCH_STRICT", raising=False)
    monkeypatch.delenv("CST_BENCHWATCH_MAX_REGRESS_PCT", raising=False)
    snap = tmp_path / "snap.json"
    snap.write_text(json.dumps({
        "enabled": True, "meta": {"tier1.session_wall_s": 900.0},
        "counters": {}, "histograms": {},
        "spans": {
            "tests/slow.py::t [spec-build]":
                {"count": 1, "total_s": 8.0, "min_s": 8.0, "max_s": 8.0},
            "tests/slow.py::t [test-body]":
                {"count": 1, "total_s": 2.0, "min_s": 2.0, "max_s": 2.0},
        }, "events": 2, "events_dropped": 0}))
    repo = tmp_path / "repo"
    repo.mkdir()
    rc = _run_cli(tmp_path, repo, "--snapshot", str(snap))
    assert rc == 0
    text = (tmp_path / "report.md").read_text()
    assert "tests/slow.py::t" in text
    assert "spec-build" in text
    # 900s session wall breaches the 870s budget -> FAIL row (advisory)
    assert "tier-1 suite wall budget" in text
    assert "❌ FAIL" in text


def test_msm_recommendation_raise_when_device_loses_at_current():
    """Device losing at the currently device-routed size and winning
    only above it means the threshold should RISE, not stay."""
    msm = report.msm_recommendation([_probe_record({
        "16": {"host_over_device": 0.8, "routed": "device"},
        "32": {"host_over_device": 1.5, "routed": "device"},
    })])
    assert msm["status"] == "raise" and msm["suggested"] == 32
    assert "_MSM_DEVICE_MIN = 32" in msm["text"]


def test_msm_recommendation_exact_threshold_is_right():
    msm = report.msm_recommendation([_probe_record({
        "6": {"host_over_device": 0.5, "routed": "host"},
        "16": {"host_over_device": 2.0, "routed": "device"},
    })])
    assert msm["status"] == "keep" and msm["suggested"] == 16
    assert "threshold is right" in msm["text"]


def test_snapshot_records_ordered_by_mtime(tmp_path):
    """tier1_wall_s thresholds must be evaluated against the NEWEST
    snapshot — records are ts-stamped from the file mtime so stored
    history orders them."""
    import os

    def _snap(path, wall, mtime):
        path.write_text(json.dumps({
            "enabled": True, "meta": {"tier1.session_wall_s": wall},
            "counters": {}, "histograms": {}, "spans": {},
            "events": 0, "events_dropped": 0}))
        os.utime(path, (mtime, mtime))

    old, new = tmp_path / "old.json", tmp_path / "new.json"
    _snap(old, 900.0, 1_000_000.0)
    _snap(new, 700.0, 2_000_000.0)
    records = []
    for p in (new, old):     # ingest order must not matter
        recs, _, _ = history.parse_telemetry_snapshot(p)
        records.extend(recs)
    assert all(isinstance(r.get("ts"), float) for r in records)
    rows = {t["id"]: t for t in report.evaluate_thresholds(records)}
    assert rows["tier1-wall"]["observed"] == 700.0
    assert rows["tier1-wall"]["status"] == "PASS"
