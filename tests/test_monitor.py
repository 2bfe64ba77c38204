"""SLO watchdog + metrics exposition contract tests
(`consensus_specs_tpu/telemetry/monitor.py`, `metrics_export.py`).

Pins the live-monitoring contracts the pod round leans on: the rule
engine evaluated on a FAKE clock (windows, breach→clear hysteresis,
flap suppression), malformed `CST_SLO_RULES` rejected with a counted
warning instead of a dead round, the disabled path a true no-op, the
exposition text round-tripping through its own strict parser,
and the reqtrace live window staying a fixed-size ring so the rolling
summary is O(window) under sustained load.
"""

from __future__ import annotations

import json
import urllib.request

import pytest

from consensus_specs_tpu import telemetry
from consensus_specs_tpu.telemetry import (
    core,
    metrics_export,
    monitor,
    reqtrace,
)
from consensus_specs_tpu.telemetry.export import validate_slo_block


@pytest.fixture(autouse=True)
def _clean_registry():
    """Each test starts disabled with empty telemetry/reqtrace/monitor
    state and restores what it found (same shape as test_telemetry's
    fixture — monitor and the endpoint are module-global gates)."""
    saved = core._save_state()
    was_enabled = telemetry.enabled()
    was_rt = reqtrace.enabled()
    telemetry.configure(enabled=False)
    telemetry.reset(full=True)          # also resets reqtrace + monitor
    metrics_export.stop()
    yield
    monitor._reset_state()
    metrics_export.stop()
    metrics_export.set_status_provider(None)
    reqtrace.configure(enabled=was_rt)
    telemetry.configure(enabled=was_enabled)
    core._restore_state(saved)


RULE = {"metric": "serve.queue_depth", "op": "<", "threshold": 10,
        "for": 1, "clear": 1, "name": "q"}


def _wd(rules=None, *, status=None, counters=None, summary=None,
        **kw):
    """A watchdog on a fake clock with injected providers — the tick
    loop never runs; tests drive `tick(now=...)` directly."""
    return monitor.Watchdog(
        rules if rules is not None else {"rules": [dict(RULE)]},
        clock=lambda: 0.0,
        status_provider=status or (lambda: {"queue": {"depth": 0}}),
        summary_provider=summary or (lambda *_: {}),
        counter_provider=counters or (lambda name: 0),
        watermark_provider=lambda: {},
        **kw)


# --- rule loading ------------------------------------------------------------


def test_load_rules_all_source_forms(tmp_path):
    obj = {"rules": [dict(RULE)]}
    assert monitor.load_rules(obj) == obj
    assert monitor.load_rules(json.dumps(obj))["rules"][0]["name"] == "q"
    p = tmp_path / "rules.json"
    p.write_text(json.dumps(obj))
    assert monitor.load_rules(str(p))["rules"][0]["metric"] \
        == "serve.queue_depth"
    spec = ("tick_s=0.5; serve.p99_ms{kind=verify}<500:for=2:clear=3;"
            " serve.throughput_rps>=100:window_s=10:name=tp")
    plan = monitor.load_rules(spec)
    assert plan["tick_s"] == 0.5
    r0, r1 = plan["rules"]
    assert r0 == {"metric": "serve.p99_ms", "kind": "verify",
                  "op": "<", "threshold": 500.0, "for": 2, "clear": 3}
    assert r1 == {"metric": "serve.throughput_rps", "op": ">=",
                  "threshold": 100.0, "window_s": 10.0, "name": "tp"}


def test_malformed_rules_list_every_problem():
    bad = {"rules": [{"metric": "serve.p99_ms", "op": "!=",
                      "threshold": "fast", "for": 0, "bogus": 1}]}
    with pytest.raises(ValueError) as exc:
        monitor.load_rules(bad)
    msg = str(exc.value)
    for frag in ("'op'", "'threshold'", "'for'", "bogus"):
        assert frag in msg
    # and the other source-level failures raise too
    with pytest.raises(ValueError):
        monitor.load_rules("not a spec at all {{{")
    with pytest.raises(ValueError):
        monitor.load_rules({"rules": []})
    with pytest.raises(ValueError):
        monitor.load_rules(42)


def test_duplicate_and_mislabeled_rules_rejected():
    dup = {"rules": [dict(RULE), dict(RULE)]}
    with pytest.raises(ValueError, match="duplicate rule name"):
        monitor.load_rules(dup)
    labeled = {"rules": [{"metric": "serve.queue_depth", "op": "<",
                          "threshold": 1, "kind": "verify"}]}
    with pytest.raises(ValueError, match="does not take a kind"):
        monitor.load_rules(labeled)


# --- the rule engine on a fake clock -----------------------------------------


def test_breach_needs_for_consecutive_bad_ticks():
    depth = {"v": 0}
    wd = _wd({"rules": [dict(RULE, **{"for": 3})]},
             status=lambda: {"queue": {"depth": depth["v"]}})
    depth["v"] = 99
    assert wd.tick(now=1.0) == [] and wd.tick(now=2.0) == []
    depth["v"] = 0                       # healthy tick resets the streak
    assert wd.tick(now=3.0) == []
    depth["v"] = 99
    assert wd.tick(now=4.0) == [] and wd.tick(now=5.0) == []
    events = wd.tick(now=6.0)            # third consecutive bad tick
    assert [e.phase for e in events] == ["breach"]
    assert wd.breaching() == ["q"]
    ev = events[0].as_dict()
    assert ev["rule"] == "q" and ev["value"] == 99.0
    assert ev["margin"] == pytest.approx(89.0)   # past the threshold


def test_clear_needs_clear_consecutive_healthy_ticks():
    depth = {"v": 99}
    wd = _wd({"rules": [dict(RULE, clear=2)]},
             status=lambda: {"queue": {"depth": depth["v"]}})
    assert [e.phase for e in wd.tick(now=1.0)] == ["breach"]
    depth["v"] = 0
    assert wd.tick(now=2.0) == []        # one healthy tick: still breaching
    assert wd.breaching() == ["q"]
    assert [e.phase for e in wd.tick(now=3.0)] == ["clear"]
    assert wd.breaching() == []
    block = wd.slo_block()
    assert block["breaches"] == 1 and not block["clean"]
    assert [e["phase"] for e in block["events"]] == ["breach", "clear"]
    assert validate_slo_block(block) == []


def test_flapping_signal_never_breaches_with_hysteresis():
    depth = {"v": 0}
    wd = _wd({"rules": [dict(RULE, **{"for": 2})]},
             status=lambda: {"queue": {"depth": depth["v"]}})
    for i in range(20):                  # alternate bad/good forever
        depth["v"] = 99 if i % 2 == 0 else 0
        assert wd.tick(now=float(i)) == []
    assert wd.slo_block()["breaches"] == 0


def test_counter_rate_needs_a_baseline_and_respects_window():
    total = {"v": 0}
    wd = _wd({"rules": [{"metric": "counter.faults.injected",
                         "op": "<=", "threshold": 0.0,
                         "window_s": 10.0, "name": "faults"}]},
             counters=lambda name: total["v"])
    # first tick: one sample, no baseline -> no observation, streaks hold
    assert wd.tick(now=0.0) == []
    assert wd.rules[0].last_value is None
    total["v"] = 40
    events = wd.tick(now=4.0)            # 40 injected over 4s = 10/s
    assert [e.phase for e in events] == ["breach"]
    assert wd.rules[0].last_value == pytest.approx(10.0)
    # a flat counter clears only once the ramp has LEFT the 10s
    # window: at t=8 the baseline sample (t=0) is still inside it, so
    # the rate stays positive and the rule stays in breach
    assert wd.tick(now=8.0) == []
    assert wd.breaching() == ["faults"]
    events = wd.tick(now=16.0)           # window now starts at t=6: rate 0
    assert [e.phase for e in events] == ["clear"]


def test_latency_signal_per_kind_and_worst_kind():
    summary = {"verify": {"count": 5, "p50_ms": 10.0, "p99_ms": 80.0},
               "proof": {"count": 5, "p50_ms": 20.0, "p99_ms": 300.0}}
    wd = _wd({"rules": [
        {"metric": "serve.p99_ms", "kind": "verify", "op": "<",
         "threshold": 100, "name": "verify-p99"},
        {"metric": "serve.p99_ms", "op": "<", "threshold": 100,
         "name": "worst-p99"}]},
        summary=lambda *_: summary)
    events = wd.tick(now=1.0)
    # the kind-labeled rule reads its kind (healthy); the unlabeled
    # rule reads the WORST kind (proof at 300ms -> breach)
    assert [e.rule for e in events] == ["worst-p99"]
    assert events[0].value == pytest.approx(300.0)


# --- the gate ----------------------------------------------------------------


def test_install_clear_lifecycle_and_disabled_noop():
    assert not monitor.active() and monitor.current() is None
    assert monitor.clear() is None       # disabled: a true no-op
    wd = monitor.install({"rules": [dict(RULE)]}, autostart=False)
    assert monitor.active() and monitor.current() is wd
    wd.tick(now=1.0)
    block = monitor.clear()
    assert block is not None and block["ticks"] == 1
    assert validate_slo_block(block) == []
    assert not monitor.active() and monitor.clear() is None


def test_install_from_env_rejects_malformed_rules(monkeypatch, capsys):
    telemetry.configure(enabled=True)
    monkeypatch.setenv("CST_SLO_RULES", "{'not': json, not a spec}")
    monkeypatch.delenv("CST_METRICS_PORT", raising=False)
    assert monitor.install_from_env() is None
    assert not monitor.active()          # the round keeps running
    assert telemetry.counter_value("slo.rules_invalid") == 1
    assert "invalid CST_SLO_RULES" in capsys.readouterr().err


def test_install_from_env_unset_is_noop(monkeypatch):
    monkeypatch.delenv("CST_SLO_RULES", raising=False)
    monkeypatch.delenv("CST_METRICS_PORT", raising=False)
    assert monitor.install_from_env() is None
    assert not monitor.active()
    assert metrics_export.serving_port() is None


def test_profile_dir_from_env(monkeypatch):
    monkeypatch.delenv("CST_PROFILE_ON_BREACH", raising=False)
    assert monitor.profile_dir_from_env() is None
    monkeypatch.setenv("CST_PROFILE_ON_BREACH", "0")
    assert monitor.profile_dir_from_env() is None
    monkeypatch.setenv("CST_PROFILE_ON_BREACH", "1")
    assert monitor.profile_dir_from_env() == "out/slo_profiles"
    monkeypatch.setenv("CST_PROFILE_ON_BREACH", "/tmp/grabs")
    assert monitor.profile_dir_from_env() == "/tmp/grabs"


# --- exposition: render -> strict parse round-trip ---------------------------


def _serve_some_requests(n=8):
    reqtrace.configure(enabled=True)
    for i in range(n):
        ctx = reqtrace.mint("verify" if i % 2 == 0 else "proof")
        ctx.complete()


def test_exposition_round_trips_through_its_own_parser():
    telemetry.configure(enabled=True)
    telemetry.count("serve.submitted", 3)
    telemetry.gauge("serve.queue_depth", 2)
    telemetry.observe("kernel.verify.ms", 12.5)
    _serve_some_requests()
    wd = monitor.install({"rules": [dict(RULE)]}, autostart=False,
                         status_provider=lambda: {"queue": {"depth": 0}})
    wd.tick(now=1.0)
    text = metrics_export.render_exposition()
    families = metrics_export.parse_exposition(text)   # raises if bad
    assert families["cst_serve_submitted_total"] == [({}, 3.0)]
    assert ({}, 2.0) in families["cst_serve_queue_depth"]
    # reqtrace lifetime series carry kind labels
    kinds = {lb["kind"] for lb, _ in
             families["cst_serve_requests_total"]}
    assert kinds == {"verify", "proof"}
    # the watchdog publishes its own families, rule-labeled
    assert ({"rule": "q"}, 0.0) in families["cst_slo_breaching"]
    assert ({}, 1.0) in families["cst_slo_ticks_total"]


def test_parser_rejects_malformed_lines():
    for bad in ("cst_x{unclosed=\"v\" 1\n",
                "9starts_with_digit 1\n",
                "cst_x 1 2 3 extra\n",
                "# MALFORMED comment\n"):
        with pytest.raises(ValueError):
            metrics_export.parse_exposition(bad)


def test_live_endpoint_serves_parseable_text():
    telemetry.configure(enabled=True)
    telemetry.count("serve.submitted")
    port = metrics_export.start(0)       # ephemeral port
    try:
        assert metrics_export.serving_port() == port
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=10) as resp:
            assert resp.headers["Content-Type"] \
                == metrics_export.CONTENT_TYPE
            text = resp.read().decode("utf-8")
    finally:
        metrics_export.stop()
    families = metrics_export.parse_exposition(text)
    assert families["cst_serve_submitted_total"] == [({}, 1.0)]
    assert metrics_export.serving_port() is None


def test_sanitize_name():
    assert metrics_export.sanitize_name("serve.queue_depth") \
        == "serve_queue_depth"
    assert metrics_export.sanitize_name("p99@verify") == "p99_verify"
    assert metrics_export.sanitize_name("1leading") == "_1leading"


# --- the reqtrace live window stays a fixed-size ring ------------------------


def test_live_window_is_bounded_and_summary_reads_the_tail():
    reqtrace.configure(enabled=True)
    cap = reqtrace._WINDOW_CAP
    for _ in range(cap + 64):
        reqtrace.mint("old").complete()
    assert len(reqtrace._window) == cap  # ring, not the full registry
    # the freshest `window` records are the ONLY ones a summary reads:
    # after 64 fresh completions, a window of 64 sees exactly them
    for _ in range(64):
        reqtrace.mint("new").complete()
    assert set(reqtrace.rolling_summary(window=64)) == {"new"}
    assert reqtrace.rolling_summary(window=64)["new"]["count"] == 64
    # monotone totals keep counting past every cap
    total, by_kind, by_outcome = reqtrace.completed_totals()
    assert total == cap + 128
    assert by_kind["old"] == cap + 64 and by_kind["new"] == 64
    assert by_outcome == {"ok": cap + 128}
