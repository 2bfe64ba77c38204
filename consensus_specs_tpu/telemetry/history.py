"""Benchwatch ingester: bench/telemetry rounds -> one longitudinal store.

Every perf measurement this repo produces lands somewhere different —
`BENCH_r*.json` / `MULTICHIP_r*.json` driver-round wrappers (a stdout
tail with JSON metric lines buried between log lines), the persisted
pure-Python oracle baselines (`bench_baseline.json`,
`bench_bls_baseline.json`), the pytest end-of-session telemetry
snapshot (`CST_TELEMETRY_OUT`, per-test spans), and live bench
emissions.  This module parses all of them into ONE schema-versioned
record shape and appends it to a JSON-lines history store
(`out/bench_history.jsonl`), so `telemetry.report` can compute trends
instead of a human re-reading raw tails.

Record schema (version `SCHEMA`; one JSON object per line):

    {"schema": 1,
     "source": "bench_round" | "multichip_round" | "baseline"
               | "bench_emit" | "pytest_snapshot" | "costmodel",
     "metric": str,              # e.g. "attestation_batch_128x64_verify_wall"
     "value":  float | None,     # the measurement (unit below)
     "unit":   str,              # "s", "us", "bool", ...
     # optional provenance / context:
     "vs_baseline": float,       # speedup over the pure-Python oracle
     "round": int,               # BENCH_rNN / MULTICHIP_rNN round number
     "file": str,                # basename the record was parsed from
     "rc": int,                  # driver wrapper return code
     "platform": str,            # jax.devices()[0].platform: "tpu" | "cpu"
     "baseline_us_per_validator": float,   # oracle fingerprint (flagship)
     "telemetry": dict,          # compact compile_s/run_s/padding/routing
     "detail": dict,             # msm break-even per-size table
     "msm_device_min": int,
     "costmodel": dict,          # one kernel's joined roofline record
                                 # (source "costmodel" only; metric
                                 # "costmodel::<kernel>" per kernel plus
                                 # "device_mem_high_water::<device>")
     "serve": dict,              # compacted sustained-load block
                                 # (source "serve" only; metric
                                 # "serve::<metric>" — verifies/sec,
                                 # p50/p99, queue-depth histogram,
                                 # steady flag, window rates)
     "latency": dict,            # compacted tail-latency attribution
                                 # (source "latency"; per kind
                                 # "latency::p99_ms@<kind>" carrying the
                                 # component decomposition, plus
                                 # "latency::p99_queue_frac" — the
                                 # serve-p99-queue-frac advisory row's
                                 # surface, carrying the worst-N
                                 # exemplar traces)
     "slo": dict,                # compacted SLO-watchdog round summary
                                 # (source "slo"; metric
                                 # "slo::breaches[@<rule>]" /
                                 # "slo::worst_margin@<rule>" /
                                 # "slo::clean_round" — the watchdog's
                                 # breach counts, per-rule worst
                                 # margins, and the non-chaos
                                 # clean-round 0/1 gate)
     "occupancy": dict,          # compacted device-occupancy block
                                 # (source "pipeline"; metric
                                 # "pipeline::busy_frac" — the
                                 # serve-occupancy threshold row's
                                 # surface — plus
                                 # "pipeline::bubble@<cause>" seconds
                                 # and "pipeline::overlap_score")
     "resilience": dict,         # compacted chaos-round block (source
                                 # "resilience" only; metric
                                 # "resilience::<metric>" — recovery
                                 # latency, wrong-result count, degraded
                                 # throughput, breaker transitions,
                                 # Merkle heal wall)
     "mesh": dict,               # compacted shard-loss recovery block
                                 # (source "mesh"; metric
                                 # "mesh::<metric>" — recovery latency,
                                 # lost/wrong statements, degraded
                                 # lanes, re-admissions)
     "checkpoint": dict,         # compacted restore block (source
                                 # "checkpoint"; metric
                                 # "checkpoint::<metric>" — restore wall
                                 # w/ restore-vs-rebuild speedup as
                                 # vs_baseline, journal depth, snapshot
                                 # bytes)
     "das": dict,                # compacted PeerDAS sampling-matrix
                                 # block (source "das"; metric
                                 # "das::verify_wall@<cols>x<blobs>"
                                 # per swept matrix + "das::speedup"
                                 # vs the pure-Python oracle +
                                 # "das::cells_per_s" throughput)
     "forkchoice": dict,         # compacted device LMD-GHOST tree
                                 # block (source "forkchoice"; metric
                                 # "forkchoice::head_wall@<b>x<v>" per
                                 # swept tree + "forkchoice::speedup"
                                 # vs the phase0 spec oracle +
                                 # "forkchoice::heads_per_s")
     "scaling": dict,            # compacted mesh-sharded flagship rung
                                 # (source "scaling"; metric
                                 # "scaling::flagship@<n>" per rung wall
                                 # + "scaling::efficiency[@<n>]" per-chip
                                 # throughput retention +
                                 # "scaling::flagship_8m_ok")
     "ts": float}                # wall-clock stamp (live emissions only)

Robustness contract (pinned by tests/test_benchwatch.py): malformed or
truncated inputs — a round that timed out before printing JSON, a
traceback tail, a non-JSON file, a history line with an unknown schema
version — are SKIPPED with a counted warning, never a crash.  A perf
dashboard that dies on the exact rounds that failed would be useless on
the rounds that matter most.

Stdlib-only, like the rest of the telemetry package: importing this
never touches jax, numpy, or a spec build.
"""

from __future__ import annotations

import json
import os
import re
import sys
from pathlib import Path

SCHEMA = 1

SOURCES = ("bench_round", "multichip_round", "baseline", "bench_emit",
           "pytest_snapshot", "costmodel", "serve", "resilience",
           "mesh", "checkpoint", "scaling", "das", "forkchoice",
           "latency", "slo", "pipeline")

_ROUND_FILE_RE = re.compile(r"(?:BENCH|MULTICHIP)_r(\d+)\.json$")

# stderr log lines worth mining from a round tail: the oracle-baseline
# fingerprint (tells the trend engine whether two rounds' vs_baseline
# numbers are even comparable) and the per-config compile+first walls
# (the ROADMAP's "< 40s" acceptance target predates the telemetry
# sub-object, so old rounds only carry them as log lines)
# two baseline log formats: fresh measure puts us/validator in parens
# ("baseline: 77.6s @ 1024 validators (75802.3 us/validator)"),
# persisted loads print it after the paren group ("baseline (persisted
# 2026-07-29): 244.6 us/validator @ 1024 validators")
_BASELINE_LINE_RE = re.compile(
    r"\(([0-9.]+)\s*us/validator\)"
    r"|baseline\s*\([^)]*\):\s*([0-9.]+)\s*us/validator")
_COMPILE_FIRST_RES = (
    (re.compile(r"compile\+first run ([0-9.]+)s"),
     "epoch_sweep_compile_first_s"),
    (re.compile(r"attestation batch compile\+first: ([0-9.]+)s"),
     "attestation_batch_compile_first_s"),
    (re.compile(r"sync aggregate compile\+first: ([0-9.]+)s"),
     "sync_aggregate_compile_first_s"),
    (re.compile(r"kzg batch device compile\+first: ([0-9.]+)s"),
     "blob_kzg_batch_compile_first_s"),
)


# --- record shape ------------------------------------------------------------


def make_record(source: str, metric: str, value, unit: str = "s",
                **extra) -> dict:
    """One normalized history record.  `extra` keys with value None are
    dropped so the JSONL stays compact and byte-stable (dedup hashes
    the canonical line)."""
    rec = {"schema": SCHEMA, "source": source, "metric": metric,
           "value": value, "unit": unit}
    for k, v in extra.items():
        if v is not None:
            rec[k] = v
    return rec


def validate_record(rec) -> list[str]:
    """Problems with one history record (empty == valid).  The contract
    `bench_smoke.py` asserts on every live emission."""
    problems: list[str] = []
    if not isinstance(rec, dict):
        return [f"record is {type(rec).__name__}, not dict"]
    if rec.get("schema") != SCHEMA:
        problems.append(f"schema must be {SCHEMA}, got {rec.get('schema')!r}")
    if rec.get("source") not in SOURCES:
        problems.append(f"unknown source {rec.get('source')!r}")
    if not isinstance(rec.get("metric"), str) or not rec.get("metric"):
        problems.append(f"metric must be a non-empty str, "
                        f"got {rec.get('metric')!r}")
    v = rec.get("value")
    if v is not None and (not isinstance(v, (int, float))
                          or isinstance(v, bool)):
        problems.append(f"value must be a number or null, got {v!r}")
    if not isinstance(rec.get("unit"), str):
        problems.append(f"unit must be a str, got {rec.get('unit')!r}")
    vb = rec.get("vs_baseline")
    if vb is not None and (not isinstance(vb, (int, float))
                           or isinstance(vb, bool)):
        problems.append(f"vs_baseline must be a number, got {vb!r}")
    return problems


def _canonical_line(rec: dict) -> str:
    return json.dumps(rec, sort_keys=True, separators=(",", ":"))


def _compact_telemetry(tel) -> dict | None:
    """The compile/run + padding + routing core of a bench telemetry
    sub-object; the full counter registry stays in the round file.  The
    `costmodel` watermark summary rides along compactly (per-kernel
    cost records become their own `costmodel`-source records instead —
    see `costmodel_records`)."""
    if not isinstance(tel, dict):
        return None
    out = {k: tel[k] for k in ("compile_s", "run_s", "padding", "routing")
           if k in tel}
    cm = tel.get("costmodel")
    if isinstance(cm, dict) and isinstance(cm.get("watermarks"), dict) \
            and cm["watermarks"]:
        out["watermarks"] = cm["watermarks"]
    return out or None


def serve_records(metric: str, serve, chaos: bool = False,
                  **context) -> list[dict]:
    """`serve`-source history records mined from one metric line's
    sustained-load `"serve"` sub-object (`serve.loadgen.run_load`'s
    block): one scalar record each for the steady-state throughput and
    the latency percentiles — the threshold-gate surface — with the
    compacted block (steady flag, window rates, queue-depth histogram,
    mode/shape knobs) riding on the throughput record.  `chaos` marks a
    chaos round (bench_serve hoists the `"resilience"` sub-object to
    the metric line's top level, so the caller must pass the flag) —
    it gates `slo::clean_round` off.  Malformed blocks yield zero
    records, never an exception."""
    vps = serve.get("verifies_per_s") if isinstance(serve, dict) else None
    if not isinstance(vps, (int, float)) or isinstance(vps, bool):
        return []
    compact = {k: serve[k] for k in (
        "steady", "windows", "window_s", "duration_s", "mode",
        "rate_multiple", "max_batch", "depth", "submitted", "settled",
        "failed", "rechecks", "batches", "queue_depth", "inflight_max",
        "retries", "fallbacks", "shed")
        if k in serve}
    if isinstance(serve.get("latency_source"), str):
        compact["latency_source"] = serve["latency_source"]
    records = [make_record(
        "serve", "serve::verifies_per_s", serve["verifies_per_s"],
        unit="verifies/s", serve=compact, via_metric=metric, **context)]
    for key, unit in (("p50_ms", "ms"), ("p99_ms", "ms")):
        v = serve.get(key)
        if isinstance(v, (int, float)) and not isinstance(v, bool):
            records.append(make_record(
                "serve", f"serve::{key}", v, unit=unit,
                via_metric=metric, **context))
    records.extend(latency_records(
        metric, serve.get("latency_attribution"), **context))
    records.extend(slo_records(
        metric, serve.get("slo"),
        chaos=chaos or isinstance(serve.get("resilience"), dict),
        **context))
    records.extend(occupancy_records(
        metric, serve.get("occupancy"), **context))
    return records


def occupancy_records(metric: str, occ, **context) -> list[dict]:
    """`pipeline`-source history records mined from a serve block's
    `"occupancy"` sub-object (`telemetry.occupancy.block`, rounds armed
    with CST_OCCUPANCY): the `pipeline::busy_frac` record — the
    `serve-occupancy` threshold row's surface — carrying the compacted
    block (wall, per-device busy, bubble attribution, depth), one
    `pipeline::bubble@<cause>` seconds record per bubble cause, and
    `pipeline::overlap_score` when any host prep was recorded.
    Malformed blocks yield zero records, never an exception."""
    if not isinstance(occ, dict):
        return []
    frac = occ.get("busy_frac")
    if not isinstance(frac, (int, float)) or isinstance(frac, bool):
        return []
    compact = {k: occ[k] for k in (
        "wall_s", "busy_s", "busy_frac", "bubbles_s", "depth",
        "events", "events_dropped", "device_seconds_by_kind")
        if k in occ}
    devs = occ.get("devices")
    if isinstance(devs, dict):
        compact["devices"] = {
            d: {k: b[k] for k in ("busy_s", "busy_frac", "spans")
                if isinstance(b, dict) and k in b}
            for d, b in devs.items()}
    records = [make_record(
        "pipeline", "pipeline::busy_frac", frac, unit="frac",
        occupancy=compact, via_metric=metric, **context)]
    bub = occ.get("bubbles_s")
    if isinstance(bub, dict):
        for cause, v in sorted(bub.items()):
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                records.append(make_record(
                    "pipeline", f"pipeline::bubble@{cause}", v,
                    unit="s", via_metric=metric, **context))
    ov = occ.get("overlap")
    if isinstance(ov, dict):
        score = ov.get("score")
        if isinstance(score, (int, float)) and not isinstance(score, bool):
            records.append(make_record(
                "pipeline", "pipeline::overlap_score", score,
                unit="frac", overlap=ov, via_metric=metric, **context))
    return records


def slo_records(metric: str, slo, chaos: bool = False,
                **context) -> list[dict]:
    """`slo`-source history records mined from a serve block's `"slo"`
    sub-object (`telemetry.monitor.Watchdog.slo_block`, armed rounds
    only): one `slo::breaches` total carrying the compact block, per
    rule a `slo::breaches@<rule>` count plus `slo::worst_margin@<rule>`
    when the rule ever failed a tick, and — on NON-chaos rounds only —
    the `slo::clean_round` 0/1 record the `slo-clean-round` threshold
    row gates on (a chaos round breaches BY DESIGN; its arc is asserted
    in the round itself and mined as `resilience::slo_arc_ok`).
    Malformed blocks yield zero records, never an exception."""
    if not isinstance(slo, dict):
        return []
    breaches = slo.get("breaches")
    ticks = slo.get("ticks")
    if not isinstance(breaches, int) or isinstance(breaches, bool) \
            or not isinstance(ticks, int) or isinstance(ticks, bool):
        return []
    compact = {k: slo[k] for k in (
        "ticks", "breaches", "clean", "breaching_now", "events_dropped")
        if k in slo}
    compact["rules"] = [
        {k: r[k] for k in ("name", "metric", "breaches", "clears",
                           "breaching", "worst_margin", "last_value")
         if k in r}
        for r in slo.get("rules", []) if isinstance(r, dict)]
    if slo.get("profiles"):
        compact["profiles"] = slo["profiles"]
    records = [make_record(
        "slo", "slo::breaches", breaches, unit="count", slo=compact,
        via_metric=metric, **context)]
    for r in slo.get("rules", []):
        if not isinstance(r, dict) or not isinstance(r.get("name"), str) \
                or not r.get("name"):
            continue
        rb = r.get("breaches")
        if isinstance(rb, int) and not isinstance(rb, bool):
            records.append(make_record(
                "slo", f"slo::breaches@{r['name']}", rb, unit="count",
                via_metric=metric, **context))
        wm = r.get("worst_margin")
        if isinstance(wm, (int, float)) and not isinstance(wm, bool):
            records.append(make_record(
                "slo", f"slo::worst_margin@{r['name']}", wm,
                unit="margin", via_metric=metric, **context))
    if not chaos and isinstance(slo.get("clean"), bool):
        records.append(make_record(
            "slo", "slo::clean_round", 1.0 if slo["clean"] else 0.0,
            unit="bool", via_metric=metric, **context))
    return records


def latency_records(metric: str, la, **context) -> list[dict]:
    """`latency`-source history records mined from a serve block's
    `latency_attribution` sub-object (`telemetry.reqtrace.attribution`,
    traced rounds only): one `latency::p99_ms@<kind>` record per
    request kind carrying the compacted per-kind block (p50/p90/p99,
    component decomposition, outcome counts), and one
    `latency::p99_queue_frac` record — the `serve-p99-queue-frac`
    advisory threshold row's surface — carrying the worst-N exemplar
    traces.  Malformed blocks yield zero records, never an
    exception."""
    if not isinstance(la, dict) or not isinstance(la.get("kinds"), dict):
        return []
    records: list[dict] = []
    for kind, blk in sorted(la["kinds"].items()):
        if not isinstance(blk, dict):
            continue
        p99 = blk.get("p99_ms")
        if not isinstance(p99, (int, float)) or isinstance(p99, bool):
            continue
        compact = {k: blk[k] for k in (
            "count", "p50_ms", "p90_ms", "p99_ms", "mean_components_ms",
            "p99_components_ms", "p99_queue_frac", "outcomes")
            if k in blk}
        records.append(make_record(
            "latency", f"latency::p99_ms@{kind}", p99, unit="ms",
            latency=compact, via_metric=metric, **context))
    frac = la.get("p99_queue_frac")
    if isinstance(frac, (int, float)) and not isinstance(frac, bool):
        records.append(make_record(
            "latency", "latency::p99_queue_frac", frac, unit="frac",
            latency={"worst": la.get("worst") or [],
                     "requests": la.get("requests"),
                     "answered": la.get("answered")},
            via_metric=metric, **context))
    return records


def resilience_records(metric: str, res, **context) -> list[dict]:
    """`resilience`-source history records mined from one metric line's
    chaos-round `"resilience"` sub-object
    (`resilience.chaos.run_chaos_load`): one scalar record per recovery
    metric — `resilience::recovery_latency_s` (the `chaos-recovery`
    threshold row's surface, carrying the compacted block),
    `resilience::wrong_results` (the correctness gate),
    degraded/baseline throughput, fault/transition counts, and the
    Merkle heal wall.  Malformed blocks yield zero records, never an
    exception."""
    if not isinstance(res, dict) or not isinstance(res.get("chaos"), bool):
        return []
    compact = {k: res[k] for k in (
        "chaos", "faults_injected", "injected_sites", "fault_victims",
        "wrong_results",
        "failed_requests", "checked_results", "recovered", "retries",
        "fallbacks", "shed") if k in res}
    br = res.get("breaker")
    if isinstance(br, dict):
        compact["breaker_states"] = br.get("states")
        compact["breaker_trips"] = br.get("trips")
    records = [make_record(
        "resilience", "resilience::recovery_latency_s",
        res.get("recovery_latency_s"), unit="s", resilience=compact,
        via_metric=metric, **context)]

    def scalar(key, name, unit):
        v = res.get(key)
        if isinstance(v, (int, float)) and not isinstance(v, bool):
            records.append(make_record(
                "resilience", name, v, unit=unit, via_metric=metric,
                **context))

    # recovered as its own 0/1 record (the chaos-recovered threshold
    # row): the latency record above carries value None on an
    # unrecovered round, which a numeric threshold cannot see — without
    # this, a failed round would silently leave the previous successful
    # round's PASS on the dashboard
    if isinstance(res.get("recovered"), bool):
        records.append(make_record(
            "resilience", "resilience::recovered",
            1.0 if res["recovered"] else 0.0, unit="bool",
            via_metric=metric, **context))
    scalar("wrong_results", "resilience::wrong_results", "count")
    scalar("degraded_verifies_per_s",
           "resilience::degraded_verifies_per_s", "verifies/s")
    scalar("baseline_verifies_per_s",
           "resilience::baseline_verifies_per_s", "verifies/s")
    scalar("faults_injected", "resilience::faults_injected", "count")
    if isinstance(br, dict) and isinstance(br.get("transitions"), list):
        records.append(make_record(
            "resilience", "resilience::breaker_transitions",
            len(br["transitions"]), unit="count", via_metric=metric,
            **context))
    heal = res.get("heal")
    if isinstance(heal, dict) and isinstance(heal.get("recovery_s"),
                                             (int, float)):
        records.append(make_record(
            "resilience", "resilience::merkle_heal_s",
            heal["recovery_s"], unit="s", via_metric=metric,
            heal_path=heal.get("path"), **context))
    fl = res.get("flagship")
    if isinstance(fl, dict) and isinstance(fl.get("degraded_steps"), int) \
            and not isinstance(fl.get("degraded_steps"), bool):
        records.append(make_record(
            "resilience", "resilience::flagship_degraded_steps",
            fl["degraded_steps"], unit="count", via_metric=metric,
            flagship={k: fl[k] for k in ("wrong_results",
                                         "checked_settles", "recovered")
                      if k in fl},
            **context))
    # the chaos round's watchdog arc as a 0/1 gate record: breached
    # inside the fault window AND cleared after recovery (the inverse
    # of slo::clean_round — a chaos round that stayed clean means the
    # watchdog missed a live incident)
    arc = res.get("slo_arc")
    if isinstance(arc, dict) \
            and isinstance(arc.get("breached_in_fault_window"), bool) \
            and isinstance(arc.get("cleared_after_recovery"), bool):
        ok = (arc["breached_in_fault_window"]
              and arc["cleared_after_recovery"])
        records.append(make_record(
            "resilience", "resilience::slo_arc_ok",
            1.0 if ok else 0.0, unit="bool", slo_arc=arc,
            via_metric=metric, **context))
    records.extend(mesh_records(metric, res.get("mesh"), **context))
    records.extend(checkpoint_records(metric, res.get("checkpoint"),
                                      **context))
    return records


def mesh_records(metric: str, mesh, **context) -> list[dict]:
    """`mesh`-source history records mined from a chaos round's
    `"mesh"` sub-object (`resilience.mesh.MeshVerifier.block` plus the
    segment's correctness counters): the shard-loss recovery latency
    (carrying the compact block — the `mesh-recovery` threshold row's
    surface), lost/wrong statement counts (the zero-loss gate), and
    the degradation/re-admission counters.  Skipped segments (too few
    devices) and malformed blocks yield zero records."""
    if not isinstance(mesh, dict) or "skipped" in mesh \
            or not isinstance(mesh.get("devices"), int):
        return []
    compact = {k: mesh[k] for k in (
        "devices", "degraded_lanes", "max_degraded_lanes",
        "device_lost_events", "readmissions", "retrips", "redispatches",
        "recoveries", "verified_statements", "lost_statements",
        "wrong_results", "checked_statements", "readmitted",
        "recovered") if k in mesh}
    records = [make_record(
        "mesh", "mesh::recovery_latency_s",
        mesh.get("recovery_latency_s"), unit="s", mesh=compact,
        via_metric=metric, **context)]
    # recovered as its own 0/1 record (the mesh-recovered threshold
    # row): an unrecovered round's latency record carries value null,
    # which a numeric threshold skips — without this the previous
    # round's PASS would stand (same fix as resilience::recovered)
    if isinstance(mesh.get("recovered"), bool):
        records.append(make_record(
            "mesh", "mesh::recovered",
            1.0 if mesh["recovered"] else 0.0, unit="bool",
            via_metric=metric, **context))

    def scalar(key, name, unit="count"):
        v = mesh.get(key)
        if isinstance(v, (int, float)) and not isinstance(v, bool):
            records.append(make_record(
                "mesh", name, v, unit=unit, via_metric=metric,
                **context))

    scalar("lost_statements", "mesh::lost_statements")
    scalar("wrong_results", "mesh::wrong_results")
    scalar("max_degraded_lanes", "mesh::degraded_lanes")
    scalar("device_lost_events", "mesh::device_lost_events")
    scalar("readmissions", "mesh::readmissions")
    return records


def checkpoint_records(metric: str, cp, **context) -> list[dict]:
    """`checkpoint`-source history records mined from a chaos round's
    `"checkpoint"` sub-object (`resilience.chaos._checkpoint_segment`):
    the restore wall with the restore-vs-rebuild speedup as its
    `vs_baseline` (the `checkpoint-restore` threshold row evaluates
    that field), plus journal depth and snapshot size.  Malformed
    blocks yield zero records."""
    if not isinstance(cp, dict) \
            or not isinstance(cp.get("restore_s"), (int, float)) \
            or isinstance(cp.get("restore_s"), bool):
        return []
    compact = {k: cp[k] for k in (
        "n_chunks", "journal_entries", "journal_replayed",
        "journal_frac", "snapshot_bytes", "rebuild_s", "parity")
        if k in cp}
    speedup = cp.get("speedup")
    records = [make_record(
        "checkpoint", "checkpoint::restore", cp["restore_s"], unit="s",
        vs_baseline=(speedup if isinstance(speedup, (int, float))
                     and not isinstance(speedup, bool) else None),
        checkpoint=compact, via_metric=metric, **context)]
    for key, name, unit in (
            ("journal_entries", "checkpoint::journal_entries", "count"),
            ("snapshot_bytes", "checkpoint::snapshot_bytes", "bytes")):
        v = cp.get(key)
        if isinstance(v, (int, float)) and not isinstance(v, bool):
            records.append(make_record(
                "checkpoint", name, v, unit=unit, via_metric=metric,
                **context))
    return records


def scaling_records(metric: str, sc, **context) -> list[dict]:
    """`scaling`-source history records mined from one metric line's
    mesh-sharded flagship `"scaling"` sub-object (`bench.py --worker
    scaling`): per rung a `scaling::flagship@<n_validators>` wall
    record (carrying the compact rung block — n_devices, per-chip and
    single-chip throughput) and a `scaling::efficiency@<n>` per-chip
    retention record; one `scaling::efficiency` summary record (the
    LARGEST completed rung at the widest mesh — the threshold-gate
    surface, so a small rung's tie can never outrank it) and a
    `scaling::flagship_8m_ok` 0/1 record when an 8M-validator rung was
    attempted.  Malformed blocks yield zero records, never an
    exception."""
    if not isinstance(sc, dict) or not isinstance(sc.get("rungs"), list):
        return []
    records: list[dict] = []
    best = None
    for r in sc["rungs"]:
        if not isinstance(r, dict):
            continue
        n = r.get("n_validators")
        wall = r.get("wall_s")
        if not isinstance(n, int) or isinstance(n, bool) \
                or not isinstance(wall, (int, float)) \
                or isinstance(wall, bool):
            continue
        compact = {k: r[k] for k in (
            "n_validators", "n_devices", "per_chip_vps", "total_vps",
            "single_chip_wall_s", "single_chip_vps", "efficiency")
            if k in r}
        records.append(make_record(
            "scaling", f"scaling::flagship@{n}", wall, unit="s",
            scaling=compact, via_metric=metric, **context))
        eff = r.get("efficiency")
        if isinstance(eff, (int, float)) and not isinstance(eff, bool):
            records.append(make_record(
                "scaling", f"scaling::efficiency@{n}", eff,
                unit="ratio", via_metric=metric, **context))
            key = (n, r.get("n_devices") or 0)
            if best is None or key > best[0]:
                best = (key, eff, compact)
    if best is not None:
        records.append(make_record(
            "scaling", "scaling::efficiency", best[1], unit="ratio",
            scaling=best[2], via_metric=metric, **context))
    if isinstance(sc.get("ok_8m"), bool):
        records.append(make_record(
            "scaling", "scaling::flagship_8m_ok",
            1.0 if sc["ok_8m"] else 0.0, unit="bool",
            via_metric=metric, **context))
    return records


def das_records(metric: str, das, **context) -> list[dict]:
    """`das`-source history records mined from one metric line's
    PeerDAS `"das"` sub-object (`bench.py --worker das` /
    `bench_smoke.py --das`): the verification wall for the swept
    sampling matrix (carrying the compact block, speedup as
    `vs_baseline`), the `das::speedup` record the CPU-evaluated
    `das-speedup` threshold row gates on, and the `das::cells_per_s`
    throughput record the TPU-gated `das-throughput` row reads.
    Malformed blocks yield zero records, never an exception."""
    if not isinstance(das, dict):
        return []
    matrix = das.get("matrix")
    wall = das.get("verify_wall_s")
    if not isinstance(matrix, dict) \
            or not isinstance(wall, (int, float)) \
            or isinstance(wall, bool):
        return []
    cols, blobs = matrix.get("columns"), matrix.get("blobs")
    if not isinstance(cols, int) or not isinstance(blobs, int) \
            or isinstance(cols, bool) or isinstance(blobs, bool):
        return []
    compact = {k: das[k] for k in (
        "matrix", "rung", "oracle_wall_s", "oracle_cells_measured",
        "compile_first_s", "batch_verdict", "isolate",
        "eval_crosscheck") if k in das}
    speedup = das.get("speedup")
    speedup = speedup if isinstance(speedup, (int, float)) \
        and not isinstance(speedup, bool) else None
    records = [make_record(
        "das", f"das::verify_wall@{cols}x{blobs}", wall, unit="s",
        vs_baseline=speedup, das=compact, via_metric=metric,
        **context)]
    if speedup is not None:
        records.append(make_record(
            "das", "das::speedup", speedup, unit="x",
            via_metric=metric, **context))
    cps = das.get("cells_per_s")
    if isinstance(cps, (int, float)) and not isinstance(cps, bool):
        records.append(make_record(
            "das", "das::cells_per_s", cps, unit="cells/s",
            via_metric=metric, **context))
    return records


def das_producer_records(metric: str, prod, **context) -> list[dict]:
    """`das`-source history records mined from one metric line's
    `"das_producer"` sub-object (the FK20 producer + erasure-recovery
    sweep `bench.py --worker das` emits): `das::produce_wall` (carrying
    the compact block, producer speedup as `vs_baseline`),
    `das::proofs_per_s`, and the `das::producer_speedup` record the
    CPU-evaluated `das-producer-speedup` threshold row gates on; when
    the recovery sub-object is present, `das::recover_wall` plus the
    `das::recover_speedup` record behind `das-recover-speedup`.
    Malformed blocks yield zero records, never an exception."""
    if not isinstance(prod, dict):
        return []

    def _num(v):
        return v if isinstance(v, (int, float)) \
            and not isinstance(v, bool) else None

    wall = _num(prod.get("produce_wall_s"))
    if wall is None:
        return []
    speedup = _num(prod.get("producer_speedup"))
    compact = {k: prod[k] for k in (
        "produce_first_s", "du_wall_s", "du_msms_measured",
        "parity") if k in prod}
    records = [make_record(
        "das", "das::produce_wall", wall, unit="s",
        vs_baseline=speedup, das_producer=compact, via_metric=metric,
        **context)]
    if speedup is not None:
        records.append(make_record(
            "das", "das::producer_speedup", speedup, unit="x",
            via_metric=metric, **context))
    pps = _num(prod.get("proofs_per_s"))
    if pps is not None:
        records.append(make_record(
            "das", "das::proofs_per_s", pps, unit="proofs/s",
            via_metric=metric, **context))
    rec = prod.get("recover")
    if isinstance(rec, dict):
        rwall = _num(rec.get("wall_s"))
        rspeed = _num(rec.get("speedup"))
        if rwall is not None:
            records.append(make_record(
                "das", "das::recover_wall", rwall, unit="s",
                vs_baseline=rspeed,
                das_recover={k: rec[k] for k in (
                    "cells_in", "missing", "oracle_wall_s",
                    "oracle_cosets_measured", "roundtrip") if k in rec},
                via_metric=metric, **context))
        if rspeed is not None:
            records.append(make_record(
                "das", "das::recover_speedup", rspeed, unit="x",
                via_metric=metric, **context))
    return records


def forkchoice_records(metric: str, fc, **context) -> list[dict]:
    """`forkchoice`-source history records mined from one metric
    line's `"forkchoice"` sub-object (`bench.py --worker forkchoice` /
    `bench_smoke.py --forkchoice`): the per-shape head wall (carrying
    the compact block, speedup as `vs_baseline`), the
    `forkchoice::speedup` record the CPU-evaluated `fc-speedup`
    threshold row gates on, and the `forkchoice::heads_per_s` record
    the TPU-gated `fc-head-throughput` row reads.  Malformed blocks
    yield zero records, never an exception."""
    if not isinstance(fc, dict):
        return []
    tree = fc.get("tree")
    wall = fc.get("head_wall_s")
    if not isinstance(tree, dict) \
            or not isinstance(wall, (int, float)) \
            or isinstance(wall, bool):
        return []
    blocks, validators = tree.get("blocks"), tree.get("validators")
    if not isinstance(blocks, int) or not isinstance(validators, int) \
            or isinstance(blocks, bool) or isinstance(validators, bool):
        return []
    compact = {k: fc[k] for k in (
        "tree", "rungs", "apply_wall_s", "oracle_head_wall_s",
        "oracle_validators_measured", "compile_first_s", "parity")
        if k in fc}
    speedup = fc.get("speedup")
    speedup = speedup if isinstance(speedup, (int, float)) \
        and not isinstance(speedup, bool) else None
    records = [make_record(
        "forkchoice", f"forkchoice::head_wall@{blocks}x{validators}",
        wall, unit="s", vs_baseline=speedup, forkchoice=compact,
        via_metric=metric, **context)]
    if speedup is not None:
        records.append(make_record(
            "forkchoice", "forkchoice::speedup", speedup, unit="x",
            via_metric=metric, **context))
    hps = fc.get("heads_per_s")
    if isinstance(hps, (int, float)) and not isinstance(hps, bool):
        records.append(make_record(
            "forkchoice", "forkchoice::heads_per_s", hps,
            unit="heads/s", via_metric=metric, **context))
    return records


def costmodel_records(metric: str, tel, **context) -> list[dict]:
    """Per-kernel `costmodel`-source history records mined from one
    metric line's telemetry sub-object (joined roofline records from
    `telemetry.costmodel.block`).  Malformed blocks yield zero records,
    never an exception — same degradation policy as every other parser
    here.  `context` carries provenance (round/file/rc/platform/ts)."""
    if not isinstance(tel, dict):
        return []
    cm = tel.get("costmodel")
    if not isinstance(cm, dict) or not isinstance(cm.get("kernels"), dict):
        return []
    records = []
    for kernel, rec in sorted(cm["kernels"].items()):
        if not isinstance(rec, dict) or "error" in rec:
            continue
        run_s = rec.get("run_s_mean")
        records.append(make_record(
            "costmodel", f"costmodel::{kernel}",
            run_s if isinstance(run_s, (int, float)) else None,
            unit="s", costmodel=rec, via_metric=metric, **context))
    wms = cm.get("watermarks")
    if isinstance(wms, dict):
        for dev, wm in sorted(wms.items()):
            if isinstance(wm, dict) and isinstance(
                    wm.get("high_water_bytes"), int):
                records.append(make_record(
                    "costmodel", f"device_mem_high_water::{dev}",
                    wm["high_water_bytes"], unit="bytes",
                    samples=wm.get("samples"), **context))
    return records


# --- bench round tails -------------------------------------------------------


def _tail_json_lines(tail: str) -> list[dict]:
    out = []
    for line in tail.splitlines():
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            obj = json.loads(line)
        except json.JSONDecodeError:
            continue    # truncated mid-line — the enclosing round warns
        if isinstance(obj, dict) and "metric" in obj:
            out.append(obj)
    return out


def _merge_metric_lines(lines: list[dict]) -> dict[str, dict]:
    """bench.py re-prints the flagship line as a growing superset after
    each extras worker; later occurrences win, and the `extra` map is
    flattened into per-metric records (each already carries its own
    value/unit/vs_baseline/telemetry)."""
    merged: dict[str, dict] = {}
    for obj in lines:
        flat = dict(obj)
        extras = flat.pop("extra", None) or {}
        merged[flat["metric"]] = flat
        platform = flat.get("platform")
        for name, sub in extras.items():
            if not isinstance(sub, dict):
                continue
            sub = dict(sub)
            sub.setdefault("metric", name)
            if platform is not None:
                sub.setdefault("platform", platform)
            merged[name] = sub
    return merged


def parse_bench_round(path) -> tuple[list[dict], list[str]]:
    """All history records extractable from one BENCH_rNN.json wrapper.
    A round whose tail has no parseable metric line (timeout, crash)
    yields zero metric records and one warning — never an exception."""
    path = Path(path)
    warnings: list[str] = []
    m = _ROUND_FILE_RE.search(path.name)
    rnd = int(m.group(1)) if m else None
    try:
        wrapper = json.loads(path.read_text())
    except (OSError, json.JSONDecodeError, UnicodeDecodeError) as e:
        return [], [f"{path.name}: unreadable round wrapper "
                    f"({type(e).__name__}: {e})"]
    if not isinstance(wrapper, dict):
        return [], [f"{path.name}: round wrapper is not a JSON object"]
    rnd = wrapper.get("n", rnd) if isinstance(wrapper.get("n"), int) else rnd
    rc = wrapper.get("rc")
    tail = wrapper.get("tail") or ""
    if not isinstance(tail, str):
        return [], [f"{path.name}: round tail is not a string"]

    fingerprint = None
    fm = _BASELINE_LINE_RE.search(tail)
    if fm:
        fingerprint = float(fm.group(1) or fm.group(2))

    records: list[dict] = []
    # cost records are cumulative per-process facts, so every metric
    # line in a round carries (a superset of) the previous line's
    # costmodel block — keep ONE record per kernel/device, last line
    # wins (it has the most dispatches joined in)
    cost_by_metric: dict[str, dict] = {}
    merged = _merge_metric_lines(_tail_json_lines(tail))
    for name, obj in merged.items():
        rec = make_record(
            "bench_round", name, obj.get("value"),
            unit=obj.get("unit", "s"),
            vs_baseline=obj.get("vs_baseline"),
            round=rnd, file=path.name, rc=rc,
            platform=obj.get("platform"),
            telemetry=_compact_telemetry(obj.get("telemetry")),
            detail=obj.get("detail"),
            msm_device_min=obj.get("msm_device_min"),
            error=obj.get("error"),
        )
        if name == "mainnet_epoch_sweep_1m_validators_wall" and fingerprint:
            rec["baseline_us_per_validator"] = fingerprint
        records.append(rec)
        records.extend(serve_records(
            name, obj.get("serve"),
            chaos=isinstance(obj.get("resilience"), dict),
            round=rnd, file=path.name,
            rc=rc, platform=obj.get("platform")))
        records.extend(resilience_records(
            name, obj.get("resilience"), round=rnd, file=path.name,
            rc=rc, platform=obj.get("platform")))
        records.extend(scaling_records(
            name, obj.get("scaling"), round=rnd, file=path.name,
            rc=rc, platform=obj.get("platform")))
        records.extend(das_records(
            name, obj.get("das"), round=rnd, file=path.name,
            rc=rc, platform=obj.get("platform")))
        records.extend(das_producer_records(
            name, obj.get("das_producer"), round=rnd, file=path.name,
            rc=rc, platform=obj.get("platform")))
        records.extend(forkchoice_records(
            name, obj.get("forkchoice"), round=rnd, file=path.name,
            rc=rc, platform=obj.get("platform")))
        for crec in costmodel_records(
                name, obj.get("telemetry"), round=rnd, file=path.name,
                rc=rc, platform=obj.get("platform")):
            cost_by_metric[crec["metric"]] = crec
    records.extend(cost_by_metric.values())

    # compile+first walls from the stderr log lines; a metric record's
    # telemetry block is the second source when the log line is gone
    for cf_re, cf_metric in _COMPILE_FIRST_RES:
        cm = cf_re.search(tail)
        if cm:
            records.append(make_record(
                "bench_round", cf_metric, float(cm.group(1)),
                round=rnd, file=path.name, rc=rc))

    if not merged:
        warnings.append(
            f"{path.name}: no parseable metric line in round tail "
            f"(rc={rc}) — skipped")
    return records, warnings


def parse_multichip_round(path) -> tuple[list[dict], list[str]]:
    path = Path(path)
    m = _ROUND_FILE_RE.search(path.name)
    rnd = int(m.group(1)) if m else None
    try:
        wrapper = json.loads(path.read_text())
    except (OSError, json.JSONDecodeError, UnicodeDecodeError) as e:
        return [], [f"{path.name}: unreadable round wrapper "
                    f"({type(e).__name__}: {e})"]
    if not isinstance(wrapper, dict) or "ok" not in wrapper:
        return [], [f"{path.name}: not a multichip round wrapper"]
    rec = make_record(
        "multichip_round", "multichip_dryrun_ok",
        1.0 if wrapper.get("ok") else 0.0, unit="bool",
        round=rnd, file=path.name, rc=wrapper.get("rc"),
        n_devices=wrapper.get("n_devices"),
        skipped=bool(wrapper.get("skipped")) or None)
    return [rec], []


# --- oracle baselines --------------------------------------------------------


def parse_baseline_file(path) -> tuple[list[dict], list[str]]:
    """bench_baseline.json / bench_bls_baseline.json -> oracle metric
    records (the pure-Python costs every vs_baseline divides by)."""
    path = Path(path)
    try:
        data = json.loads(path.read_text())
    except (OSError, json.JSONDecodeError, UnicodeDecodeError) as e:
        return [], [f"{path.name}: unreadable baseline "
                    f"({type(e).__name__}: {e})"]
    if not isinstance(data, dict):
        return [], [f"{path.name}: baseline is not a JSON object"]
    mapping = (
        ("seconds_per_validator", "oracle_epoch_us_per_validator",
         "us", 1e6),
        ("oracle_seconds_per_fast_aggregate_verify",
         "oracle_fast_aggregate_verify_s", "s", 1.0),
        ("oracle_seconds_per_sync_aggregate_verify",
         "oracle_sync_aggregate_verify_s", "s", 1.0),
    )
    records = []
    for key, metric, unit, scale in mapping:
        v = data.get(key)
        if isinstance(v, (int, float)) and not isinstance(v, bool):
            records.append(make_record(
                "baseline", metric, round(v * scale, 6), unit=unit,
                file=path.name, measured_at=data.get("measured_at")))
    if not records:
        return [], [f"{path.name}: no known baseline keys — skipped"]
    return records, []


# --- pytest telemetry snapshot (CST_TELEMETRY_OUT) ---------------------------

# per-test phase aggregates written by tests/conftest.py:
#   "<nodeid> [spec-build]" / "<nodeid> [test-body]"
_PHASE_SUFFIX_RE = re.compile(r"^(?P<test>.+) \[(?P<phase>spec-build|"
                              r"test-body)\]$")


def parse_telemetry_snapshot(path) -> tuple[list[dict], list[dict],
                                            list[str]]:
    """(history_records, per_test_attribution, warnings) from one
    `telemetry.snapshot()` JSON file (the CST_TELEMETRY_OUT artifact).

    History gets the small stuff (tier-1 session wall, spec-build
    total); the per-test attribution rows — one per test nodeid, with
    `total_s` split into `spec_build_s` vs `test_body_s` — go straight
    to the report's top-N table rather than ballooning the store with
    thousands of per-test lines."""
    path = Path(path)
    try:
        snap = json.loads(path.read_text())
    except (OSError, json.JSONDecodeError, UnicodeDecodeError) as e:
        return [], [], [f"{path.name}: unreadable snapshot "
                        f"({type(e).__name__}: {e})"]
    if not isinstance(snap, dict) or not isinstance(snap.get("spans"), dict):
        return [], [], [f"{path.name}: not a telemetry snapshot — skipped"]

    # the snapshot file's mtime is the record timestamp: snapshots carry
    # no round number, and without a ts every stored tier1_wall_s would
    # tie in the report's latest-wins ordering (the FIRST-ever value
    # would be evaluated forever)
    try:
        ts = round(path.stat().st_mtime, 1)
    except OSError:
        ts = None

    records: list[dict] = []
    meta = snap.get("meta") or {}
    wall = meta.get("tier1.session_wall_s")
    if isinstance(wall, (int, float)) and not isinstance(wall, bool):
        # platform-stamped "cpu": the tier-1 suite always runs on the
        # CPU backend (tests/conftest.py pins it), and an unstamped
        # record would be grouped with the TPU rounds by the report's
        # regression gate — noisy pytest walls must not read as TPU
        # perf regressions
        records.append(make_record(
            "pytest_snapshot", "tier1_wall_s", round(float(wall), 3),
            file=path.name, tests=meta.get("tier1.tests"),
            platform="cpu", ts=ts))

    tests: dict[str, dict] = {}
    spec_build_total = 0.0
    for name, agg in snap["spans"].items():
        if not isinstance(agg, dict):
            continue
        total = agg.get("total_s")
        if not isinstance(total, (int, float)):
            continue
        if name == "spec.build":
            spec_build_total = float(total)
            continue
        pm = _PHASE_SUFFIX_RE.match(name)
        if pm:
            row = tests.setdefault(
                pm.group("test"),
                {"test": pm.group("test"), "total_s": 0.0,
                 "spec_build_s": 0.0, "test_body_s": 0.0})
            key = ("spec_build_s" if pm.group("phase") == "spec-build"
                   else "test_body_s")
            row[key] += float(total)
        elif "::" in name:
            row = tests.setdefault(
                name, {"test": name, "total_s": 0.0,
                       "spec_build_s": 0.0, "test_body_s": 0.0})
            row["total_s"] += float(total)
    for row in tests.values():
        if not row["total_s"]:
            row["total_s"] = row["spec_build_s"] + row["test_body_s"]
    if spec_build_total:
        records.append(make_record(
            "pytest_snapshot", "tier1_spec_build_total_s",
            round(spec_build_total, 3), file=path.name, platform="cpu",
            ts=ts))
    attribution = sorted(tests.values(), key=lambda r: -r["total_s"])
    return records, attribution, []


# pytest `--durations` report lines: "0.52s call tests/foo.py::test_x"
_DURATION_LINE_RE = re.compile(
    r"^\s*([0-9.]+)s\s+(call|setup|teardown)\s+(\S+::\S+)\s*$")


def parse_durations(text: str) -> list[dict]:
    """pytest --durations output -> [{test, phase, dur_s}] rows (a
    second, coarser source for the tier-1 attribution table when no
    telemetry snapshot is available)."""
    rows = []
    for line in text.splitlines():
        m = _DURATION_LINE_RE.match(line)
        if m:
            rows.append({"test": m.group(3), "phase": m.group(2),
                         "dur_s": float(m.group(1))})
    return rows


# --- the store ---------------------------------------------------------------


def append_records(path, records) -> int:
    """Append records as JSON lines (creating parent dirs); returns the
    number written.  No dedup — use `sync_records` for idempotence."""
    path = Path(path)
    records = [r for r in records if not validate_record(r)]
    if not records:
        return 0
    path.parent.mkdir(parents=True, exist_ok=True)
    with open(path, "a") as f:
        for rec in records:
            f.write(_canonical_line(rec) + "\n")
    return len(records)


def load_history(path) -> tuple[list[dict], int, list[str]]:
    """(records, skipped_count, warnings).  Lines that are not valid
    JSON, not schema-`SCHEMA` records, or otherwise malformed are
    skipped and counted — an old or future store must degrade, not
    crash the reporter."""
    path = Path(path)
    records: list[dict] = []
    warnings: list[str] = []
    skipped = 0
    if not path.exists():
        return records, skipped, warnings
    try:
        text = path.read_text()
    except (OSError, UnicodeDecodeError) as e:
        return [], 1, [f"{path.name}: unreadable history "
                       f"({type(e).__name__}: {e})"]
    for i, line in enumerate(text.splitlines(), 1):
        if not line.strip():
            continue
        try:
            rec = json.loads(line)
        except json.JSONDecodeError:
            skipped += 1
            warnings.append(f"{path.name}:{i}: malformed history line "
                            f"— skipped")
            continue
        if not isinstance(rec, dict) or rec.get("schema") != SCHEMA:
            skipped += 1
            warnings.append(
                f"{path.name}:{i}: unknown schema version "
                f"{rec.get('schema') if isinstance(rec, dict) else '?'!r} "
                f"(this reader is v{SCHEMA}) — skipped")
            continue
        problems = validate_record(rec)
        if problems:
            skipped += 1
            warnings.append(f"{path.name}:{i}: invalid record "
                            f"({problems[0]}) — skipped")
            continue
        records.append(rec)
    return records, skipped, warnings


def sync_records(path, records) -> int:
    """Append only records whose canonical line is not already in the
    store — re-running the reporter over the same checked-in rounds is
    a no-op on the second pass.  Returns the number appended."""
    existing, _, _ = load_history(path)
    seen = {_canonical_line(r) for r in existing}
    fresh = [r for r in records
             if not validate_record(r) and _canonical_line(r) not in seen]
    return append_records(path, fresh)


# --- live bench emissions ----------------------------------------------------


def emission_platform() -> str | None:
    """Platform stamp for a live bench emission that carries none: what
    the device of this process reports, when the process has JAX loaded
    (bench_bls / bench_serve emit from the process that ran the work).
    None otherwise — this module never imports JAX, and the bench.py
    parent stamps each record from its worker's own report."""
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    return jax.devices()[0].platform


# live-emission costmodel dedupe: a bench process emits one metric line
# per config, but cost records are cumulative per-process facts — each
# later line carries (a superset of) the previous block, and the fresh
# `ts`/`via_metric` stamps would defeat the store's canonical-line
# dedupe.  Re-emit a kernel/watermark record only when its payload
# actually changed (more dispatches joined in, high-water moved).
_emitted_cost_payloads: dict[str, str] = {}


def emission_records(metric_line: dict, ts: float | None = None
                     ) -> list[dict]:
    """Normalize one live bench stdout line (a bench_bls metric record,
    or bench.py's flagship superset line with `extra`) into history
    records, stamped with the wall clock so distinct runs stay
    distinct."""
    records = []
    for name, obj in _merge_metric_lines([metric_line]).items():
        platform = obj.get("platform") or emission_platform()
        records.append(make_record(
            "bench_emit", name, obj.get("value"),
            unit=obj.get("unit", "s"),
            vs_baseline=obj.get("vs_baseline"),
            platform=platform,
            telemetry=_compact_telemetry(obj.get("telemetry")),
            detail=obj.get("detail"),
            msm_device_min=obj.get("msm_device_min"),
            error=obj.get("error"),
            ts=round(ts, 1) if ts is not None else None))
        for srec in serve_records(
                name, obj.get("serve"),
                chaos=isinstance(obj.get("resilience"), dict),
                platform=platform,
                ts=round(ts, 1) if ts is not None else None):
            records.append(srec)
        for rrec in resilience_records(
                name, obj.get("resilience"), platform=platform,
                ts=round(ts, 1) if ts is not None else None):
            records.append(rrec)
        for srec in scaling_records(
                name, obj.get("scaling"), platform=platform,
                ts=round(ts, 1) if ts is not None else None):
            records.append(srec)
        for drec in das_records(
                name, obj.get("das"), platform=platform,
                ts=round(ts, 1) if ts is not None else None):
            records.append(drec)
        for drec in das_producer_records(
                name, obj.get("das_producer"), platform=platform,
                ts=round(ts, 1) if ts is not None else None):
            records.append(drec)
        for frec in forkchoice_records(
                name, obj.get("forkchoice"), platform=platform,
                ts=round(ts, 1) if ts is not None else None):
            records.append(frec)
        for crec in costmodel_records(
                name, obj.get("telemetry"), platform=platform,
                ts=round(ts, 1) if ts is not None else None):
            payload = _canonical_line(
                {k: v for k, v in crec.items()
                 if k not in ("ts", "via_metric")})
            if _emitted_cost_payloads.get(crec["metric"]) == payload:
                continue
            _emitted_cost_payloads[crec["metric"]] = payload
            records.append(crec)
    return records


def append_emission(metric_line: dict, ts: float | None = None) -> int:
    """The bench-side hook: when CST_BENCHWATCH_HISTORY names a path,
    append this emission's normalized records there.  Disabled (the
    default) it is a single env read — the bench JSON contract on
    stdout is unchanged either way."""
    path = os.environ.get("CST_BENCHWATCH_HISTORY")
    if not path or not isinstance(metric_line, dict) \
            or "metric" not in metric_line:
        return 0
    try:
        return append_records(path, emission_records(metric_line, ts=ts))
    except OSError:
        return 0    # history is an observability side-channel, never fatal


# --- repo-wide ingest --------------------------------------------------------


def ingest_repo(root) -> tuple[list[dict], list[str]]:
    """Every record extractable from the checked-in perf artifacts under
    `root`: BENCH_r*.json, MULTICHIP_r*.json, and the two persisted
    oracle baselines."""
    root = Path(root)
    records: list[dict] = []
    warnings: list[str] = []
    for path in sorted(root.glob("BENCH_r*.json")):
        recs, warns = parse_bench_round(path)
        records.extend(recs)
        warnings.extend(warns)
    for path in sorted(root.glob("MULTICHIP_r*.json")):
        recs, warns = parse_multichip_round(path)
        records.extend(recs)
        warnings.extend(warns)
    for name in ("bench_baseline.json", "bench_bls_baseline.json"):
        path = root / name
        if path.exists():
            recs, warns = parse_baseline_file(path)
            records.extend(recs)
            warnings.extend(warns)
    return records, warnings
