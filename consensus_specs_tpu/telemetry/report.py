"""Benchwatch reporter: trends, ROADMAP threshold gates, attribution.

`python -m consensus_specs_tpu.telemetry.report` ingests every perf
artifact in the repo (BENCH/MULTICHIP round wrappers, oracle baselines,
the optional pytest telemetry snapshot), folds it into the longitudinal
store (`out/bench_history.jsonl`, see `telemetry.history`), and renders
one markdown dashboard:

- per-metric trend tables across rounds (value, speedup vs the
  pure-Python oracle, delta vs the previous round);
- the declarative ROADMAP threshold table (attestation >= 30x, sync
  aggregate >= 5x, `verify_blob_kzg_proof_batch` >= 2x, compile+first
  < 40s, tier-1 wall < 870s, multichip dryrun ok, serve steady-state
  throughput >= 10k verifies/s and p99 batch latency < 500ms — the
  sustained-load `serve::*` records `bench_serve.py` emits — plus the
  chaos-round gates: fault-stop → steady-state recovery < 60s and zero
  wrong results, from the `resilience::*` records; the mesh shard-loss
  gates — recovery < 60s, zero lost/wrong statements — from the
  `mesh::*` records; checkpoint restore+replay >= 5x over a full
  rebuild from the `checkpoint::*` records; and the mesh-sharded
  flagship gates — >= 70% per-chip throughput retention at the full
  mesh and the 8M-validator rung completing, from the `scaling::*`
  records; and the SLO watchdog gates — a zero-breach non-chaos serve
  round (`slo::clean_round`) and the chaos breach→clear arc
  (`resilience::slo_arc_ok`)) evaluated against the latest data;
- a generic round-over-round regression rule (no TPU metric may
  regress more than CST_BENCHWATCH_MAX_REGRESS_PCT percent);
- the `_MSM_DEVICE_MIN` break-even recommendation from the
  `g1_msm_breakeven_probe` rows;
- the Utilization section (CST_COSTMODEL rounds): per-kernel roofline
  table from the XLA cost/memory analysis records — flops, bytes,
  arithmetic intensity, achieved-vs-peak, compute/memory/launch-bound
  classification — plus the attestation compile-vs-execute verdict and
  per-device memory high-water marks;
- the tier-1 wall-time attribution table, split spec-build vs
  test-body per test (the conftest phase spans), naming the trim
  targets the ROADMAP asks for.

Exit code contract (what CI gates on): nonzero iff a round-over-round
regression fired, or — with `--strict` / CST_BENCHWATCH_STRICT=1 — any
ROADMAP threshold FAILs.  Without strict mode the threshold column is
advisory: the ROADMAP targets are acceptance criteria for the *next*
TPU round ("re-open per config if not met"), and several checked-in
rounds predate the kernels that are meant to meet them, so hard-gating
every CI run on them would just mean a permanently red gate.

Adding a threshold for a new metric = one entry in `THRESHOLDS`
(regex over metric names, field, op, target); the README's Benchwatch
section documents the columns.

Stdlib-only; safe to run anywhere, never imports jax.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time
from pathlib import Path

from . import history

# --- declarative threshold table --------------------------------------------
#
# field: which record field is compared ("vs_baseline" | "value").
# tpu_only: evaluate only against TPU-platform records (the ROADMAP
#   speedup targets are TPU acceptance criteria; a CPU smoke round must
#   read "no data", not FAIL).
# op: ">=" (bigger is better) or "<" (smaller is better).

THRESHOLDS = (
    {"id": "attestation-speedup",
     "title": "#2 attestation batch vs oracle",
     "metric": r"attestation_batch_\d+x\d+_verify_wall",
     "field": "vs_baseline", "op": ">=", "target": 30.0, "tpu_only": True},
    {"id": "sync-aggregate-speedup",
     "title": "#3 sync aggregate vs oracle",
     "metric": r"sync_aggregate_\d+_verify_wall",
     "field": "vs_baseline", "op": ">=", "target": 5.0, "tpu_only": True},
    {"id": "kzg-batch-speedup",
     "title": "#5 verify_blob_kzg_proof_batch vs oracle",
     "metric": r"blob_kzg_proof_batch_\d+_verify_wall",
     "field": "vs_baseline", "op": ">=", "target": 2.0, "tpu_only": True},
    {"id": "attestation-compile-first",
     "title": "attestation compile+first wall",
     "metric": r"attestation_batch_compile_first_s",
     "field": "value", "op": "<", "target": 40.0, "tpu_only": True},
    {"id": "tier1-wall",
     "title": "tier-1 suite wall budget",
     "metric": r"tier1_wall_s",
     "field": "value", "op": "<", "target": 870.0, "tpu_only": False},
    {"id": "multichip",
     "title": "multichip dryrun healthy",
     "metric": r"multichip_dryrun_ok",
     "field": "value", "op": ">=", "target": 1.0, "tpu_only": False},
    # the serving subsystem's production claim (ROADMAP sustained-load
    # item): steady-state throughput orders of magnitude past the
    # EdDSA-vs-BLS per-core baseline, with bounded tail latency.  TPU
    # acceptance criteria — the CPU smoke's closed-loop rate reads
    # "no data" here, not FAIL.
    {"id": "serve-throughput",
     "title": "serve steady-state verifies/sec",
     "metric": r"serve::verifies_per_s",
     "field": "value", "op": ">=", "target": 10000.0, "tpu_only": True},
    {"id": "serve-p99",
     "title": "serve p99 batch latency (ms)",
     "metric": r"serve::p99_ms",
     "field": "value", "op": "<", "target": 500.0, "tpu_only": True},
    # tail-latency attribution (request tracing, CST_TRACE_REQUESTS):
    # the advisory decomposition row behind serve-p99 — if more than
    # half of the p99 tail's wall is QUEUE WAIT, the service is
    # under-batched/under-pumped (an arrival/scheduling problem), not
    # device-bound, and kernel work won't move the p99.  TPU-gated like
    # the serve rows: the CPU smoke's closed-loop drive intentionally
    # saturates the queue, so its queue fraction is a property of the
    # drive, not the service.
    {"id": "serve-p99-queue-frac",
     "title": "serve p99 tail: queue-wait fraction (advisory)",
     "metric": r"latency::p99_queue_frac",
     "field": "value", "op": "<", "target": 0.5, "tpu_only": True},
    # incremental merkleization (ROADMAP stateless-client item): the
    # persisted-layer dirty-path re-hash must beat a full re-merkleize
    # by >= 5x at 1% dirty — measurable on the CPU smoke (the ratio is
    # shape-, not platform-, bound), so not TPU-gated.
    {"id": "merkle-incremental-speedup",
     "title": "incremental vs full re-merkleize @ 1% dirty",
     "metric": r"merkle_incr::update@frac0\.01",
     "field": "vs_baseline", "op": ">=", "target": 5.0, "tpu_only": False},
    # resilience (chaos rounds, CST_SERVE_CHAOS=1): after an active
    # fault plan stops firing, the service must return to steady state
    # within a bounded wall — and must have answered every checked
    # request correctly while degraded (the breaker/oracle-fallback
    # path).  Shape-, not platform-, bound: evaluated on the CPU chaos
    # smoke too.
    {"id": "chaos-recovered",
     "title": "chaos round: service returned to steady state",
     "metric": r"resilience::recovered",
     "field": "value", "op": ">=", "target": 1.0, "tpu_only": False},
    {"id": "chaos-recovery",
     "title": "chaos round: fault-stop → steady-state recovery (s)",
     "metric": r"resilience::recovery_latency_s",
     "field": "value", "op": "<", "target": 60.0, "tpu_only": False},
    {"id": "chaos-correctness",
     "title": "chaos round: wrong verification results",
     "metric": r"resilience::wrong_results",
     "field": "value", "op": "<", "target": 1.0, "tpu_only": False},
    # the live SLO watchdog (CST_SLO_RULES): a healthy serve round must
    # end with ZERO breaches (the slo::clean_round 0/1 record is only
    # mined from NON-chaos rounds — a chaos round breaches by design),
    # and a chaos round must walk the full arc: breach inside the fault
    # window, clear after recovery (resilience::slo_arc_ok).  Both are
    # shape-, not platform-, bound.
    {"id": "slo-clean-round",
     "title": "SLO watchdog: clean serve round (zero breaches)",
     "metric": r"slo::clean_round",
     "field": "value", "op": ">=", "target": 1.0, "tpu_only": False},
    {"id": "chaos-slo-arc",
     "title": "SLO watchdog: chaos breach→clear arc completed",
     "metric": r"resilience::slo_arc_ok",
     "field": "value", "op": ">=", "target": 1.0, "tpu_only": False},
    # mesh resilience (PR 9): a device_loss against the sharded verify
    # path must re-bucket onto the survivors within a bounded wall and
    # lose ZERO statements — CI-testable on the 8-host-device simulated
    # mesh (`make chaos-mesh-smoke`), so not TPU-gated.
    {"id": "mesh-recovered",
     "title": "mesh chaos: every shard loss produced a recovered verdict",
     "metric": r"mesh::recovered",
     "field": "value", "op": ">=", "target": 1.0, "tpu_only": False},
    {"id": "mesh-recovery",
     "title": "mesh chaos: shard-loss → recovered verdict (s)",
     "metric": r"mesh::recovery_latency_s",
     "field": "value", "op": "<", "target": 60.0, "tpu_only": False},
    # two rows, not one alternation: the threshold engine evaluates
    # ONE latest record per row, and the two metrics are emitted by the
    # same round with the same timestamp — an alternation would gate
    # whichever record happened to sort first and silently ignore the
    # other
    {"id": "mesh-lost-statements",
     "title": "mesh chaos: statements dropped by a shard loss",
     "metric": r"mesh::lost_statements",
     "field": "value", "op": "<", "target": 1.0, "tpu_only": False},
    {"id": "mesh-wrong-results",
     "title": "mesh chaos: statements answered wrong while degraded",
     "metric": r"mesh::wrong_results",
     "field": "value", "op": "<", "target": 1.0, "tpu_only": False},
    # mesh-sharded flagship scaling (the partition-registry epoch
    # pipeline): per-chip throughput at the full mesh must retain >=
    # 70% of the single-chip per-chip throughput at the same per-chip
    # shard size (weak scaling), and the 8M-validator rung must
    # complete without OOM.  TPU acceptance criteria — the CPU shard
    # smoke's simulated 8-host-device numbers read "no data" here.
    {"id": "scaling-efficiency",
     "title": "per-chip throughput retention at full mesh",
     "metric": r"scaling::efficiency",
     "field": "value", "op": ">=", "target": 0.70, "tpu_only": True},
    {"id": "flagship-8m",
     "title": "8M-validator flagship rung completes (no OOM)",
     "metric": r"scaling::flagship_8m_ok",
     "field": "value", "op": ">=", "target": 1.0, "tpu_only": True},
    # DAS / PeerDAS (the batched cell-proof workload): the device
    # route over a full 128-column sampling matrix must beat the
    # pure-Python fulu oracle >= 2x — the oracle pays a Lagrange
    # interpolation per cell, so the ratio is shape-bound and
    # CPU-evaluable (the smoke measures it at 128x8).  Absolute
    # throughput is a chip number: cells/s stays TPU-gated for the
    # next round.
    {"id": "das-speedup",
     "title": "DAS cell-proof batch vs pure-Python oracle",
     "metric": r"das::speedup",
     "field": "value", "op": ">=", "target": 2.0, "tpu_only": False},
    {"id": "das-throughput",
     "title": "DAS sampling-matrix throughput (cells/s)",
     "metric": r"das::cells_per_s",
     "field": "value", "op": ">=", "target": 20000.0, "tpu_only": True},
    # the producer side (PR 16): FK20 must beat the D_u partial route
    # >= 4x on full-matrix proof production, and the device erasure
    # decode + re-prove must beat the pure-Python oracle >= 2x.  Both
    # ratios are shape-bound (the D_u route pays ~64 large MSMs the
    # FK20 FFTs collapse; the oracle re-proves 128 cosets in python),
    # so both rows are CPU-evaluable.
    {"id": "das-producer-speedup",
     "title": "FK20 proof producer vs the D_u MSM route",
     "metric": r"das::producer_speedup",
     "field": "value", "op": ">=", "target": 4.0, "tpu_only": False},
    {"id": "das-recover-speedup",
     "title": "device erasure recovery vs pure-Python oracle",
     "metric": r"das::recover_speedup",
     "field": "value", "op": ">=", "target": 2.0, "tpu_only": False},
    # fork choice (the device LMD-GHOST proto-array store): batched
    # latest-message folding + pointer-jumping head selection must
    # beat the phase0 spec oracle's get_head >= 2x — the oracle pays a
    # python walk over every validator per child, so the ratio is
    # shape-bound and CPU-evaluable (the fc smoke measures it at the
    # tiny matrix).  Absolute head throughput is a chip number: the
    # heads/s row stays TPU-gated for the next round.
    {"id": "fc-speedup",
     "title": "fork-choice head vs phase0 spec oracle",
     "metric": r"forkchoice::speedup",
     "field": "value", "op": ">=", "target": 2.0, "tpu_only": False},
    {"id": "fc-head-throughput",
     "title": "fork-choice head polls per second",
     "metric": r"forkchoice::heads_per_s",
     "field": "value", "op": ">=", "target": 100.0, "tpu_only": True},
    # checkpoint restore (PR 9): snapshot + journal replay must beat
    # the full O(N) re-merkleize >= 5x at <= 1% journal depth (the
    # speedup rides the restore record's vs_baseline).  Shape-, not
    # platform-, bound — evaluated on the CPU chaos smoke.
    {"id": "checkpoint-restore",
     "title": "checkpoint restore+replay vs full rebuild",
     "metric": r"checkpoint::restore",
     "field": "vs_baseline", "op": ">=", "target": 5.0,
     "tpu_only": False},
    # device occupancy (PR 20): the depth-pipelined serve loop must
    # keep the chip busy >= 70% of the measured wall on the pod round —
    # the complementary fleet-side number to the per-kernel roofline
    # table.  A CPU smoke's busy_frac measures interpreter overhead,
    # not pipeline health, so the row is TPU-gated; the smoke instead
    # pins the ledger's accounting (busy + bubbles == wall).
    {"id": "serve-occupancy",
     "title": "serve device busy fraction under sustained load",
     "metric": r"pipeline::busy_frac",
     "field": "value", "op": ">=", "target": 0.70, "tpu_only": True},
)

FLAGSHIP = "mainnet_epoch_sweep_1m_validators_wall"


def _platform_group(rec: dict) -> str:
    """Records from the historical TPU driver rounds predate the
    `platform` field — group them with explicit TPU records."""
    p = rec.get("platform")
    if p is None or str(p).startswith("tpu"):
        return "tpu"
    return str(p)


def _order_key(rec: dict):
    """Rounds first (by number), then live emissions (by timestamp) —
    'latest' and 'previous' mean the same thing everywhere."""
    rnd = rec.get("round")
    return (0, rnd, 0.0) if isinstance(rnd, int) \
        else (1, 0, float(rec.get("ts") or 0.0))


def _by_metric(records) -> dict[str, list[dict]]:
    out: dict[str, list[dict]] = {}
    for rec in records:
        out.setdefault(rec["metric"], []).append(rec)
    for series in out.values():
        series.sort(key=_order_key)
    return out


def _where(rec: dict) -> str:
    if isinstance(rec.get("round"), int):
        return f"round {rec['round']}"
    if rec.get("ts"):
        return time.strftime("%Y-%m-%d %H:%M",
                             time.localtime(rec["ts"]))
    return rec.get("file", "?")


# --- threshold evaluation ----------------------------------------------------


def evaluate_thresholds(records) -> list[dict]:
    """One row per THRESHOLDS entry: the latest eligible measurement
    and its PASS / FAIL / 'no data' status."""
    rows = []
    for th in THRESHOLDS:
        pattern = re.compile(th["metric"] + r"\Z")
        candidates = [
            r for r in records
            if pattern.match(r["metric"])
            and isinstance(r.get(th["field"]), (int, float))
            and (not th["tpu_only"] or _platform_group(r) == "tpu")
        ]
        row = dict(th, status="no data", observed=None, where=None)
        if candidates:
            latest = max(candidates, key=_order_key)
            observed = float(latest[th["field"]])
            ok = observed >= th["target"] if th["op"] == ">=" \
                else observed < th["target"]
            row.update(status="PASS" if ok else "FAIL",
                       observed=observed, where=_where(latest),
                       metric_name=latest["metric"])
        rows.append(row)
    return rows


# --- round-over-round regression rule ----------------------------------------


def _comparable_oracles(prev: dict, cur: dict) -> bool:
    """vs_baseline numbers only compare across rounds when both divided
    by the same kind of oracle measurement.  The flagship rounds carry
    the oracle fingerprint (us/validator) mined from the round tail;
    fingerprints within 2x mean 'same oracle', a missing fingerprint on
    one side means the baseline was re-measured or the tail was
    truncated — fall back to raw wall."""
    fa = prev.get("baseline_us_per_validator")
    fb = cur.get("baseline_us_per_validator")
    if fa and fb:
        return 0.5 <= fa / fb <= 2.0
    return fa is None and fb is None


def find_regressions(records, max_regress_pct: float) -> list[dict]:
    """Latest-vs-previous comparison per TPU metric.  A drop in
    vs_baseline (comparable oracles) or a rise in wall seconds beyond
    `max_regress_pct` is a regression.  <= 0 disables the rule."""
    if max_regress_pct <= 0:
        return []
    regressions = []
    for metric, series in sorted(_by_metric(records).items()):
        series = [r for r in series
                  if _platform_group(r) == "tpu"
                  and r.get("unit") != "bool"
                  and isinstance(r.get("value"), (int, float))]
        if len(series) < 2:
            continue
        prev, cur = series[-2], series[-1]
        pv, cv = prev.get("vs_baseline"), cur.get("vs_baseline")
        if isinstance(pv, (int, float)) and isinstance(cv, (int, float)) \
                and pv > 0 and _comparable_oracles(prev, cur):
            change_pct = (cv - pv) / pv * 100.0
            if change_pct < -max_regress_pct:
                regressions.append({
                    "metric": metric,
                    "kind": "vs_baseline",
                    "prev": pv, "cur": cv,
                    "change_pct": round(change_pct, 1),
                    "prev_where": _where(prev), "cur_where": _where(cur),
                })
            continue
        if prev["value"] > 0:
            change_pct = (cur["value"] - prev["value"]) / prev["value"] * 100.0
            if change_pct > max_regress_pct:
                regressions.append({
                    "metric": metric,
                    "kind": "wall",
                    "prev": prev["value"], "cur": cur["value"],
                    "change_pct": round(change_pct, 1),
                    "prev_where": _where(prev), "cur_where": _where(cur),
                })
    return regressions


# --- _MSM_DEVICE_MIN recommendation ------------------------------------------


def msm_recommendation(records) -> dict:
    """Close the ROADMAP measurement loop: from the latest
    `g1_msm_breakeven_probe` detail rows, the smallest batch size where
    the device kernel beats the host oracle (host_over_device > 1), or
    'keep the current threshold' when no size wins."""
    probes = [r for r in records
              if r["metric"].startswith("g1_msm_breakeven_probe")
              and isinstance(r.get("detail"), dict)]
    if not probes:
        return {"status": "no data",
                "text": ("no `g1_msm_breakeven_probe` rows ingested yet — "
                         "run `bench_bls.py` with CST_TELEMETRY=1 on the "
                         "TPU to produce them")}
    # the routing decision is for the TPU: a real-chip probe always
    # outranks a CPU smoke probe, however recent the smoke run
    tpu_probes = [r for r in probes if _platform_group(r) == "tpu"]
    latest = max(tpu_probes or probes, key=_order_key)
    current = latest.get("msm_device_min", 16)
    sizes = []
    for n, d in latest["detail"].items():
        try:
            n = int(n)
        except (TypeError, ValueError):
            continue
        ratio = d.get("host_over_device") if isinstance(d, dict) else None
        if isinstance(ratio, (int, float)):
            sizes.append((n, float(ratio), d.get("routed")))
    sizes.sort()
    wins = [n for n, ratio, _ in sizes if ratio > 1.0]
    if wins:
        # assuming win/loss is monotone in n, the right threshold is the
        # smallest winning size — below current means small MSMs are
        # being left on the host that the device would win, ABOVE
        # current means sizes in [current, suggested) are routed to a
        # device that measurably loses there
        suggested = min(wins)
        if suggested < current:
            status = "lower"
            verdict = (f"suggest `_MSM_DEVICE_MIN = {suggested}` — "
                       f"device beats host from n={suggested} "
                       f"(currently {current})")
        elif suggested == current:
            status = "keep"
            verdict = (f"keep {current} — device wins from exactly "
                       f"n={current}, the threshold is right")
        else:
            status = "raise"
            verdict = (f"suggest `_MSM_DEVICE_MIN = {suggested}` — "
                       f"device only wins from n={suggested}, but "
                       f"n>={current} already routes to the device "
                       f"where the host measures faster")
    else:
        suggested = None
        verdict = (f"keep {current} — no device win observed at any "
                   f"probed size")
        status = "keep"
    if _platform_group(latest) != "tpu":
        verdict += (" (CPU probe only — the routing decision needs a "
                    "TPU round to confirm)")
    return {"status": status, "suggested": suggested, "current": current,
            "where": _where(latest), "platform": _platform_group(latest),
            "sizes": [{"n": n, "host_over_device": r, "routed": routed}
                      for n, r, routed in sizes],
            "text": verdict}


# --- kernel utilization (cost model) -----------------------------------------


_ATT_METRIC_RE = re.compile(r"attestation_batch_\d+x\d+_verify_wall\Z")


def collect_utilization(records) -> dict:
    """The cost-model read side: latest joined roofline record per
    kernel (`costmodel`-source records; TPU rounds outrank CPU smoke,
    same precedence as the MSM probe), latest per-device memory
    high-water marks, and the attestation compile-vs-execute verdict
    rendered from the latest attestation round's measured split.
    Malformed costmodel fields are skipped with a counted warning
    (`warnings` key), never a crash — CST_COSTMODEL rounds must degrade
    like every other benchwatch input."""
    warnings: list[str] = []
    by_kernel: dict[str, list[dict]] = {}
    watermarks: dict[str, list[dict]] = {}
    for r in records:
        if r.get("source") != "costmodel":
            continue
        metric = r["metric"]
        if metric.startswith("costmodel::"):
            cm = r.get("costmodel")
            if not isinstance(cm, dict) or not isinstance(
                    cm.get("flops"), (int, float)):
                warnings.append(
                    f"costmodel record {metric!r} has a malformed "
                    f"cost block — skipped")
                continue
            by_kernel.setdefault(metric[len("costmodel::"):],
                                 []).append(r)
        elif metric.startswith("device_mem_high_water::"):
            watermarks.setdefault(metric[len("device_mem_high_water::"):],
                                  []).append(r)

    def latest_preferring_tpu(series):
        series.sort(key=_order_key)
        tpu = [r for r in series if _platform_group(r) == "tpu"]
        return (tpu or series)[-1]

    kernels = {}
    for kernel, series in sorted(by_kernel.items()):
        rec = latest_preferring_tpu(series)
        kernels[kernel] = dict(rec["costmodel"],
                               where=_where(rec),
                               platform=_platform_group(rec))
    wm_rows = {}
    for dev, series in sorted(watermarks.items()):
        rec = latest_preferring_tpu(series)
        wm_rows[dev] = {"high_water_bytes": rec.get("value"),
                        "samples": rec.get("samples"),
                        "where": _where(rec)}

    # compile-vs-execute verdict for the attestation path (the ROADMAP's
    # "is the 81s compile- or execute-bound?" question), from the latest
    # attestation record that embeds the measured split — TPU rounds
    # outrank the CI CPU smoke here too, else the smoke round appended
    # before every report would always override the real chip's answer
    verdict = None
    att = [r for r in records
           if _ATT_METRIC_RE.match(r.get("metric", ""))
           and isinstance(r.get("telemetry"), dict)
           and isinstance(r["telemetry"].get("compile_s"), (int, float))
           and isinstance(r["telemetry"].get("run_s"), (int, float))]
    if att:
        latest = latest_preferring_tpu(att)
        tel = latest["telemetry"]
        c, x = float(tel["compile_s"]), float(tel["run_s"])
        if x > 0 and c > 0:
            ratio = c / x
            kind = "compile-bound" if ratio >= 2.0 else (
                "execute-bound" if ratio <= 0.5 else "balanced")
            verdict = {
                "kind": kind, "compile_s": c, "run_s": x,
                "ratio": round(ratio, 1), "where": _where(latest),
                "platform": _platform_group(latest),
                "text": (f"{kind}: trace+XLA-compile {c:g}s vs "
                         f"steady-state execute {x:g}s per round "
                         f"({ratio:.1f}x) at {_where(latest)}"),
            }
    return {"kernels": kernels, "watermarks": wm_rows,
            "verdict": verdict, "warnings": warnings}


# --- markdown rendering ------------------------------------------------------


def _fmt(v, nd=4) -> str:
    if v is None:
        return "—"
    if isinstance(v, float):
        return f"{round(v, nd):g}"
    return str(v)


def _cell(rec: dict | None) -> str:
    if rec is None:
        return "—"
    if rec.get("value") is None:
        return "fail" if rec.get("error") else "—"
    s = f"{_fmt(rec['value'])}{'' if rec.get('unit') == 'bool' else ' s'}"
    if isinstance(rec.get("vs_baseline"), (int, float)):
        s += f" ({_fmt(rec['vs_baseline'], 1)}x)"
    return s


def render_trend_tables(records) -> list[str]:
    lines: list[str] = []
    round_recs = [r for r in records
                  if r["source"] in ("bench_round", "multichip_round")]
    rounds = sorted({r["round"] for r in round_recs
                     if isinstance(r.get("round"), int)})
    if rounds:
        lines.append("## Round trends\n")
        lines.append("Cells are `wall (speedup vs the pure-Python "
                     "oracle)`; Δ compares the last two measured "
                     "rounds.\n")
        header = "| metric | " + " | ".join(f"r{n:02d}" for n in rounds) \
            + " | Δ last |"
        lines.append(header)
        lines.append("|---" * (len(rounds) + 2) + "|")
        for metric, series in sorted(_by_metric(round_recs).items()):
            per_round = {r["round"]: r for r in series
                         if isinstance(r.get("round"), int)}
            cells = [_cell(per_round.get(n)) for n in rounds]
            measured = [per_round[n] for n in rounds
                        if n in per_round
                        and isinstance(per_round[n].get("value"),
                                       (int, float))]
            delta = "—"
            if len(measured) >= 2 and series[0].get("unit") != "bool":
                prev, cur = measured[-2], measured[-1]
                pv, cv = prev.get("vs_baseline"), cur.get("vs_baseline")
                if isinstance(pv, (int, float)) \
                        and isinstance(cv, (int, float)) and pv > 0 \
                        and _comparable_oracles(prev, cur):
                    delta = f"{(cv - pv) / pv * 100.0:+.1f}% speedup"
                elif prev["value"] > 0:
                    pct = ((cur["value"] - prev["value"])
                           / prev["value"] * 100.0)
                    delta = f"{pct:+.1f}% wall"
            lines.append(f"| `{metric}` | " + " | ".join(cells)
                         + f" | {delta} |")
        lines.append("")

    emits = [r for r in records if r["source"] == "bench_emit"]
    if emits:
        lines.append("## Live emissions (CST_BENCHWATCH_HISTORY)\n")
        lines.append("| metric | platform | latest | when |")
        lines.append("|---|---|---|---|")
        for metric, series in sorted(_by_metric(emits).items()):
            latest = series[-1]
            lines.append(f"| `{metric}` | {latest.get('platform', '—')} "
                         f"| {_cell(latest)} | {_where(latest)} |")
        lines.append("")

    oracles = [r for r in records if r["source"] == "baseline"]
    if oracles:
        lines.append("## Oracle baselines (pure-Python costs the "
                     "speedups divide by)\n")
        lines.append("| metric | value | measured |")
        lines.append("|---|---|---|")
        for rec in sorted(oracles, key=lambda r: r["metric"]):
            lines.append(f"| `{rec['metric']}` | {_fmt(rec['value'])} "
                         f"{rec['unit']} | {rec.get('measured_at', '—')} |")
        lines.append("")
    return lines


def render_thresholds(rows, strict: bool) -> list[str]:
    lines = ["## ROADMAP thresholds\n"]
    mode = ("**strict** — any FAIL fails the run" if strict
            else "advisory — only regressions gate the exit code "
                 "(promote with CST_BENCHWATCH_STRICT=1)")
    lines.append(f"Gate mode: {mode}.\n")
    lines.append("| threshold | target | observed | where | status |")
    lines.append("|---|---|---|---|---|")
    for row in rows:
        target = (f"{row['field']} {row['op']} {_fmt(row['target'], 1)}")
        observed = "—" if row["observed"] is None \
            else _fmt(row["observed"], 2)
        mark = {"PASS": "✅ PASS", "FAIL": "❌ FAIL",
                "no data": "— no data"}[row["status"]]
        lines.append(f"| {row['title']} | {target} | {observed} "
                     f"| {row['where'] or '—'} | {mark} |")
    lines.append("")
    return lines


def render_regressions(regressions, max_regress_pct) -> list[str]:
    lines = ["## Round-over-round regressions\n"]
    if max_regress_pct <= 0:
        lines.append("Regression rule disabled "
                     "(CST_BENCHWATCH_MAX_REGRESS_PCT <= 0).\n")
        return lines
    if not regressions:
        lines.append(f"None — no TPU metric regressed more than "
                     f"{_fmt(max_regress_pct, 1)}% against its previous "
                     f"round.\n")
        return lines
    lines.append("| metric | compared | previous | current | change |")
    lines.append("|---|---|---|---|---|")
    for r in regressions:
        lines.append(
            f"| `{r['metric']}` | {r['kind']} "
            f"({r['prev_where']} → {r['cur_where']}) "
            f"| {_fmt(r['prev'], 2)} | {_fmt(r['cur'], 2)} "
            f"| {r['change_pct']:+.1f}% |")
    lines.append("")
    return lines


def _si(v, unit="") -> str:
    """1234567 -> '1.23 M'; keeps the roofline table readable."""
    if v is None:
        return "—"
    v = float(v)
    for thresh, suffix in ((1e12, "T"), (1e9, "G"), (1e6, "M"),
                           (1e3, "k")):
        if abs(v) >= thresh:
            return f"{v / thresh:.2f} {suffix}{unit}"
    return f"{v:g} {unit}".rstrip()


def render_utilization(util: dict, msm: dict) -> list[str]:
    lines = ["## Utilization (kernel cost model)\n"]
    kernels = util["kernels"]
    if not kernels:
        lines.append("No cost-model data — run a bench round with "
                     "`CST_TELEMETRY=1 CST_COSTMODEL=1` to capture "
                     "per-kernel XLA cost/memory analysis and re-run "
                     "the report.\n")
        return lines
    advisory = any("advisory" in str(k.get("peak_source", ""))
                   for k in kernels.values())
    lines.append("Per-kernel roofline: XLA `cost_analysis()` flop/byte "
                 "budgets joined with the measured steady-state wall; "
                 "achieved-vs-peak against the per-backend peak "
                 "registry (`BASELINE.json` `\"peaks\"`)."
                 + ("  CPU peaks are ADVISORY — utilization ranks "
                    "kernels against each other, not the hardware."
                    if advisory else "") + "\n")
    lines.append("| kernel | flops | bytes | AI (flop/B) | "
                 "FLOP/s (% peak) | B/s (% peak) | run (mean) | "
                 "bound | where |")
    lines.append("|---|---|---|---|---|---|---|---|---|")
    for name, k in sorted(kernels.items()):
        fl = _si(k.get("achieved_flops_per_s"))
        bw = _si(k.get("achieved_bytes_per_s"), "B")
        uf = k.get("util_flops_pct")
        ub = k.get("util_bw_pct")
        run = k.get("run_s_mean")
        lines.append(
            f"| `{name}` | {_si(k.get('flops'))} "
            f"| {_si(k.get('bytes_accessed'), 'B')} "
            f"| {_fmt(k.get('arithmetic_intensity'), 2)} "
            f"| {fl}{'' if uf is None else f' ({uf:g}%)'} "
            f"| {bw}{'' if ub is None else f' ({ub:g}%)'} "
            f"| {'—' if run is None else f'{run:g} s'} "
            f"| **{k.get('bound', 'unknown')}** "
            f"| {k.get('where', '—')} |")
    lines.append("")

    verdict = util["verdict"]
    lines.append("### Attestation compile-vs-execute\n")
    if verdict:
        lines.append(f"**{verdict['text']}** (platform "
                     f"{verdict['platform']}).\n")
    else:
        lines.append("No attestation round with an embedded "
                     "compile_s/run_s split ingested yet.\n")

    launch_msms = [n for n, k in sorted(kernels.items())
                   if ("msm" in n.lower() and k.get("bound") == "launch")]
    lines.append("### `_MSM_DEVICE_MIN` cross-check\n")
    if launch_msms:
        names = ", ".join(f"`{n}`" for n in launch_msms)
        lines.append(
            f"{names}: launch-overhead-bound at the probed shape — the "
            f"kernel's roofline legs explain almost none of its wall, "
            f"so small-n routing is a dispatch-overhead question, not "
            f"a throughput one.  Read together with the break-even "
            f"probe above (status: {msm.get('status', 'no data')}).\n")
    elif any("msm" in n.lower() for n in kernels):
        lines.append("No MSM kernel classifies launch-bound at the "
                     "captured shapes — the break-even probe's "
                     "host/device walls are the deciding signal.\n")
    else:
        lines.append("No MSM kernel cost records captured yet.\n")

    if util["watermarks"]:
        lines.append("### Device-memory watermarks\n")
        lines.append("| device | high water | samples | where |")
        lines.append("|---|---|---|---|")
        for dev, wm in sorted(util["watermarks"].items()):
            lines.append(f"| `{dev}` | {_si(wm['high_water_bytes'], 'B')} "
                         f"| {wm.get('samples') or '—'} "
                         f"| {wm.get('where', '—')} |")
        lines.append("")
    return lines


def render_resilience(records) -> list[str]:
    """The chaos-round read side: latest `resilience::*` records (one
    row per metric) plus the latest round's breaker/heal summary from
    the compact block riding the recovery-latency record."""
    lines = ["## Resilience (chaos rounds)\n"]
    recs = [r for r in records
            if r.get("source") in ("resilience", "mesh", "checkpoint")]
    if not recs:
        lines.append("No resilience records — run a chaos round "
                     "(`CST_SERVE_CHAOS=1 make serve` / "
                     "`make chaos-smoke`, mesh arc: "
                     "`make chaos-mesh-smoke`) to exercise fault "
                     "injection, breaker/fallback degraded mode, "
                     "shard-loss recovery, checkpoint restore, and "
                     "recovery-to-steady.\n")
        return lines
    lines.append("| metric | latest | where |")
    lines.append("|---|---|---|")
    latest_by_metric = {}
    for metric, series in sorted(_by_metric(recs).items()):
        latest = series[-1]
        latest_by_metric[metric] = latest
        val = "—" if latest.get("value") is None else \
            f"{_fmt(latest['value'])} {latest.get('unit', '')}".rstrip()
        lines.append(f"| `{metric}` | {val} | {_where(latest)} |")
    lines.append("")
    rec = latest_by_metric.get("resilience::recovery_latency_s")
    compact = rec.get("resilience") if rec else None
    if isinstance(compact, dict):
        recovered = compact.get("recovered")
        sites = ", ".join(f"{k}: {v}" for k, v in sorted(
            (compact.get("injected_sites") or {}).items())) or "—"
        lines.append(
            f"Latest chaos round: {compact.get('faults_injected', '?')} "
            f"fault(s) injected ({sites}), "
            f"{compact.get('wrong_results', '?')} wrong result(s) over "
            f"{compact.get('checked_results', '?')} checked, "
            f"{compact.get('retries', 0)} retried / "
            f"{compact.get('fallbacks', 0)} oracle-fallback / "
            f"{compact.get('shed', 0)} shed; breaker trips: "
            f"{compact.get('breaker_trips', 0)}, final states: "
            f"{compact.get('breaker_states') or {}}; "
            f"{'recovered' if recovered else 'DID NOT RECOVER'}.\n")
        fv = compact.get("fault_victims")
        if isinstance(fv, dict):
            lines.append(
                f"Blast radius (request tracing): {fv.get('count', 0)} "
                f"victim request(s) — outcomes "
                f"{fv.get('outcomes') or {}}; "
                f"{fv.get('clean_ok', 0)} settled clean "
                f"(must be 0 — a fault-hit handle recovers as "
                f"retry/fallback or poisons, never silently).\n")
    mrec = latest_by_metric.get("mesh::recovery_latency_s")
    mesh = mrec.get("mesh") if mrec else None
    if isinstance(mesh, dict):
        lines.append(
            f"Latest mesh segment: {mesh.get('devices', '?')} devices, "
            f"{mesh.get('device_lost_events', 0)} lost "
            f"(max {mesh.get('max_degraded_lanes', 0)} degraded "
            f"lane(s)), {mesh.get('redispatches', 0)} re-bucketed "
            f"re-dispatch(es), {mesh.get('readmissions', 0)} "
            f"re-admission(s); {mesh.get('lost_statements', 0)} lost / "
            f"{mesh.get('wrong_results', 0)} wrong of "
            f"{mesh.get('checked_statements', '?')} checked "
            f"statements.\n")
    crec = latest_by_metric.get("checkpoint::restore")
    cp = crec.get("checkpoint") if crec else None
    if isinstance(cp, dict):
        sp = crec.get("vs_baseline")
        lines.append(
            f"Latest checkpoint restore: {cp.get('n_chunks', '?')} "
            f"chunks, {cp.get('journal_entries', 0)} journal "
            f"entr(ies) at {cp.get('journal_frac', '?')} depth, "
            f"restore {_fmt(crec.get('value'), 4)} s vs rebuild "
            f"{_fmt(cp.get('rebuild_s'), 4)} s "
            f"({_fmt(sp, 1)}x), parity "
            f"{'OK' if cp.get('parity') else 'FAILED'}.\n")
    return lines


def render_slo(records) -> list[str]:
    """The live-watchdog read side: latest `slo::*` records (one row
    per metric), the latest round's per-rule summary from the compact
    block riding the `slo::breaches` record, and the latest chaos
    round's breach→clear arc verdict."""
    lines = ["## SLO (live watchdog)\n"]
    recs = [r for r in records if r.get("source") == "slo"]
    arcs = [r for r in records
            if r.get("metric") == "resilience::slo_arc_ok"]
    if not recs and not arcs:
        lines.append("No SLO records — arm the watchdog on a serve "
                     "round (`CST_SLO_RULES=... CST_METRICS_PORT=9464 "
                     "make serve` / `make serve-smoke`) to evaluate "
                     "rules against the live fleet and produce "
                     "`slo::*` records.\n")
        return lines
    if recs:
        lines.append("| metric | latest | where |")
        lines.append("|---|---|---|")
        latest_by_metric = {}
        for metric, series in sorted(_by_metric(recs).items()):
            latest = series[-1]
            latest_by_metric[metric] = latest
            val = "—" if latest.get("value") is None else \
                f"{_fmt(latest['value'])} {latest.get('unit', '')}".rstrip()
            lines.append(f"| `{metric}` | {val} | {_where(latest)} |")
        lines.append("")
        rec = latest_by_metric.get("slo::breaches")
        compact = rec.get("slo") if rec else None
        if isinstance(compact, dict):
            now = ", ".join(compact.get("breaching_now") or []) or "none"
            lines.append(
                f"Latest armed round: {compact.get('ticks', '?')} "
                f"tick(s), {compact.get('breaches', '?')} breach(es), "
                f"currently breaching: {now}"
                + (f", {compact['events_dropped']} event(s) dropped at "
                   f"the cap" if compact.get("events_dropped") else "")
                + (f"; profiler grabs: "
                   f"{len(compact['profiles'])}"
                   if compact.get("profiles") else "")
                + ".\n")
            rules = [r for r in compact.get("rules", [])
                     if isinstance(r, dict)]
            if rules:
                lines.append("| rule | metric | breaches | clears | "
                             "breaching | worst margin | last value |")
                lines.append("|---|---|---|---|---|---|---|")
                for r in rules:
                    lines.append(
                        f"| `{r.get('name', '—')}` "
                        f"| `{r.get('metric', '—')}` "
                        f"| {r.get('breaches', '—')} "
                        f"| {r.get('clears', '—')} "
                        f"| {'yes' if r.get('breaching') else 'no'} "
                        f"| {_fmt(r.get('worst_margin'), 3)} "
                        f"| {_fmt(r.get('last_value'), 3)} |")
                lines.append("")
    if arcs:
        latest = max(arcs, key=_order_key)
        arc = latest.get("slo_arc") or {}
        lines.append(
            ("Latest chaos arc: breached inside the fault window and "
             "cleared after recovery — the watchdog saw the incident "
             "both ways"
             if latest.get("value") else
             f"Latest chaos arc: INCOMPLETE — breached in window: "
             f"{arc.get('breached_in_fault_window')}, cleared after "
             f"recovery: {arc.get('cleared_after_recovery')}")
            + f" (rule `{arc.get('rule', '?')}`, {_where(latest)}).\n")
    return lines


def render_tail_latency(records) -> list[str]:
    """The request-tracing read side: latest per-kind
    `latency::p99_ms@<kind>` records (the compact attribution block
    rides each — p50/p90/p99 + the p99 tail's component decomposition),
    the overall p99 queue-wait fraction, and the worst-N exemplar
    traces riding the `latency::p99_queue_frac` record."""
    lines = ["## Tail latency (request tracing)\n"]
    recs = [r for r in records if r.get("source") == "latency"]
    if not recs:
        lines.append("No latency records — run a serve round with "
                     "`CST_TRACE_REQUESTS=1` (`make serve` / "
                     "`make serve-smoke`) to mint per-request contexts "
                     "and produce `latency::*` attribution records.\n")
        return lines
    by_kind: dict[str, dict] = {}
    for r in sorted((r for r in recs
                     if r["metric"].startswith("latency::p99_ms@")
                     and isinstance(r.get("latency"), dict)),
                    key=_order_key):
        by_kind[r["metric"][len("latency::p99_ms@"):]] = r
    if by_kind:
        lines.append("Per-kind percentiles are per-REQUEST "
                     "(submit→complete, queue wait and resilience "
                     "detours included); the component columns "
                     "decompose the p99 tail's wall.\n")
        lines.append("| kind | n | p50 | p90 | p99 | queue | batch | "
                     "device | settle | detour | platform | where |")
        lines.append("|---|---|---|---|---|---|---|---|---|---|---|---|")
        for kind, r in sorted(by_kind.items()):
            blk = r["latency"]
            comp = blk.get("p99_components_ms") or {}
            lines.append(
                f"| `{kind}` | {blk.get('count', '—')} "
                f"| {_fmt(blk.get('p50_ms'), 2)} "
                f"| {_fmt(blk.get('p90_ms'), 2)} "
                f"| {_fmt(r.get('value'), 2)} ms "
                f"| {_fmt(comp.get('queue_wait'), 2)} "
                f"| {_fmt(comp.get('batch_form'), 2)} "
                f"| {_fmt(comp.get('device_wall'), 2)} "
                f"| {_fmt(comp.get('settle'), 2)} "
                f"| {_fmt(comp.get('detour'), 2)} "
                f"| {_platform_group(r)} | {_where(r)} |")
        lines.append("")
    frac_recs = [r for r in recs
                 if r["metric"] == "latency::p99_queue_frac"]
    if frac_recs:
        latest = max(frac_recs, key=_order_key)
        frac = latest.get("value")
        lines.append(
            f"Overall p99 tail queue-wait fraction: "
            f"{'—' if frac is None else f'{float(frac) * 100:.0f}%'} "
            f"({_where(latest)}, platform {_platform_group(latest)}) — "
            f"above 50% the tail is an arrival/scheduling problem, not "
            f"a device one (the `serve-p99-queue-frac` advisory row).\n")
        worst = (latest.get("latency") or {}).get("worst") or []
        if worst:
            lines.append("Worst exemplar traces:\n")
            lines.append("| trace | kind | outcome | attempts | e2e | "
                         "queue | device | detour |")
            lines.append("|---|---|---|---|---|---|---|---|")
            for ex in worst:
                comp = ex.get("components_ms") or {}
                lines.append(
                    f"| {ex.get('trace_id', '—')} "
                    f"| `{ex.get('kind', '—')}` "
                    f"| {ex.get('outcome', '—')} "
                    f"| {ex.get('attempts', '—')} "
                    f"| {_fmt(ex.get('e2e_ms'), 2)} ms "
                    f"| {_fmt(comp.get('queue_wait'), 2)} "
                    f"| {_fmt(comp.get('device_wall'), 2)} "
                    f"| {_fmt(comp.get('detour'), 2)} |")
            lines.append("")
    return lines


def render_occupancy(records) -> list[str]:
    """The device-occupancy read side: latest `pipeline::*` records
    (busy fraction, per-cause bubble seconds, overlap score) plus the
    bubble-attribution and per-device summaries from the compact block
    riding the `pipeline::busy_frac` record."""
    lines = ["## Pipeline occupancy\n"]
    recs = [r for r in records if r.get("source") == "pipeline"]
    if not recs:
        lines.append("No occupancy records — arm the device-occupancy "
                     "ledger on a serve round (`CST_OCCUPANCY=1 make "
                     "serve` / `make serve-smoke`) to measure device "
                     "busy fraction and pipeline bubbles and produce "
                     "`pipeline::*` records.\n")
        return lines
    lines.append("| metric | latest | where |")
    lines.append("|---|---|---|")
    latest_by_metric = {}
    for metric, series in sorted(_by_metric(recs).items()):
        latest = series[-1]
        latest_by_metric[metric] = latest
        val = "—" if latest.get("value") is None else \
            f"{_fmt(latest['value'])} {latest.get('unit', '')}".rstrip()
        lines.append(f"| `{metric}` | {val} | {_where(latest)} |")
    lines.append("")
    rec = latest_by_metric.get("pipeline::busy_frac")
    compact = rec.get("occupancy") if rec else None
    if isinstance(compact, dict):
        frac = compact.get("busy_frac")
        lines.append(
            f"Latest armed round: device busy "
            f"{'—' if frac is None else f'{float(frac) * 100:.1f}%'} "
            f"of a {_fmt(compact.get('wall_s'), 2)} s wall at pipeline "
            f"depth {compact.get('depth', '—')}"
            + (f", {compact['events_dropped']} interval(s) dropped at "
               f"the cap" if compact.get("events_dropped") else "")
            + ".\n")
        bub = compact.get("bubbles_s")
        if isinstance(bub, dict) and bub:
            lines.append("Idle-gap attribution (busy + bubbles sum to "
                         "the wall — see the bubble-cause definitions "
                         "in the README):\n")
            lines.append("| bubble cause | seconds |")
            lines.append("|---|---|")
            for cause, v in sorted(bub.items()):
                lines.append(f"| `{cause}` | {_fmt(v, 3)} |")
            lines.append("")
        devs = compact.get("devices")
        if isinstance(devs, dict) and len(devs) > 1:
            lines.append("| device | busy | spans |")
            lines.append("|---|---|---|")
            for dev, blk in sorted(devs.items()):
                if not isinstance(blk, dict):
                    continue
                bf = blk.get("busy_frac")
                lines.append(
                    f"| `{dev}` "
                    f"| {'—' if bf is None else f'{float(bf) * 100:.1f}%'} "
                    f"| {blk.get('spans', '—')} |")
            lines.append("")
    score_rec = latest_by_metric.get("pipeline::overlap_score")
    if score_rec is not None and score_rec.get("value") is not None:
        ov = score_rec.get("overlap") or {}
        lines.append(
            f"Pipeline overlap score: "
            f"{float(score_rec['value']) * 100:.0f}% of host prep hid "
            f"under device busy ({_fmt(ov.get('hidden_s'), 3)} s of "
            f"{_fmt(ov.get('prep_s'), 3)} s, {_where(score_rec)}) — "
            f"low scores mean the depth knob is not covering host "
            f"prep, the `host_prep` bubble's complement.\n")
    return lines


def render_scaling(records) -> list[str]:
    """The mesh-sharded flagship read side: per-rung × per-n_devices
    trend table from the latest `scaling::flagship@<n>` records (the
    compact rung block rides each record), plus the latest efficiency
    summary."""
    lines = ["## Scaling (mesh-sharded flagship)\n"]
    recs = [r for r in records if r.get("source") == "scaling"]
    if not recs:
        lines.append("No scaling records — run the sharded flagship "
                     "rungs (`python bench.py --worker scaling` on the "
                     "mesh, or `make shard-smoke` for the simulated "
                     "8-host-device contract check) to produce "
                     "`scaling::*` records.\n")
        return lines
    # latest rung record per (n_validators, n_devices) — the
    # per-n_devices trend: the same rung re-measured on a wider mesh
    # lands its own row instead of overwriting the narrow one
    rows: dict[tuple[int, int], dict] = {}
    for r in sorted((r for r in recs
                     if r["metric"].startswith("scaling::flagship@")
                     and isinstance(r.get("scaling"), dict)),
                    key=_order_key):
        blk = r["scaling"]
        n = blk.get("n_validators")
        d = blk.get("n_devices")
        if isinstance(n, int) and isinstance(d, int):
            rows[(n, d)] = r
    if rows:
        lines.append("| validators | devices | step wall | "
                     "per-chip vps | single-chip vps | efficiency | "
                     "platform | where |")
        lines.append("|---|---|---|---|---|---|---|---|")
        for (n, d), r in sorted(rows.items()):
            blk = r["scaling"]
            eff = blk.get("efficiency")
            lines.append(
                f"| {n} | {d} | {_fmt(r.get('value'), 4)} s "
                f"| {_si(blk.get('per_chip_vps'))} "
                f"| {_si(blk.get('single_chip_vps'))} "
                f"| {'—' if eff is None else f'{eff * 100:.0f}%'} "
                f"| {_platform_group(r)} | {_where(r)} |")
        lines.append("")
    eff_recs = [r for r in recs if r["metric"] == "scaling::efficiency"]
    if eff_recs:
        latest = max(eff_recs, key=_order_key)
        blk = latest.get("scaling") or {}
        lines.append(
            f"Latest full-mesh efficiency: "
            f"{float(latest['value']) * 100:.0f}% per-chip throughput "
            f"retention at {blk.get('n_validators', '?')} validators "
            f"over {blk.get('n_devices', '?')} device(s) "
            f"({_where(latest)}, platform "
            f"{_platform_group(latest)}).\n")
    ok8 = [r for r in recs if r["metric"] == "scaling::flagship_8m_ok"]
    if ok8:
        latest = max(ok8, key=_order_key)
        lines.append(
            ("8M-validator rung: completed.\n"
             if latest.get("value") else
             "8M-validator rung: ATTEMPTED AND FAILED (OOM or crash — "
             "see the round log).\n"))
    return lines


def render_das(records) -> list[str]:
    """The PeerDAS read side: per-matrix verification walls from the
    latest `das::verify_wall@<cols>x<blobs>` records (the compact
    block rides each), plus the latest speedup/throughput summary."""
    lines = ["## DAS (PeerDAS cell-proof sampling)\n"]
    recs = [r for r in records if r.get("source") == "das"]
    if not recs:
        lines.append("No das records — run the sampling-matrix sweep "
                     "(`python bench.py --worker das` on the chip, or "
                     "`make das-smoke` for the CPU contract check) to "
                     "produce `das::*` records.\n")
        return lines
    rows: dict[tuple[int, int], dict] = {}
    for r in sorted((r for r in recs
                     if r["metric"].startswith("das::verify_wall@")
                     and isinstance(r.get("das"), dict)),
                    key=_order_key):
        m = (r["das"].get("matrix") or {})
        c, b = m.get("columns"), m.get("blobs")
        if isinstance(c, int) and isinstance(b, int):
            rows[(c, b)] = r
    if rows:
        lines.append("| matrix | cells | verify wall | vs oracle | "
                     "rung | platform | where |")
        lines.append("|---|---|---|---|---|---|---|")
        for (c, b), r in sorted(rows.items()):
            blk = r["das"]
            cells = (blk.get("matrix") or {}).get("cells")
            vs = r.get("vs_baseline")
            lines.append(
                f"| {c}x{b} | {cells} | {_fmt(r.get('value'), 4)} s "
                f"| {'—' if vs is None else f'{_fmt(vs, 1)}x'} "
                f"| {blk.get('rung', '—')} | {_platform_group(r)} "
                f"| {_where(r)} |")
        lines.append("")
    sp = [r for r in recs if r["metric"] == "das::speedup"]
    if sp:
        latest = max(sp, key=_order_key)
        lines.append(
            f"Latest speedup over the pure-Python oracle: "
            f"{_fmt(latest['value'], 1)}x ({_where(latest)}, platform "
            f"{_platform_group(latest)}).\n")
    cps = [r for r in recs if r["metric"] == "das::cells_per_s"]
    if cps:
        latest = max(cps, key=_order_key)
        lines.append(
            f"Latest throughput: {_si(latest['value'])} cells/s "
            f"({_where(latest)}, platform "
            f"{_platform_group(latest)}).\n")
    # the producer side: FK20 full-matrix proof production + erasure
    # recovery (the super-node path)
    pw = [r for r in recs if r["metric"] == "das::produce_wall"]
    if pw:
        latest = max(pw, key=_order_key)
        blk = latest.get("das_producer") or {}
        vs = latest.get("vs_baseline")
        lines.append(
            f"FK20 producer: {_fmt(latest.get('value'), 2)} s per blob "
            f"(all 128 proofs"
            + (f", {_fmt(vs, 1)}x vs the D_u MSM route" if vs is not None
               else "")
            + (", byte-parity OK" if blk.get("parity") else "")
            + f") — {_where(latest)}, platform "
            f"{_platform_group(latest)}.\n")
    rw = [r for r in recs if r["metric"] == "das::recover_wall"]
    if rw:
        latest = max(rw, key=_order_key)
        blk = latest.get("das_recover") or {}
        vs = latest.get("vs_baseline")
        lines.append(
            f"Erasure recovery: {_fmt(latest.get('value'), 2)} s "
            f"({blk.get('cells_in', '—')} surviving cells -> full "
            f"reconstruction + re-prove"
            + (f", {_fmt(vs, 1)}x vs the pure-Python oracle"
               if vs is not None else "")
            + (", roundtrip OK" if blk.get("roundtrip") else "")
            + f") — {_where(latest)}, platform "
            f"{_platform_group(latest)}.\n")
    pps = [r for r in recs if r["metric"] == "das::proofs_per_s"]
    if pps:
        latest = max(pps, key=_order_key)
        lines.append(
            f"Latest producer throughput: {_si(latest['value'])} "
            f"proofs/s ({_where(latest)}, platform "
            f"{_platform_group(latest)}).\n")
    return lines


def render_forkchoice(records) -> list[str]:
    """The fork-choice read side: per-shape head walls from the latest
    `forkchoice::head_wall@<blocks>x<validators>` records (the compact
    block rides each), plus the latest speedup/throughput summary."""
    lines = ["## Fork choice (device LMD-GHOST)\n"]
    recs = [r for r in records if r.get("source") == "forkchoice"]
    if not recs:
        lines.append("No forkchoice records — run the tree sweep "
                     "(`python bench.py --worker forkchoice` on the "
                     "chip, or `make fc-smoke` for the CPU contract "
                     "check) to produce `forkchoice::*` records.\n")
        return lines
    rows: dict[tuple[int, int], dict] = {}
    for r in sorted((r for r in recs
                     if r["metric"].startswith("forkchoice::head_wall@")
                     and isinstance(r.get("forkchoice"), dict)),
                    key=_order_key):
        t = (r["forkchoice"].get("tree") or {})
        b, v = t.get("blocks"), t.get("validators")
        if isinstance(b, int) and isinstance(v, int):
            rows[(b, v)] = r
    if rows:
        lines.append("| tree | head wall | apply wall | vs oracle | "
                     "rungs | platform | where |")
        lines.append("|---|---|---|---|---|---|---|")
        for (b, v), r in sorted(rows.items()):
            blk = r["forkchoice"]
            vs = r.get("vs_baseline")
            rungs = blk.get("rungs") or {}
            rung_s = (f"{rungs.get('blocks', '—')}/"
                      f"{rungs.get('validators', '—')}/"
                      f"{rungs.get('batch', '—')}")
            lines.append(
                f"| {b}x{v} | {_fmt(r.get('value'), 5)} s "
                f"| {_fmt(blk.get('apply_wall_s'), 5)} s "
                f"| {'—' if vs is None else f'{_fmt(vs, 1)}x'} "
                f"| {rung_s} | {_platform_group(r)} | {_where(r)} |")
        lines.append("")
    sp = [r for r in recs if r["metric"] == "forkchoice::speedup"]
    if sp:
        latest = max(sp, key=_order_key)
        lines.append(
            f"Latest head speedup over the phase0 spec oracle: "
            f"{_fmt(latest['value'], 1)}x ({_where(latest)}, platform "
            f"{_platform_group(latest)}).\n")
    hps = [r for r in recs if r["metric"] == "forkchoice::heads_per_s"]
    if hps:
        latest = max(hps, key=_order_key)
        lines.append(
            f"Latest head throughput: {_si(latest['value'])} heads/s "
            f"({_where(latest)}, platform "
            f"{_platform_group(latest)}).\n")
    return lines


def render_msm(msm: dict) -> list[str]:
    lines = ["## `_MSM_DEVICE_MIN` break-even\n", msm["text"] + "\n"]
    if msm.get("sizes"):
        lines.append(f"Latest probe: {msm['where']} "
                     f"(platform {msm.get('platform', '?')}).\n")
        lines.append("| n | host/device wall | routed |")
        lines.append("|---|---|---|")
        for s in msm["sizes"]:
            lines.append(f"| {s['n']} | {_fmt(s['host_over_device'], 2)} "
                         f"| {s.get('routed') or '—'} |")
        lines.append("")
    return lines


def render_attribution(attribution, durations, top_n: int) -> list[str]:
    lines = ["## Tier-1 wall-time attribution\n"]
    if attribution:
        total = sum(r["total_s"] for r in attribution)
        build = sum(r["spec_build_s"] for r in attribution)
        body = sum(r["test_body_s"] for r in attribution)
        lines.append(
            f"{len(attribution)} tests, {total:.1f}s in-test wall; "
            f"phase split {build:.1f}s spec-build vs {body:.1f}s "
            f"test-body.  Spec-build-dominated rows are the ROADMAP's "
            f"trim targets (session compile-cache reuse / redundant "
            f"spec builds).\n")
        lines.append(f"Top {min(top_n, len(attribution))} time sinks:\n")
        lines.append("| test | total | spec-build | test-body | "
                     "build share |")
        lines.append("|---|---|---|---|---|")
        for row in attribution[:top_n]:
            share = (row["spec_build_s"] / row["total_s"] * 100.0
                     if row["total_s"] else 0.0)
            lines.append(
                f"| `{row['test']}` | {row['total_s']:.2f}s "
                f"| {row['spec_build_s']:.2f}s "
                f"| {row['test_body_s']:.2f}s | {share:.0f}% |")
        lines.append("")
    elif durations:
        lines.append("No telemetry snapshot with phase spans; falling "
                     "back to pytest --durations rows (no spec-build "
                     "split).\n")
        lines.append("| test | phase | wall |")
        lines.append("|---|---|---|")
        for row in sorted(durations, key=lambda r: -r["dur_s"])[:top_n]:
            lines.append(f"| `{row['test']}` | {row['phase']} "
                         f"| {row['dur_s']:.2f}s |")
        lines.append("")
    else:
        lines.append("No attribution data — run the suite with "
                     "CST_TELEMETRY=1 CST_TELEMETRY_OUT=out/"
                     "telemetry_snapshot.json (CI does) and re-run the "
                     "report.\n")
    return lines


def render_report(result: dict) -> str:
    lines = ["# Benchwatch report\n"]
    lines.append(
        f"{result['n_records']} history records "
        f"({result['n_new_records']} new this run) from "
        f"{result['repo']}; store: `{result['history_path']}`.\n")
    lines.extend(render_thresholds(result["thresholds"], result["strict"]))
    lines.extend(render_regressions(result["regressions"],
                                    result["max_regress_pct"]))
    lines.extend(render_tail_latency(result["records"]))
    lines.extend(render_occupancy(result["records"]))
    lines.extend(render_slo(result["records"]))
    lines.extend(render_resilience(result["records"]))
    lines.extend(render_scaling(result["records"]))
    lines.extend(render_das(result["records"]))
    lines.extend(render_forkchoice(result["records"]))
    lines.extend(render_msm(result["msm"]))
    lines.extend(render_utilization(result["utilization"], result["msm"]))
    lines.extend(render_trend_tables(result["records"]))
    lines.extend(render_attribution(result["attribution"],
                                    result["durations"],
                                    result["top_n"]))
    if result["warnings"]:
        lines.append("## Ingest warnings\n")
        lines.append(f"{len(result['warnings'])} input(s) skipped "
                     "(malformed / truncated / unknown schema):\n")
        for w in result["warnings"]:
            lines.append(f"- {w}")
        lines.append("")
    verdict = result["verdict"]
    lines.append(f"---\n\n**Verdict: {verdict}**\n")
    return "\n".join(lines)


# --- orchestration -----------------------------------------------------------


def build_report(repo: Path, history_path: Path,
                 snapshots: list[Path], durations_path: Path | None,
                 top_n: int, strict: bool, max_regress_pct: float,
                 update_history: bool = True) -> dict:
    records, warnings = history.ingest_repo(repo)

    attribution: list[dict] = []
    for snap in snapshots:
        recs, attr, warns = history.parse_telemetry_snapshot(snap)
        records.extend(recs)
        warnings.extend(warns)
        if attr:
            attribution = attr   # latest snapshot wins
    durations: list[dict] = []
    if durations_path is not None:
        try:
            durations = history.parse_durations(
                Path(durations_path).read_text())
        except (OSError, UnicodeDecodeError) as e:
            warnings.append(f"{durations_path}: unreadable durations "
                            f"file ({type(e).__name__}) — skipped")

    # one pass over the store: load, diff the freshly parsed records
    # against it, optionally persist the new ones, and report over the
    # union either way
    stored, skipped, hist_warns = history.load_history(history_path)
    warnings.extend(hist_warns)
    seen = {history._canonical_line(r) for r in stored}
    fresh = [r for r in records
             if not history.validate_record(r)
             and history._canonical_line(r) not in seen]
    n_new = history.append_records(history_path, fresh) \
        if update_history else 0
    stored.extend(fresh)

    thresholds = evaluate_thresholds(stored)
    regressions = find_regressions(stored, max_regress_pct)
    msm = msm_recommendation(stored)
    utilization = collect_utilization(stored)
    warnings.extend(utilization.pop("warnings"))
    # a CST_COSTMODEL round that produced no costmodel block is a
    # counted warning, never a crash/exit — matching history.py's
    # malformed-input policy
    from . import costmodel
    if costmodel._env_enabled() and not utilization["kernels"]:
        warnings.append(
            "CST_COSTMODEL is set but no costmodel records were "
            "ingested — the round's telemetry block is missing its "
            "costmodel sub-object (bench run without CST_TELEMETRY, "
            "or a pre-costmodel bench build?)")

    failed = [t for t in thresholds if t["status"] == "FAIL"]
    gate_failures = list(regressions)
    if strict:
        gate_failures.extend(failed)
    if regressions:
        verdict = ("REGRESSION — " + ", ".join(
            f"`{r['metric']}` {r['change_pct']:+.1f}% ({r['kind']})"
            for r in regressions))
    elif strict and failed:
        verdict = ("THRESHOLD FAIL — " + ", ".join(
            t["id"] for t in failed))
    else:
        unmet = ", ".join(t["id"] for t in failed) or "none"
        verdict = f"clean (no regressions; unmet targets: {unmet})"

    return {
        "repo": str(repo),
        "history_path": str(history_path),
        "n_records": len(stored),
        "n_new_records": n_new,
        "records": stored,
        "thresholds": thresholds,
        "regressions": regressions,
        "msm": msm,
        "utilization": utilization,
        "attribution": attribution,
        "durations": durations,
        "warnings": warnings,
        "skipped_history_lines": skipped,
        "strict": strict,
        "max_regress_pct": max_regress_pct,
        "top_n": top_n,
        "verdict": verdict,
        "exit_code": 1 if gate_failures else 0,
    }


def _default_repo() -> Path:
    return Path(__file__).resolve().parents[2]


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m consensus_specs_tpu.telemetry.report",
        description="Benchwatch: longitudinal perf dashboard + "
                    "regression gate over bench/telemetry rounds.")
    parser.add_argument("--repo", type=Path, default=_default_repo(),
                        help="directory holding driver round files "
                             "(BENCH_r*/MULTICHIP_r*.json) and the "
                             "oracle baselines (default: this checkout)")
    parser.add_argument("--history", type=Path, default=None,
                        help="history store path (default: "
                             "<repo>/out/bench_history.jsonl)")
    parser.add_argument("--out", type=Path, default=None,
                        help="markdown report path (default: "
                             "<repo>/out/bench_report.md)")
    parser.add_argument("--snapshot", type=Path, action="append",
                        default=None,
                        help="telemetry snapshot file(s) for tier-1 "
                             "attribution (default: <repo>/out/"
                             "telemetry_snapshot.json when present)")
    parser.add_argument("--durations", type=Path, default=None,
                        help="saved pytest --durations output "
                             "(attribution fallback)")
    parser.add_argument("--top", type=int, default=None,
                        help="rows in the attribution table (default "
                             "CST_BENCHWATCH_TOP or 15)")
    parser.add_argument("--strict", action="store_true",
                        help="FAILing ROADMAP thresholds also gate the "
                             "exit code (same as CST_BENCHWATCH_STRICT=1)")
    parser.add_argument("--no-update", action="store_true",
                        help="do not append newly ingested records to "
                             "the history store")
    parser.add_argument("--json", type=Path, default=None,
                        help="also write the machine-readable result "
                             "(thresholds/regressions/msm) as JSON")
    args = parser.parse_args(argv)

    repo = args.repo.resolve()
    history_path = args.history or repo / "out" / "bench_history.jsonl"
    out_path = args.out or repo / "out" / "bench_report.md"
    snapshots = args.snapshot
    if snapshots is None:
        default_snap = repo / "out" / "telemetry_snapshot.json"
        snapshots = [default_snap] if default_snap.exists() else []
    strict = args.strict or \
        os.environ.get("CST_BENCHWATCH_STRICT", "0") not in ("", "0")
    try:
        max_regress_pct = float(
            os.environ.get("CST_BENCHWATCH_MAX_REGRESS_PCT", "20"))
    except ValueError:
        max_regress_pct = 20.0
    if args.top is not None:
        top_n = args.top
    else:
        try:
            top_n = int(os.environ.get("CST_BENCHWATCH_TOP", "15") or 15)
        except ValueError:
            top_n = 15

    result = build_report(
        repo=repo, history_path=history_path, snapshots=snapshots,
        durations_path=args.durations, top_n=top_n, strict=strict,
        max_regress_pct=max_regress_pct,
        update_history=not args.no_update)

    text = render_report(result)
    out_path.parent.mkdir(parents=True, exist_ok=True)
    out_path.write_text(text)
    print(text)
    if args.json:
        slim = {k: v for k, v in result.items()
                if k not in ("records", "attribution", "durations")}
        args.json.parent.mkdir(parents=True, exist_ok=True)
        args.json.write_text(json.dumps(slim, indent=1) + "\n")
    print(f"benchwatch: {result['verdict']} -> {out_path}",
          file=sys.stderr)
    return result["exit_code"]


if __name__ == "__main__":
    sys.exit(main())
