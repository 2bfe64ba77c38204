"""Sustained-load serving benchmark — the ROADMAP's attestation-
verification service under continuous traffic.

Every other bench measures one cold batch; this one drives the
`consensus_specs_tpu.serve` executor (deferred-result futures, AOT-
warmed `_bucket` executables, double-buffered batch pipeline) with the
mainnet per-slot arrival mix until throughput reaches steady state
(last 3 windows within ±20%), then prints ONE JSON metric line:

  {"metric": "serve_sustained_load", "value": <verifies/s>,
   "unit": "verifies/s", "vs_baseline": <x vs the oracle's
   FastAggregateVerify rate>, "serve": {...}}

The `"serve"` sub-object is `serve.loadgen.run_load`'s block (schema
pinned by `telemetry.export.validate_serve_block`): steady-state
verifies/sec, p50/p99 batch latency, window rates, queue-depth
histogram, pipeline stats.  `vs_baseline` divides the measured rate by
the persisted pure-Python oracle's single-verify rate
(bench_bls_baseline.json) — the per-core signatures/sec framing of
PAPERS.md's EdDSA-vs-BLS committee-consensus paper.

Exit-code contract: nonzero when loadgen never reached steady state
within its ≤3x window extension — the metric line then carries an
explicit `"error"` naming the non-convergence (and `serve.steady` is
false), instead of reporting the last unconverged window as if it were
a steady-state rate.

Resilience: `CST_FAULTS` installs a fault plan before the load runs
(the seams stay zero-overhead without it), and `CST_SERVE_CHAOS=1`
switches to the chaos harness (`resilience.chaos.run_chaos_load`):
baseline → faults live (breaker/oracle-fallback degraded mode) →
recovery-to-steady, with the `"resilience"` sub-object (schema
`validate_resilience_block`) embedded in the metric line and mined into
`resilience::*` benchwatch records for the `chaos-recovery` /
`chaos-correctness` threshold rows.  A chaos round additionally exits
nonzero on any wrong result or when the service never recovers.

Request tracing: `CST_TRACE_REQUESTS=1` mints a per-request
`RequestContext` at every submit (chaos rounds arm it automatically) —
the serve block's p50/p99 switch to per-request submit→complete
semantics (`latency_source: "reqtrace"`), a `latency_attribution`
sub-object decomposes the per-kind tail into
queue_wait/batch_form/device_wall/settle/detour, `latency::*` history
records feed the report's "Tail latency" section, and the worst-N
exemplar traces are written to `out/serve_exemplars.json` (the CI
artifact).  `CST_SERVE_STATUS_EVERY=<s>` additionally dumps the
executor's live `status()` JSON on stderr while the round runs.

Monitoring: `CST_METRICS_PORT=<port>` serves live Prometheus text
exposition while the round runs (the loadgen self-scrapes it mid-round
into `out/metrics_scrape.txt`), and `CST_SLO_RULES=...` arms the live
SLO watchdog — the serve block gains the `"slo"` evidence sub-object
(schema `validate_slo_block`, mined into `slo::*` records for the
`slo-clean-round` threshold row) and the breach evidence is written to
`out/slo_breaches.json` (`out/chaos_slo_breaches.json` on chaos
rounds, where the deterministic breach→clear arc is asserted and gated
by `chaos-slo-arc`).  See README "Monitoring".

Knobs are the CST_SERVE_* family (README "Serving"); the CPU smoke runs
closed-loop (`CST_SERVE_RATE=0`) so the measured rate is the host's
capacity instead of an idle fixed-rate clock.  With CST_TELEMETRY=1 the
line also carries the standard `"telemetry"` block, and
CST_BENCHWATCH_HISTORY lands `serve::*` (and `resilience::*`) history
records for the benchwatch threshold rows.
"""

from __future__ import annotations

import json
import os
import sys
import time
from pathlib import Path

import jax

jax.config.update("jax_enable_x64", True)

from consensus_specs_tpu import telemetry  # noqa: E402
from consensus_specs_tpu.resilience import faults  # noqa: E402
from consensus_specs_tpu.telemetry import history as benchwatch  # noqa: E402
from consensus_specs_tpu.utils.jaxtools import (  # noqa: E402
    device_fields, enable_compile_cache)

enable_compile_cache()

BLS_BASELINE_FILE = (Path(__file__).resolve().parent
                     / "bench_bls_baseline.json")


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def _oracle_verifies_per_s() -> float | None:
    """The pure-Python oracle's FastAggregateVerify rate (verifies/s)
    from the persisted baseline — the denominator of `vs_baseline`."""
    try:
        data = json.loads(BLS_BASELINE_FILE.read_text())
        per_verify = float(data["oracle_seconds_per_fast_aggregate_verify"])
        return 1.0 / per_verify if per_verify > 0 else None
    except (OSError, KeyError, ValueError, TypeError):
        return None


def _emit(record: dict) -> None:
    """One metric line on stdout, `"telemetry"` embedded on telemetry
    rounds, history records appended when CST_BENCHWATCH_HISTORY names
    a path — the same contract as bench.py / bench_bls.py."""
    record = telemetry.embed_bench_block({**record, **device_fields()})
    benchwatch.append_emission(record, ts=time.time())
    print(json.dumps(record), flush=True)


def main() -> int:
    from consensus_specs_tpu.serve.loadgen import config_from_env, run_load
    from consensus_specs_tpu.telemetry import (
        validate_resilience_block,
        validate_serve_block,
    )

    chaos = os.environ.get("CST_SERVE_CHAOS", "0") not in ("", "0")
    cfg = config_from_env()
    log(f"serve bench: {cfg} on "
        f"{jax.devices()[0].platform}:{len(jax.devices())}"
        + (" [CHAOS]" if chaos else ""))
    if not chaos and faults.plan_from_env_source():
        # run_load installs the plan itself, after kernel warmup (the
        # chaos harness instead owns install/clear phase by phase); the
        # executor arms retry/breaker/fallback automatically
        log(f"serve bench: fault plan ARMED: "
            f"{faults.load_plan(faults.plan_from_env_source()).describe()}")
    block = run_load(cfg)
    problems = validate_serve_block(block)
    res = block.get("resilience")
    if chaos:
        problems += validate_resilience_block(res)
    if problems:
        log(f"serve bench: INVALID serve block: {problems}")
        return 1
    oracle_rate = _oracle_verifies_per_s()
    vs_baseline = (round(block["verifies_per_s"] / oracle_rate, 2)
                   if oracle_rate else None)
    record = {
        "metric": "serve_sustained_load",
        "value": block["verifies_per_s"],
        "unit": "verifies/s",
        "vs_baseline": vs_baseline,
        "serve": {k: v for k, v in block.items() if k != "resilience"},
    }
    if res is not None:
        record["resilience"] = res
    la = block.get("latency_attribution")
    if la is not None:
        # worst-N exemplar traces as a standalone artifact (CI uploads
        # both): enough to reconstruct WHERE each tail request's wall
        # went without re-running the round.  Chaos rounds write their
        # own file so the CI job's later chaos-smoke step cannot
        # clobber the serve-smoke step's exemplars
        exemplars = Path(__file__).resolve().parent / "out" / \
            ("chaos_exemplars.json" if chaos else "serve_exemplars.json")
        exemplars.parent.mkdir(exist_ok=True)
        exemplars.write_text(json.dumps(
            {"metric": "serve_sustained_load",
             "latency_source": block.get("latency_source"),
             "p99_queue_frac": la.get("p99_queue_frac"),
             "kinds": {k: v.get("p99_components_ms")
                       for k, v in la.get("kinds", {}).items()},
             "worst": la.get("worst", [])}, indent=1) + "\n")
        log(f"serve bench: tail attribution — p99 queue frac "
            f"{la.get('p99_queue_frac')}, worst exemplars -> "
            f"{exemplars}")
    slo = block.get("slo")
    if slo is not None:
        # the watchdog's breach evidence as a standalone artifact (CI
        # uploads it next to the exemplars): the per-rule summary plus
        # the bounded breach→clear event log with exemplar payloads
        slo_out = Path(__file__).resolve().parent / "out" / \
            ("chaos_slo_breaches.json" if chaos else "slo_breaches.json")
        slo_out.parent.mkdir(exist_ok=True)
        slo_out.write_text(json.dumps(
            {"metric": "serve_sustained_load", "slo": slo}, indent=1)
            + "\n")
        log(f"serve bench: SLO watchdog — {slo['breaches']} breach(es) "
            f"over {slo['ticks']} tick(s), evidence -> {slo_out}")
    rc = 0
    if not block["steady"]:
        # the exit-code contract: an unconverged run must not pass for
        # a steady-state measurement — say so IN the metric line too
        record["error"] = ("loadgen never reached steady state within "
                           "the 3x window extension")
        rc = 1
    if chaos and (res["wrong_results"] > 0 or not res["recovered"]):
        record["error"] = (f"chaos round failed: "
                           f"{res['wrong_results']} wrong result(s), "
                           f"recovered={res['recovered']}")
        rc = 1
    _emit(record)
    log(f"serve bench: {block['verifies_per_s']} verifies/s "
        f"(steady={block['steady']}, {block['mode']} loop), "
        f"p50 {block['p50_ms']} ms / p99 {block['p99_ms']} ms, "
        f"{block['settled']} settled in {block['duration_s']}s"
        + (f", {vs_baseline}x oracle" if vs_baseline else ""))
    if chaos:
        log(f"serve bench: chaos — {res['faults_injected']} fault(s), "
            f"{res['wrong_results']} wrong / {res['checked_results']} "
            f"checked, {res['fallbacks']} oracle-fallback, "
            f"{res['retries']} retried, breaker trips "
            f"{res['breaker']['trips']}, recovery "
            f"{res['recovery_latency_s']}s, degraded "
            f"{res['degraded_verifies_per_s']} verifies/s "
            f"(baseline {res['baseline_verifies_per_s']}), merkle heal "
            f"{res['heal']['recovery_s']}s")
    if rc:
        log(f"serve bench: FAILED — {record['error']}")
    return rc


if __name__ == "__main__":
    sys.exit(main())
