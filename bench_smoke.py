"""CPU smoke for the benchmark harnesses (`make bench-smoke`).

Runs tiny-shape configurations of bench.py (epoch worker) and
bench_bls.py on the CPU platform and asserts the JSON output contract
the external driver parses — so bench bit-rot (import errors, schema
drift, kernel regressions that crash at trace time) is caught without a
TPU.  The kzg worker is excluded: its mainnet 4096-wide blob shapes have
no tiny-shape knob and would dominate the lane's wall time.

The sub-benches run with CST_TELEMETRY=1 so the `"telemetry"` sub-object
(compile_s/run_s split, padding waste, MSM/h2c routing — see
`consensus_specs_tpu.telemetry`) is asserted present and schema-valid on
every metric line: the bench contract cannot silently drop it.  The
bench_bls run also sets CST_TRACE_FILE and checks the emitted Chrome
trace is loadable trace-event JSON, and probes the MSM break-even at one
tiny size (n=4) to keep the probe path exercised.

Both sub-benches additionally run with CST_COSTMODEL=1 and assert the
cost-model contract: the telemetry block carries a `costmodel` block
with nonzero flops/bytes for the flagship kernel (the fused epoch step;
the BLS round must cover the pairing/MSM/h2c/sha256 kernel surface),
and the benchwatch store round-trips the new `costmodel` record kind.

A third round runs bench_serve.py closed-loop on tiny shapes with
request tracing armed (CST_TRACE_REQUESTS=1) and asserts the serving
contract: a steady-state `"serve"` sub-object (verifies/sec,
per-request p50/p99, queue-depth histogram — `validate_serve_block`),
the `latency_attribution` tail decomposition (every served kind
present, exemplar components summing to end-to-end within 1ms), the
`serve::*` + `latency::*` benchwatch history records, the queue-depth
/ in-flight gauge counter tracks AND the per-request flow arrows
(submit → batch → settle, one per kind) in the Chrome trace, the
report's "Tail latency" section, and the worst-N exemplar artifact
(`out/serve_exemplars.json`).

`bench_smoke.py --chaos` (the `make chaos-smoke` / CI chaos-smoke
lane) runs ONLY the chaos round: bench_serve.py under
CST_SERVE_CHAOS=1 with a canned fault plan injecting dispatch failures
into the RLC kernel, asserting the resilience contract end to end —
zero wrong results, breaker trip → oracle fallback → re-close, finite
recovery latency, a schema-valid `"resilience"` block
(`validate_resilience_block`), the `resilience::*` history-record
round-trip, and the benchwatch report's Resilience section +
`chaos-recovery` threshold row rendering from those records.  Since
PR 9 the round also carries the checkpoint kill-and-resurrect segment
(restore+replay ≥5x over a full rebuild, root parity, the
`checkpoint::*` records and `checkpoint-restore` threshold row), the
flagship breaker arc (`flagship::degraded_steps`), and the heal path
record (`heal["path"] == "checkpoint"` — recovery restored from the
snapshot, not the O(N) rebuild).

`bench_smoke.py --das` (the `make das-smoke` lane) runs the PeerDAS
cell-proof sweep at the 128x8 sampling matrix on CPU: the `"das"`
block schema (`validate_das_block`), the >= 2x das-speedup acceptance
criterion vs the pure-Python oracle (shape-bound — the oracle pays a
per-cell Lagrange interpolation), the mixed-invalid isolation arc, the
coset-barycentric cross-check, the `das::*` history round-trip, and
the report's DAS section + threshold-row wiring.

`bench_smoke.py --forkchoice` (the `make fc-smoke` lane) runs the
device LMD-GHOST sweep on a tiny CPU tree (64 blocks x 1024
validators): the `"forkchoice"` block schema
(`validate_forkchoice_block`), the >= 2x fc-speedup acceptance
criterion vs the phase0 spec oracle's `get_head` (shape-bound — the
oracle walks every active validator per child in pure Python),
bit-exact head parity, the `forkchoice::*` history round-trip, and
the report's Fork choice section + threshold-row wiring.

`bench_smoke.py --chaos-mesh` (the `make chaos-mesh-smoke` lane) runs
the same round with CST_CHAOS_MESH=1 on the simulated 8-host-device
CPU mesh (XLA_FLAGS=--xla_force_host_platform_device_count=8): a
`device_loss` fault into `batch_verify_sharded` must re-bucket the
lost shard's statements over the surviving devices — zero wrong or
dropped statements, an invalid statement still rejected while
degraded, the half-open probe re-admitting the full mesh — and the
`mesh::*` records must round-trip with the `mesh-recovery` /
`mesh-lost-statements` threshold rows PASSing.
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import time
from pathlib import Path

from consensus_specs_tpu.telemetry import validate_bench_block
from consensus_specs_tpu.telemetry import history as benchwatch

HERE = Path(__file__).resolve().parent


def _run(cmd, env_extra, timeout):
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    env.update(env_extra)
    print(f"--- {' '.join(cmd)} ---", file=sys.stderr, flush=True)
    proc = subprocess.run([sys.executable] + cmd, capture_output=True,
                          text=True, timeout=timeout, env=env,
                          cwd=str(HERE))
    if proc.stderr:
        sys.stderr.write(proc.stderr[-2000:])
        sys.stderr.flush()
    if proc.returncode != 0:
        raise SystemExit(f"{cmd}: rc={proc.returncode}")
    parsed = []
    for line in (proc.stdout or "").splitlines():
        if not line.strip():
            continue
        try:
            parsed.append(json.loads(line))
        except json.JSONDecodeError:
            raise SystemExit(f"{cmd}: non-JSON stdout line: {line!r}")
    if not parsed:
        raise SystemExit(f"{cmd}: produced no JSON line")
    return parsed


def _check_telemetry(record, where: str) -> dict:
    tel = record.get("telemetry")
    problems = validate_bench_block(tel)
    if problems:
        raise SystemExit(f"{where}: bad telemetry block {problems}: "
                         f"{json.dumps(tel)[:500]}")
    return tel


def _check_costmodel(tel, where: str, expect_substrings=()) -> dict:
    """Assert the `costmodel` block exists, is schema-valid, carries
    nonzero flops/bytes for at least one kernel matching each expected
    substring, and has a coherent watermark summary."""
    from consensus_specs_tpu.telemetry import validate_costmodel_block

    cm = tel.get("costmodel")
    problems = validate_costmodel_block(cm)
    if problems:
        raise SystemExit(f"{where}: bad costmodel block {problems}: "
                         f"{json.dumps(cm)[:500]}")
    kernels = cm["kernels"]
    good = {k: v for k, v in kernels.items() if "error" not in v}
    for sub in expect_substrings:
        hits = [k for k in good if sub in k]
        assert hits, (where, sub, sorted(kernels))
        k = hits[0]
        assert good[k]["flops"] > 0 and good[k]["bytes_accessed"] > 0, \
            (where, k, good[k])
        assert good[k]["bound"] in ("compute", "memory", "launch"), \
            (where, k, good[k])
    assert cm["watermarks"], (where, "no watermark samples")
    for dev, wm in cm["watermarks"].items():
        assert wm["high_water_bytes"] >= wm["last_bytes"] >= 0, (dev, wm)
    return cm


def main():
    out = _run(["bench.py", "--worker", "epoch"],
               {"CST_BENCH_N": "1024", "CST_TELEMETRY": "1",
                "CST_COSTMODEL": "1"},
               timeout=900)
    last = out[-1]
    assert isinstance(last.get("seconds"), (int, float)) \
        and last["seconds"] > 0, last
    tel = _check_telemetry(last, "epoch worker")
    assert tel["compile_s"] > 0, tel   # the fused step DID compile
    # the flagship kernel's cost record: nonzero XLA flop/byte budget
    # the incremental-flagship contract: the rewired step reports its
    # dirty fraction and at least one passed full-rebuild parity check
    assert isinstance(last.get("dirty_frac"), float) \
        and 0 < last["dirty_frac"] <= 1, last
    assert last.get("parity_checks", 0) >= 1, last
    cm = _check_costmodel(tel, "epoch worker",
                          expect_substrings=("epoch_sweep", "merkle_build",
                                             "merkle_incr"))
    print("bench.py epoch worker JSON OK:",
          json.dumps({k: v for k, v in last.items() if k != "telemetry"}),
          f"(telemetry: compile {tel['compile_s']}s run {tel['run_s']}s; "
          f"costmodel: {len(cm['kernels'])} kernel(s))")

    trace_file = HERE / "out" / "smoke_trace.json"
    trace_file.parent.mkdir(exist_ok=True)
    if trace_file.exists():
        trace_file.unlink()
    # CST_BENCHWATCH_HISTORY makes every emitted metric line also land
    # in the longitudinal store; default to a scratch file so a local
    # smoke run does not pollute out/bench_history.jsonl, but let CI
    # point it AT the real store (its benchwatch job reports over it).
    # Only the scratch default is ever deleted — an externally named
    # store is longitudinal data this smoke must append to, not wipe.
    hist_env = os.environ.get("CST_BENCHWATCH_HISTORY")
    hist_file = Path(hist_env) if hist_env \
        else HERE / "out" / "smoke_history.jsonl"
    if not hist_env and hist_file.exists():
        hist_file.unlink()
    run_t0 = time.time()
    out = _run(["bench_bls.py"],
               {"CST_BLS_BENCH_N": "2", "CST_BLS_BENCH_COMMITTEE": "2",
                "CST_BLS_BENCH_SYNC": "4",
                "CST_TELEMETRY": "1", "CST_COSTMODEL": "1",
                "CST_BLS_BENCH_MSM_SIZES": "4",
                "CST_TRACE_FILE": str(trace_file),
                "CST_BENCHWATCH_HISTORY": str(hist_file)},
               timeout=1800)
    metrics = [o for o in out if "metric" in o]
    assert len(metrics) == 3, out    # configs #2, #3 + the MSM probe
    for m in metrics:
        assert {"metric", "value", "unit", "vs_baseline"} <= set(m), m
        assert isinstance(m["value"], (int, float)), m
        _check_telemetry(m, m["metric"])
    probe = [m for m in metrics
             if m["metric"].startswith("g1_msm_breakeven_probe")]
    assert probe and probe[0].get("detail", {}).get("4"), probe
    # the cost-model kernel surface: RLC (device h2c), pairing, MSM,
    # sha256 merkle + barycentric from the cost sweep — cost records
    # are per-process, so the last metric line carries them all
    _check_costmodel(metrics[-1]["telemetry"], "bench_bls",
                     expect_substrings=("rlc", "pairing", "msm",
                                        "sha256", "barycentric"))
    print("bench_bls.py JSON OK:", json.dumps(
        [{k: v for k, v in m.items() if k != "telemetry"}
         for m in metrics]))

    # the benchwatch history-record contract: every metric line this run
    # emitted must have landed in the store as one schema-valid record,
    # platform-stamped "cpu" (the smoke pin).  Assertions apply to THIS
    # run's records (ts >= run start, with clock slack) — a pre-existing
    # external store may hold anything
    hist_records, skipped, hist_warns = benchwatch.load_history(hist_file)
    if not hist_env:     # we created the scratch file fresh
        assert not skipped and not hist_warns, (skipped, hist_warns)
    fresh = [r for r in hist_records
             if isinstance(r.get("ts"), (int, float))
             and r["ts"] >= run_t0 - 5]
    stored = {r["metric"]: r for r in fresh}
    assert {m["metric"] for m in metrics} <= set(stored), (
        sorted(stored), metrics)
    # the bench metric lines land as bench_emit; the same run also
    # appends costmodel-kind records (checked in depth below) — every
    # fresh record of either kind must be schema-valid and cpu-stamped
    for m in metrics:
        assert stored[m["metric"]]["source"] == "bench_emit", \
            stored[m["metric"]]
    for rec in fresh:
        problems = benchwatch.validate_record(rec)
        assert not problems, (problems, rec)
        assert rec["source"] in ("bench_emit", "costmodel"), rec
        assert rec["platform"] == "cpu", rec
    probe_rec = [r for r in fresh
                 if r["metric"].startswith("g1_msm_breakeven_probe")]
    assert probe_rec and probe_rec[0].get("detail", {}).get("4"), probe_rec
    print(f"benchwatch history OK: {len(fresh)} records this run -> "
          f"{hist_file}")

    # the new `costmodel` record kind round-trips: one schema-valid
    # record per captured kernel plus the per-device memory high-water
    # marks, all re-loadable through the same history reader
    cost_recs = [r for r in hist_records if r.get("source") == "costmodel"]
    cost_kernels = [r for r in cost_recs
                    if r["metric"].startswith("costmodel::")]
    wm_recs = [r for r in cost_recs
               if r["metric"].startswith("device_mem_high_water::")]
    assert cost_kernels, [r["metric"] for r in hist_records]
    assert wm_recs, [r["metric"] for r in cost_recs]
    for rec in cost_recs:
        assert not benchwatch.validate_record(rec), rec
    names = {r["metric"] for r in cost_kernels}
    for sub in ("rlc", "pairing", "msm", "sha256", "barycentric"):
        assert any(sub in n for n in names), (sub, sorted(names))
    for rec in cost_kernels:
        cm = rec.get("costmodel")
        assert isinstance(cm, dict) and cm.get("flops", 0) > 0, rec
    print(f"costmodel history OK: {len(cost_kernels)} kernel record(s), "
          f"{len(wm_recs)} watermark record(s)")

    # CST_TRACE_FILE must have produced loadable Chrome trace-event JSON
    trace = json.loads(trace_file.read_text())
    events = trace["traceEvents"]
    spans = [e for e in events if e.get("ph") == "X"]
    assert spans, "trace file has no complete ('X') events"
    for e in spans:
        assert {"name", "ph", "ts", "dur", "pid", "tid"} <= set(e), e
    names = {e["name"] for e in spans}
    assert "bls.batch_verify" in names, sorted(names)
    # cost-model counter track: watermark samples + per-kernel cost
    # records ride as 'C' (counter) events alongside the span track
    counters = [e for e in events if e.get("ph") == "C"]
    counter_names = {e["name"] for e in counters}
    assert "device_memory_bytes" in counter_names, sorted(counter_names)
    assert any(n.startswith("cost.") for n in counter_names), \
        sorted(counter_names)
    print(f"chrome trace OK: {len(spans)} spans + {len(counters)} "
          f"counter events -> {trace_file}")

    # the incremental-merkleization dirty-fraction round (ROADMAP
    # "Incremental merkleization for the flagship"): the acceptance
    # shape — 2**20 leaves on CPU, incremental update at 1% dirty vs a
    # full re-merkleize — emitting the merkle_incr::* records the
    # benchwatch `merkle-incremental-speedup` threshold row evaluates.
    # The parent appends the records (the worker only prints), stamped
    # with the worker's platform so the TPU-only regression rule never
    # sees a CPU smoke as a TPU round.
    merkle_t0 = time.time()
    out = _run(["bench.py", "--worker", "merkle"],
               {"CST_MERKLE_N": str(1 << 20),
                "CST_MERKLE_DIRTY_FRAC": "0.01,1.0",
                "CST_MERKLE_PROOF_BATCH": "64",
                "CST_TELEMETRY": "1"},
               timeout=1800)
    merkle = out[-1]
    platform = merkle.get("platform", "cpu")
    upd = merkle.get("merkle_incr::update@frac0.01")
    assert isinstance(upd, dict), sorted(merkle)
    assert {"value", "unit", "vs_baseline", "detail"} <= set(upd), upd
    assert upd["unit"] == "s" and upd["value"] > 0, upd
    assert upd["detail"]["n_leaves"] == 1 << 20, upd
    # the ROADMAP target is >= 5x at 1% dirty (threshold row); the smoke
    # gate is a loose sanity floor so a slow CI host cannot flake it
    assert upd["vs_baseline"] >= 2.0, upd
    _check_telemetry(upd, "merkle worker")
    full_upd = merkle.get("merkle_incr::update@frac1")
    assert isinstance(full_upd, dict) and full_upd["value"] > 0, merkle
    proofs = [v for k, v in merkle.items()
              if k.startswith("merkle_incr::proofs@")]
    assert proofs and proofs[0]["detail"]["us_per_proof"] > 0, merkle
    prev_hist = os.environ.get("CST_BENCHWATCH_HISTORY")
    os.environ["CST_BENCHWATCH_HISTORY"] = str(hist_file)
    try:
        for name, rec in merkle.items():
            if isinstance(rec, dict) and "value" in rec:
                benchwatch.append_emission(
                    dict(rec, metric=name, platform=platform),
                    ts=time.time())
    finally:
        if prev_hist is None:
            os.environ.pop("CST_BENCHWATCH_HISTORY", None)
        else:
            os.environ["CST_BENCHWATCH_HISTORY"] = prev_hist
    hist_records, _, _ = benchwatch.load_history(hist_file)
    fresh = {r["metric"]: r for r in hist_records
             if isinstance(r.get("ts"), (int, float))
             and r["ts"] >= merkle_t0 - 5}
    mrec = fresh.get("merkle_incr::update@frac0.01")
    assert mrec is not None, sorted(fresh)
    assert not benchwatch.validate_record(mrec), mrec
    assert mrec["platform"] == platform, mrec
    print(f"merkle incremental OK: {upd['vs_baseline']}x vs full "
          f"re-merkleize @ 1% dirty @ 2**20 leaves "
          f"({proofs[0]['detail']['us_per_proof']} us/proof)")

    # the serving subsystem's sustained-load round: closed-loop (the
    # measured rate is this host's capacity — an open-loop mainnet-rate
    # clock on an arbitrary CI box would idle or diverge), tiny pool /
    # committee / rung shapes, long-enough windows that batch-settle
    # granularity doesn't defeat the ±20% steady-state check.  Asserts
    # the `"serve"` bench sub-object contract, the serve::* history
    # record round-trip, and the gauge counter tracks in the trace.
    from consensus_specs_tpu.telemetry import validate_serve_block

    serve_trace = HERE / "out" / "smoke_serve_trace.json"
    if serve_trace.exists():
        serve_trace.unlink()
    exemplar_file = HERE / "out" / "serve_exemplars.json"
    if exemplar_file.exists():
        exemplar_file.unlink()
    scrape_file = HERE / "out" / "metrics_scrape.txt"
    if scrape_file.exists():
        scrape_file.unlink()
    slo_file = HERE / "out" / "slo_breaches.json"
    if slo_file.exists():
        slo_file.unlink()
    # an ephemeral port for the live exposition endpoint (bind/release:
    # CI runners share the host, a fixed port would collide)
    with socket.socket() as sock:
        sock.bind(("127.0.0.1", 0))
        metrics_port = sock.getsockname()[1]
    serve_t0 = time.time()
    # CST_TRACE_REQUESTS=1: the round runs with request tracing armed —
    # per-request percentile semantics, the latency_attribution block,
    # flow events in the trace, latency::* records, and the exemplar
    # artifact are all asserted below (the acceptance arc of the
    # request-tracing PR).  CST_METRICS_PORT + CST_SLO_RULES arm the
    # live-monitoring arc: the loadgen self-scrapes the exposition
    # endpoint mid-round (validated line-by-line below) and the SLO
    # watchdog runs sane-bound rules the round must end CLEAN on
    out = _run(["bench_serve.py"],
               {"CST_SERVE_DURATION_S": "12", "CST_SERVE_RATE": "0",
                "CST_SERVE_POOL": "4", "CST_SERVE_COMMITTEE": "4",
                "CST_SERVE_MAX_BATCH": "8", "CST_SERVE_WINDOWS": "3",
                "CST_TELEMETRY": "1", "CST_TRACE_REQUESTS": "1",
                "CST_TRACE_FILE": str(serve_trace),
                "CST_METRICS_PORT": str(metrics_port),
                "CST_SLO_RULES": ("serve.p99_ms<100000:name=p99-sane; "
                                  "serve.queue_depth<100000"
                                  ":name=queue-sane"),
                "CST_OCCUPANCY": "1",
                "CST_BENCHWATCH_HISTORY": str(hist_file)},
               timeout=900)
    serve_lines = [o for o in out if o.get("metric") == "serve_sustained_load"]
    assert len(serve_lines) == 1, out
    sl = serve_lines[0]
    assert sl["unit"] == "verifies/s" and sl["value"] > 0, sl
    block = sl.get("serve")
    problems = validate_serve_block(block)
    assert not problems, (problems, json.dumps(block)[:500])
    assert block["steady"], ("no steady state", block["windows"])
    assert block["settled"] == block["submitted"] > 0, block
    assert block["failed"] == 0, block
    assert block["p50_ms"] is not None and block["p99_ms"] is not None, block
    assert block["queue_depth"]["hist"], block
    assert block["mode"] == "closed", block
    # the stateless-client lane: `submit_proof_request` rode the same
    # futures pipeline (and settled — failed==0 covers it above)
    assert block["kinds"].get("proof", 0) >= 1, block["kinds"]
    _check_telemetry(sl, "serve bench")

    # request-tracing contract: per-request percentile basis, a
    # schema-valid latency_attribution with one entry per served kind,
    # and components that sum to each exemplar's end-to-end within 1ms
    from consensus_specs_tpu.telemetry import validate_latency_attribution
    served_kinds = {k for k, n in block["kinds"].items() if n > 0}

    assert block.get("latency_source") == "reqtrace", block.get(
        "latency_source")
    la = block.get("latency_attribution")
    problems = validate_latency_attribution(la)
    assert not problems, (problems, json.dumps(la)[:500])
    assert served_kinds <= set(la["kinds"]), (served_kinds,
                                              sorted(la["kinds"]))
    assert la["answered"] == block["settled"], (la["answered"], block)
    for ex_rec in la["worst"]:
        total = sum(ex_rec["components_ms"].values())
        assert abs(total - ex_rec["e2e_ms"]) <= 1.0, ex_rec
    for kind, blk in la["kinds"].items():
        assert sum(blk["outcomes"].values()) == blk["count"], (kind, blk)
    print(f"latency attribution OK: {len(la['kinds'])} kind(s), p99 "
          f"queue frac {la['p99_queue_frac']}, {len(la['worst'])} "
          f"exemplar(s)")

    # device-occupancy contract (CST_OCCUPANCY=1): the serve block
    # carries a schema-valid occupancy sub-object whose busy wall plus
    # the four bubble causes partition the measured wall EXACTLY (the
    # same contiguity discipline as the reqtrace components), and at
    # depth>=2 the prep-overlap score is computable
    from consensus_specs_tpu.telemetry import validate_occupancy_block
    occ = block.get("occupancy")
    assert occ is not None, "CST_OCCUPANCY=1 but no occupancy block"
    problems = validate_occupancy_block(occ)
    assert not problems, (problems, json.dumps(occ)[:500])
    assert occ["busy_s"] > 0, occ
    occ_total = occ["busy_s"] + sum(occ["bubbles_s"].values())
    assert abs(occ_total - occ["wall_s"]) <= 1e-6 * occ["wall_s"], \
        (occ_total, occ["wall_s"], occ["bubbles_s"])
    if (occ.get("depth") or 0) >= 2:
        assert occ["overlap"]["score"] is not None, occ["overlap"]
    print(f"occupancy OK: busy_frac {occ['busy_frac']}, bubbles "
          + json.dumps({k: round(v, 3)
                        for k, v in occ["bubbles_s"].items()})
          + f", overlap score {occ['overlap']['score']}")
    # the worst-N exemplar artifact bench_serve writes for CI upload
    exemplars = json.loads(exemplar_file.read_text())
    assert exemplars["worst"] == la["worst"], exemplar_file

    # live-monitoring arc, scrape side: the loadgen self-scraped the
    # CST_METRICS_PORT endpoint mid-round and wrote the exposition text
    # verbatim — re-parse it LINE BY LINE with the strict parser and
    # assert every served kind appears as a labeled lifetime series
    from consensus_specs_tpu.telemetry import metrics_export
    assert scrape_file.exists(), \
        "loadgen never wrote the mid-round scrape artifact"
    scrape = metrics_export.parse_exposition(scrape_file.read_text())
    scraped_kinds = {lb["kind"] for lb, _ in
                     scrape.get("cst_serve_requests_total", [])}
    assert served_kinds <= scraped_kinds, (sorted(served_kinds),
                                           sorted(scraped_kinds))
    assert scrape.get("cst_serve_live_queue_depth"), sorted(scrape)
    # the watchdog publishes its own rule-labeled families
    slo_rules_scraped = {lb.get("rule") for lb, _ in
                         scrape.get("cst_slo_breaching", [])}
    assert slo_rules_scraped == {"p99-sane", "queue-sane"}, \
        slo_rules_scraped
    assert scrape.get("cst_slo_ticks_total", [({}, 0.0)])[0][1] > 0, \
        scrape.get("cst_slo_ticks_total")
    # the occupancy families publish live: the rolling busy fraction
    # and the cause-labeled bubble accumulators
    assert scrape.get("cst_serve_device_busy_frac"), sorted(scrape)
    bubble_causes = {lb["cause"] for lb, _ in
                     scrape.get("cst_serve_bubble_seconds_total", [])}
    assert bubble_causes == {"host_prep", "queue_starved",
                             "settle_serialized", "drain"}, bubble_causes
    print(f"metrics scrape OK: {len(scrape)} families, kinds "
          f"{sorted(scraped_kinds)} -> {scrape_file}")

    # live-monitoring arc, watchdog side: a healthy round ends CLEAN —
    # zero breaches over a positive tick count, schema-valid, and the
    # breach-evidence artifact rides along for CI upload
    from consensus_specs_tpu.telemetry import validate_slo_block
    slo = block.get("slo")
    assert slo is not None, "CST_SLO_RULES armed but no slo block"
    assert not validate_slo_block(slo), validate_slo_block(slo)
    assert slo["ticks"] > 0, slo
    assert slo["breaches"] == 0 and slo["clean"], slo
    assert {r["name"] for r in slo["rules"]} == {"p99-sane",
                                                 "queue-sane"}, slo
    assert json.loads(slo_file.read_text())["slo"]["clean"], slo_file
    print(f"slo watchdog OK: clean round, {slo['ticks']} tick(s), "
          f"evidence -> {slo_file}")

    print("bench_serve.py JSON OK:", json.dumps(
        {k: v for k, v in sl.items() if k not in ("telemetry", "serve")}),
        f"({block['verifies_per_s']} verifies/s, steady over "
        f"{len(block['windows'])} windows)")

    # serve history round-trip: the emission must land as the
    # bench_emit line PLUS serve-source serve::* records (throughput
    # carrying the compacted block, latency percentiles standalone)
    # PLUS the latency-source attribution records the traced round mines
    hist_records, _, _ = benchwatch.load_history(hist_file)
    fresh = [r for r in hist_records
             if isinstance(r.get("ts"), (int, float))
             and r["ts"] >= serve_t0 - 5]
    by_metric = {r["metric"]: r for r in fresh}
    assert "serve_sustained_load" in by_metric, sorted(by_metric)
    assert by_metric["serve_sustained_load"]["source"] == "bench_emit"
    for name in ("serve::verifies_per_s", "serve::p50_ms",
                 "serve::p99_ms"):
        rec = by_metric.get(name)
        assert rec is not None, (name, sorted(by_metric))
        assert rec["source"] == "serve" and rec["platform"] == "cpu", rec
        assert not benchwatch.validate_record(rec), rec
    vrec = by_metric["serve::verifies_per_s"]
    assert vrec["serve"]["queue_depth"]["hist"], vrec
    assert isinstance(vrec["serve"]["steady"], bool), vrec
    assert vrec["serve"]["latency_source"] == "reqtrace", vrec
    for kind in sorted(served_kinds):
        rec = by_metric.get(f"latency::p99_ms@{kind}")
        assert rec is not None, (kind, sorted(by_metric))
        assert rec["source"] == "latency", rec
        assert not benchwatch.validate_record(rec), rec
        comp = rec["latency"]["p99_components_ms"]
        assert set(comp) == {"queue_wait", "batch_form", "device_wall",
                             "settle", "detour"}, comp
    qrec = by_metric.get("latency::p99_queue_frac")
    assert qrec is not None and qrec["source"] == "latency", \
        sorted(by_metric)
    assert qrec["latency"]["worst"], qrec
    # the slo record kinds land too: zero breaches carrying the compact
    # block, and the clean-round 0/1 the threshold row gates
    brec = by_metric.get("slo::breaches")
    assert brec is not None and brec["source"] == "slo", sorted(by_metric)
    assert not benchwatch.validate_record(brec), brec
    assert brec["value"] == 0 and brec["slo"]["ticks"] > 0, brec
    crec = by_metric.get("slo::clean_round")
    assert crec is not None and crec["value"] == 1.0, crec
    assert not benchwatch.validate_record(crec), crec
    # the pipeline-source occupancy records land: busy_frac carrying
    # the compact block, one bubble record per cause
    orec = by_metric.get("pipeline::busy_frac")
    assert orec is not None and orec["source"] == "pipeline", \
        sorted(by_metric)
    assert not benchwatch.validate_record(orec), orec
    assert orec["value"] == occ["busy_frac"], (orec, occ["busy_frac"])
    for cause in ("host_prep", "queue_starved", "settle_serialized",
                  "drain"):
        assert f"pipeline::bubble@{cause}" in by_metric, sorted(by_metric)
    print(f"serve history OK: {len(fresh)} records this run "
          f"(incl. {sum(1 for m in by_metric if m.startswith('latency::'))} "
          f"latency:: records)")

    # the serve pipeline's gauges ride the Chrome trace as 'C' counter
    # tracks (queue depth + in-flight batches breathing against the
    # span timeline, same mechanism as device_memory_bytes)
    trace = json.loads(serve_trace.read_text())
    counter_names = {e["name"] for e in trace["traceEvents"]
                     if e.get("ph") == "C"}
    assert "serve.queue_depth" in counter_names, sorted(counter_names)
    assert "serve.inflight_batches" in counter_names, sorted(counter_names)
    assert any(n.startswith("pipeline.device_busy.")
               for n in counter_names), sorted(counter_names)
    span_names = {e["name"] for e in trace["traceEvents"]
                  if e.get("ph") == "X"}
    assert "serve.pump" in span_names, sorted(span_names)
    # request-tracing flow events: every served kind must have at least
    # one submit→…→settle flow arrow ('s' and matching 'f' by id), and
    # request/batch lifecycle spans ride the per-kind request tracks
    flow_s = [e for e in trace["traceEvents"] if e.get("ph") == "s"]
    flow_f = [e for e in trace["traceEvents"] if e.get("ph") == "f"]
    s_names = {e["name"] for e in flow_s}
    for kind in sorted(served_kinds):
        assert f"req.{kind}" in s_names, (kind, sorted(s_names))
    s_ids = {e["id"] for e in flow_s}
    f_ids = {e["id"] for e in flow_f}
    assert s_ids and s_ids == f_ids, (len(s_ids), len(f_ids))
    assert any(n.startswith("req.") for n in span_names), span_names
    assert any(n.startswith("batch.") for n in span_names), span_names
    print(f"serve trace OK: gauge counter tracks + {len(flow_s)} "
          f"request flow arrows -> {serve_trace}")

    # the report renders the Tail latency section from the latency::*
    # records; the serve-p99-queue-frac advisory row stays TPU-gated
    # ('no data' on this CPU round)
    from consensus_specs_tpu.telemetry import report as bw_report

    serve_report = HERE / "out" / "smoke_serve_report.md"
    rc = bw_report.main(["--repo", str(HERE), "--history",
                         str(hist_file), "--out", str(serve_report),
                         "--no-update"])
    assert rc == 0, f"benchwatch report exited {rc}"
    text = serve_report.read_text()
    assert "## Tail latency (request tracing)" in text, text[:2000]
    assert "`verify`" in text and "Worst exemplar traces:" in text
    result = bw_report.build_report(
        repo=HERE, history_path=hist_file, snapshots=[],
        durations_path=None, top_n=5, strict=False,
        max_regress_pct=0.0, update_history=False)
    rows = {t["id"]: t for t in result["thresholds"]}
    assert rows["serve-p99-queue-frac"]["status"] == "no data", \
        rows["serve-p99-queue-frac"]
    # the watchdog section renders and the clean-round row gates green
    # on this zero-breach round
    assert "## SLO (live watchdog)" in text, text[:2000]
    assert rows["slo-clean-round"]["status"] == "PASS", \
        rows["slo-clean-round"]
    # the occupancy section renders from the pipeline:: records; the
    # serve-occupancy floor stays TPU-gated on this CPU round
    assert "## Pipeline occupancy" in text, text[:2000]
    assert rows["serve-occupancy"]["status"] == "no data", \
        rows["serve-occupancy"]
    print(f"tail-latency report OK: section rendered, TPU-gated "
          f"queue-frac row reads 'no data' on CPU, slo-clean-round "
          f"PASS -> {serve_report}")

    # telemetry-OFF contract: the default path (what a non-telemetry
    # TPU round runs) must emit the plain 2-metric lines — no
    # "telemetry" key, no probe.  Same shapes as the run above, so the
    # persistent compile cache makes this re-run cheap.
    out = _run(["bench_bls.py"],
               {"CST_BLS_BENCH_N": "2", "CST_BLS_BENCH_COMMITTEE": "2",
                "CST_BLS_BENCH_SYNC": "4",
                "CST_TELEMETRY": "", "CST_TRACE_FILE": "",
                "CST_COSTMODEL": ""},
               timeout=1800)
    metrics = [o for o in out if "metric" in o]
    assert len(metrics) == 2, out
    for m in metrics:
        assert {"metric", "value", "unit", "vs_baseline"} <= set(m), m
        assert "telemetry" not in m, m
    print("bench_bls.py telemetry-off JSON OK:", json.dumps(metrics))
    print("bench smoke: PASS")


def chaos_main(mesh: bool = False):
    """The chaos-smoke lane (see module docstring): one bench_serve.py
    chaos round on tiny CPU shapes under a canned fault plan, then the
    resilience record/report contract checks.  `mesh=True` (the
    chaos-mesh lane) additionally arms the simulated-mesh shard-loss
    segment and asserts its contract."""
    from consensus_specs_tpu.telemetry import validate_resilience_block

    hist_env = os.environ.get("CST_BENCHWATCH_HISTORY")
    hist_file = Path(hist_env) if hist_env \
        else HERE / "out" / "smoke_chaos_history.jsonl"
    hist_file.parent.mkdir(exist_ok=True)
    if not hist_env and hist_file.exists():
        hist_file.unlink()
    chaos_slo_file = HERE / "out" / "chaos_slo_breaches.json"
    if chaos_slo_file.exists():
        chaos_slo_file.unlink()
    incidents_dir = HERE / "out" / "smoke_incidents"
    if incidents_dir.exists():
        import shutil
        shutil.rmtree(incidents_dir)
    chaos_t0 = time.time()
    # the canned plan: deterministic dispatch failures into the RLC
    # verify kernel (the acceptance shape — resilience.chaos's default,
    # spelled out here so the smoke pins the spec-string form too).
    # `key=rlc_h*` matches the single-chip RLC kernels (rlc_h2c /
    # rlc_host_hash) but NOT rlc_sharded@… — the mesh segment owns its
    # own device_loss plan and must not eat the serve round's faults.
    env = {"CST_SERVE_CHAOS": "1",
           "CST_FAULTS": "seed=1234;dispatch:raise:key=rlc_h*:count=4",
           "CST_SERVE_DURATION_S": "9", "CST_SERVE_RATE": "0",
           "CST_SERVE_POOL": "4", "CST_SERVE_COMMITTEE": "4",
           "CST_SERVE_MAX_BATCH": "8", "CST_SERVE_WINDOWS": "3",
           "CST_TELEMETRY": "1",
           "CST_FLIGHTREC_ON_BREACH": "1",
           "CST_FLIGHTREC_DIR": str(incidents_dir),
           "CST_BENCHWATCH_HISTORY": str(hist_file)}
    if mesh:
        env["CST_CHAOS_MESH"] = "1"
        env.setdefault(
            "XLA_FLAGS", os.environ.get("XLA_FLAGS")
            or "--xla_force_host_platform_device_count=8")
    out = _run(["bench_serve.py"], env, timeout=1800 if mesh else 1200)
    lines = [o for o in out if o.get("metric") == "serve_sustained_load"]
    assert len(lines) == 1, out
    sl = lines[0]
    assert "error" not in sl, sl.get("error")
    res = sl.get("resilience")
    problems = validate_resilience_block(res)
    assert not problems, (problems, json.dumps(res)[:500])
    # the acceptance arc: faults fired, zero wrong answers, the breaker
    # tripped into oracle-fallback degraded mode and re-closed, the
    # service returned to steady state with a finite recovery latency,
    # and the diverged Merkle forest healed back to the oracle root
    assert res["faults_injected"] >= 1, res
    assert res["injected_sites"].get("dispatch", 0) >= 1, res
    assert res["wrong_results"] == 0, res
    assert res["failed_requests"] == 0, res
    assert res["checked_results"] > 0, res
    assert res["fallbacks"] >= 1 and res["retries"] >= 1, res
    br = res["breaker"]
    assert br["trips"] >= 1, br
    tos = [t["to"] for t in br["transitions"]]
    assert "open" in tos and "half_open" in tos and "closed" in tos, br
    # every breaker that saw post-fault traffic re-closed — usually via
    # the half-open probe (half_open → closed), but a batch dispatched
    # BEFORE the trip that settles successfully after it closes the
    # breaker directly (open → closed): the pipeline keeps `depth`
    # batches in flight, and their success is real device health.  A
    # rung the closed-loop batching never revisited after the fault
    # window keeps its open breaker (no probe traffic) — that is not a
    # failed recovery, which the recovery-latency/steady asserts pin
    reclosed = [t["key"] for t in br["transitions"]
                if t["to"] == "closed"
                and t["from"] in ("half_open", "open")]
    assert reclosed, br
    assert any(s == "closed" for s in br["states"].values()), br
    assert res["recovered"] and res["recovery_latency_s"] is not None, res
    assert 0 < res["recovery_latency_s"] < 300, res
    assert res["heal"]["diverged"] and res["heal"]["detected"], res
    assert res["heal"]["recovery_s"] > 0, res
    # the heal routed through checkpoint restore (snapshot valid), not
    # the O(N) rebuild floor
    assert res["heal"]["path"] == "checkpoint", res["heal"]
    # checkpoint kill-and-resurrect: root parity held and restore+replay
    # beat the full rebuild (the >=5x gate is the threshold row below)
    cp = res["checkpoint"]
    assert cp["parity"], cp
    assert cp["restore_s"] > 0 and cp["rebuild_s"] > 0, cp
    assert cp["journal_entries"] >= 1 and cp["snapshot_bytes"] > 0, cp
    assert cp["journal_frac"] <= 0.01, cp
    assert cp["speedup"] is not None and cp["speedup"] >= 5.0, cp
    # flagship breaker arc: the settle degraded onto the spec oracle
    # (trip + open settle), answered correctly, and re-closed
    fl = res["flagship"]
    assert fl["degraded_steps"] >= 2, fl
    assert fl["wrong_results"] == 0 and fl["checked_settles"] >= 4, fl
    assert fl["recovered"], fl
    assert fl["breaker"]["trips"] >= 1, fl
    serve = sl["serve"]
    assert serve["steady"], serve["windows"]
    assert serve["failed"] == 0, serve
    # request tracing is armed for every chaos round: per-request
    # latency semantics plus the fault→victim correlation — the blast
    # radius must be exactly the retried/fallback-answered/poisoned
    # handles (a fault victim can never settle with a clean 'ok')
    from consensus_specs_tpu.telemetry import validate_latency_attribution
    assert serve.get("latency_source") == "reqtrace", serve.get(
        "latency_source")
    la = serve.get("latency_attribution")
    assert not validate_latency_attribution(la), la
    assert "verify" in la["kinds"], sorted(la["kinds"])
    fv = res["fault_victims"]
    assert fv["count"] >= 1, fv
    assert fv["trace_ids"], fv
    assert fv["clean_ok"] == 0, fv
    assert sum(fv["outcomes"].values()) == fv["count"], fv
    assert set(fv["outcomes"]) <= {"retry", "fallback", "poisoned",
                                   "recheck", "timeout"}, fv
    # the arc recovered every victim: zero poisoned handles (matches
    # failed_requests == 0 above)
    assert fv["outcomes"].get("poisoned", 0) == 0, fv
    print("fault victims OK:", json.dumps(fv["outcomes"]),
          f"({fv['count']} victim(s))")
    # the SLO watchdog's deterministic chaos arc: the injected-fault
    # counter rule breached while the plan was live and the breach
    # CLEARED after recovery — the transition proven in both directions
    from consensus_specs_tpu.telemetry import validate_slo_block
    slo = serve.get("slo")
    assert slo is not None, "chaos round must arm the SLO watchdog"
    assert not validate_slo_block(slo), validate_slo_block(slo)
    assert slo["ticks"] > 0, slo
    assert slo["breaches"] >= 1 and not slo["clean"], slo
    assert any(r["name"] == "chaos-fault-injections"
               for r in slo["rules"]), slo["rules"]
    arc = res["slo_arc"]
    assert arc["rule"] == "chaos-fault-injections", arc
    assert arc["breached_in_fault_window"], arc
    assert arc["cleared_after_recovery"], arc
    # the breach evidence artifact landed (the CI upload)
    assert chaos_slo_file.exists(), chaos_slo_file
    slo_art = json.loads(chaos_slo_file.read_text())["slo"]
    assert slo_art["breaches"] >= 1, slo_art
    print(f"slo chaos arc OK: {slo['breaches']} breach(es) over "
          f"{slo['ticks']} tick(s), breach->clear both ways, "
          f"evidence -> {chaos_slo_file}")

    # incident flight-recorder arc (CST_FLIGHTREC_ON_BREACH=1): each
    # breached rule froze exactly ONE self-contained bundle — the fault
    # plan, the breach events, the breaker arc, and the exemplars must
    # all be readable from the bundle directory alone (plain json, no
    # live process) so a post-mortem needs nothing but the CI artifact
    from consensus_specs_tpu.telemetry import flightrec
    breached_rules = {r["name"] for r in slo["rules"]
                      if r["breaches"] >= 1}
    incidents = slo["incidents"]
    assert len(incidents) == len(breached_rules) >= 1, \
        (incidents, breached_rules)
    dumped_rules = set()
    for inc in incidents:
        bundle = Path(inc)
        if not bundle.is_absolute():
            bundle = HERE / bundle
        assert bundle.is_dir(), bundle
        manifest = json.loads((bundle / "manifest.json").read_text())
        problems = flightrec.validate_manifest(manifest)
        assert not problems, (problems, bundle)
        assert manifest["rule"] in breached_rules, manifest
        dumped_rules.add(manifest["rule"])
        fp = manifest["fault_plan"]
        assert fp is not None and fp["seed"] == 1234 and fp["faults"], fp
        events = [json.loads(ln) for ln in
                  (bundle / "events.jsonl").read_text().splitlines()]
        kinds = {e["kind"] for e in events}
        assert "slo_breach" in kinds, sorted(kinds)
        assert "fault_injected" in kinds, sorted(kinds)
        # the breaker arc up to the freeze: the chaos trip is in the ring
        trips = [e for e in events if e["kind"] == "breaker_transition"]
        assert any(e["to"] == "open" for e in trips), sorted(kinds)
        exemplars = json.loads((bundle / "exemplars.json").read_text())
        assert "worst" in exemplars, bundle
        json.loads((bundle / "state.json").read_text())
    assert dumped_rules == breached_rules, (dumped_rules, breached_rules)
    print(f"incident bundles OK: {len(incidents)} bundle(s) for "
          f"breached rule(s) {sorted(breached_rules)} -> {incidents_dir}")
    if mesh:
        mb = res["mesh"]
        assert "skipped" not in mb, mb
        assert mb["devices"] >= 2, mb
        assert mb["device_lost_events"] >= 1, mb
        assert mb["redispatches"] >= 1, mb
        assert mb["readmissions"] >= 1 and mb["readmitted"], mb
        assert mb["lost_statements"] == 0, mb
        assert mb["wrong_results"] == 0 and mb["checked_statements"] > 0, mb
        assert mb["recovery_latency_s"] is not None, mb
        assert mb["max_degraded_lanes"] >= 1, mb
        assert mb["recovered"], mb
        print("mesh segment OK:", json.dumps(mb))
    print("chaos round OK:", json.dumps(
        {k: res[k] for k in ("faults_injected", "wrong_results",
                             "fallbacks", "retries",
                             "recovery_latency_s",
                             "degraded_verifies_per_s",
                             "baseline_verifies_per_s")}))
    print("checkpoint segment OK:", json.dumps(cp))
    print("flagship segment OK:", json.dumps(
        {k: fl[k] for k in ("degraded_steps", "wrong_results",
                            "recovered")}))

    # resilience history round-trip: the emission lands as resilience-
    # source records, schema-valid, with the compact block riding the
    # recovery-latency record
    hist_records, _, _ = benchwatch.load_history(hist_file)
    fresh = {r["metric"]: r for r in hist_records
             if isinstance(r.get("ts"), (int, float))
             and r["ts"] >= chaos_t0 - 5}
    for name in ("resilience::recovery_latency_s",
                 "resilience::wrong_results",
                 "resilience::degraded_verifies_per_s",
                 "resilience::faults_injected",
                 "resilience::breaker_transitions",
                 "resilience::merkle_heal_s"):
        rec = fresh.get(name)
        assert rec is not None, (name, sorted(fresh))
        assert rec["source"] == "resilience", rec
        assert not benchwatch.validate_record(rec), rec
    rrec = fresh["resilience::recovery_latency_s"]
    assert rrec["value"] > 0 and rrec["resilience"]["recovered"], rrec
    assert fresh["resilience::wrong_results"]["value"] == 0
    # the fault-victim correlation rides the compact resilience block
    assert rrec["resilience"]["fault_victims"]["count"] >= 1, rrec
    # the chaos round's traced latency records land too
    lrec = fresh.get("latency::p99_ms@verify")
    assert lrec is not None and lrec["source"] == "latency", \
        sorted(fresh)
    assert not benchwatch.validate_record(lrec), lrec
    # the heal record carries the taken recovery path
    assert fresh["resilience::merkle_heal_s"]["heal_path"] == "checkpoint"
    # the checkpoint record kind round-trips: restore wall with the
    # restore-vs-rebuild speedup riding as vs_baseline
    crec = fresh.get("checkpoint::restore")
    assert crec is not None, sorted(fresh)
    assert crec["source"] == "checkpoint", crec
    assert not benchwatch.validate_record(crec), crec
    assert crec["value"] > 0 and crec["vs_baseline"] >= 5.0, crec
    assert crec["checkpoint"]["parity"], crec
    for name in ("checkpoint::journal_entries",
                 "checkpoint::snapshot_bytes"):
        rec = fresh.get(name)
        assert rec is not None and rec["source"] == "checkpoint", \
            (name, sorted(fresh))
    # the flagship degraded-steps record
    frec = fresh.get("resilience::flagship_degraded_steps")
    assert frec is not None and frec["value"] >= 2, frec
    assert frec["flagship"]["wrong_results"] == 0, frec
    # the SLO arc record the chaos-slo-arc row gates on, plus the
    # per-rule breach count; a breaching round must NOT mint the
    # clean-round record (that gate is for quiet rounds only)
    arec = fresh.get("resilience::slo_arc_ok")
    assert arec is not None and arec["value"] == 1.0, arec
    assert not benchwatch.validate_record(arec), arec
    srec = fresh.get("slo::breaches@chaos-fault-injections")
    assert srec is not None and srec["value"] >= 1, sorted(fresh)
    assert "slo::clean_round" not in fresh, fresh["slo::clean_round"]
    if mesh:
        for name in ("mesh::recovery_latency_s", "mesh::recovered",
                     "mesh::lost_statements",
                     "mesh::wrong_results", "mesh::degraded_lanes",
                     "mesh::device_lost_events", "mesh::readmissions"):
            rec = fresh.get(name)
            assert rec is not None, (name, sorted(fresh))
            assert rec["source"] == "mesh", rec
            assert not benchwatch.validate_record(rec), rec
        mrec = fresh["mesh::recovery_latency_s"]
        assert mrec["value"] is not None and mrec["value"] > 0, mrec
        assert mrec["mesh"]["device_lost_events"] >= 1, mrec
        assert fresh["mesh::lost_statements"]["value"] == 0
        assert fresh["mesh::wrong_results"]["value"] == 0
        assert fresh["mesh::recovered"]["value"] == 1.0
    print(f"resilience history OK: {len(fresh)} records this run -> "
          f"{hist_file}")

    # the report renders the Resilience section and evaluates the
    # chaos-recovery / chaos-correctness threshold rows from the store
    from consensus_specs_tpu.telemetry import report as bw_report

    report_md = HERE / "out" / "smoke_chaos_report.md"
    rc = bw_report.main(["--repo", str(HERE), "--history", str(hist_file),
                         "--out", str(report_md), "--no-update"])
    assert rc == 0, f"benchwatch report exited {rc}"
    text = report_md.read_text()
    assert "## Resilience (chaos rounds)" in text, text[:2000]
    assert "`resilience::recovery_latency_s`" in text
    assert "Latest chaos round:" in text
    assert "Blast radius (request tracing):" in text
    assert "## Tail latency (request tracing)" in text, text[:2000]
    result = bw_report.build_report(
        repo=HERE, history_path=hist_file, snapshots=[],
        durations_path=None, top_n=5, strict=False,
        max_regress_pct=0.0, update_history=False)
    rows = {t["id"]: t for t in result["thresholds"]}
    assert rows["chaos-recovery"]["status"] == "PASS", rows["chaos-recovery"]
    assert rows["chaos-recovered"]["status"] == "PASS", \
        rows["chaos-recovered"]
    assert rows["chaos-correctness"]["status"] == "PASS", \
        rows["chaos-correctness"]
    assert rows["checkpoint-restore"]["status"] == "PASS", \
        rows["checkpoint-restore"]
    assert rows["chaos-slo-arc"]["status"] == "PASS", rows["chaos-slo-arc"]
    assert "## SLO (live watchdog)" in text, text[:2000]
    assert "Latest checkpoint restore:" in text
    if mesh:
        for row_id in ("mesh-recovered", "mesh-recovery",
                       "mesh-lost-statements", "mesh-wrong-results"):
            assert rows[row_id]["status"] == "PASS", rows[row_id]
        assert "Latest mesh segment:" in text
        print("mesh report OK: mesh-recovered + mesh-recovery + "
              "mesh-lost-statements + mesh-wrong-results PASS")
    print(f"chaos report OK: chaos-recovery + chaos-correctness + "
          f"checkpoint-restore PASS -> {report_md}")
    print("chaos smoke: PASS")


def shard_main():
    """The shard-smoke lane (`make shard-smoke` / CI): a tiny
    mesh-sharded flagship scaling round on the simulated 8-host-device
    mesh, asserting the `"scaling"` block schema, the `scaling::*`
    history-record round-trip, and the benchwatch report's Scaling
    section + threshold rows ('no data' on CPU — the
    scaling-efficiency / flagship-8m gates are TPU acceptance
    criteria, so the smoke pins the plumbing, not the number)."""
    from consensus_specs_tpu.telemetry import validate_scaling_block

    hist_env = os.environ.get("CST_BENCHWATCH_HISTORY")
    hist_file = Path(hist_env) if hist_env \
        else HERE / "out" / "smoke_shard_history.jsonl"
    hist_file.parent.mkdir(exist_ok=True)
    if not hist_env and hist_file.exists():
        hist_file.unlink()
    shard_t0 = time.time()
    out = _run(["bench.py", "--worker", "scaling"],
               {"CST_SHARD_RUNGS": "4096,8192", "CST_SHARD_ITERS": "2",
                "CST_TELEMETRY": "1",
                "XLA_FLAGS": os.environ.get("XLA_FLAGS")
                or "--xla_force_host_platform_device_count=8"},
               timeout=900)
    last = out[-1]
    fs = last.get("flagship_scaling")
    assert isinstance(fs, dict) and fs.get("value", 0) > 0, last
    assert fs["unit"] == "validators/s/chip", fs
    block = fs.get("scaling")
    problems = validate_scaling_block(block)
    assert not problems, (problems, json.dumps(block)[:500])
    assert block["n_devices"] == 8, block
    assert len(block["rungs"]) == 2, block
    for rung in block["rungs"]:
        assert rung["n_devices"] == 8 and rung["wall_s"] > 0, rung
        assert 0 < rung["efficiency"], rung
    # no 8M rung attempted at smoke shapes: the flagship-8m gate must
    # read 'no data', not a stale PASS/FAIL
    assert block["ok_8m"] is None, block
    _check_telemetry(fs, "scaling worker")
    print("scaling worker JSON OK:", json.dumps(
        {k: v for k, v in fs.items() if k != "telemetry"}))

    # the scaling record kind round-trips through the store: per-rung
    # flagship + efficiency records and the efficiency summary, all
    # schema-valid, cpu-stamped, mined from the ONE metric line (the
    # parent appends, like the driver does for extras workers)
    prev_hist = os.environ.get("CST_BENCHWATCH_HISTORY")
    os.environ["CST_BENCHWATCH_HISTORY"] = str(hist_file)
    try:
        benchwatch.append_emission(
            dict(fs, metric="flagship_scaling",
                 platform=last.get("platform", "cpu")),
            ts=time.time())
    finally:
        if prev_hist is None:
            os.environ.pop("CST_BENCHWATCH_HISTORY", None)
        else:
            os.environ["CST_BENCHWATCH_HISTORY"] = prev_hist
    hist_records, skipped, warns = benchwatch.load_history(hist_file)
    fresh = {r["metric"]: r for r in hist_records
             if isinstance(r.get("ts"), (int, float))
             and r["ts"] >= shard_t0 - 5}
    for name in ("flagship_scaling", "scaling::flagship@4096",
                 "scaling::flagship@8192", "scaling::efficiency@4096",
                 "scaling::efficiency@8192", "scaling::efficiency"):
        rec = fresh.get(name)
        assert rec is not None, (name, sorted(fresh))
        assert not benchwatch.validate_record(rec), rec
        assert rec["platform"] == "cpu", rec
        if name.startswith("scaling::"):
            assert rec["source"] == "scaling", rec
    srec = fresh["scaling::flagship@8192"]
    assert srec["scaling"]["n_devices"] == 8, srec
    assert srec["value"] > 0, srec
    # the summary efficiency record carries the LARGEST rung's block
    erec = fresh["scaling::efficiency"]
    assert erec["scaling"]["n_validators"] == 8192, erec
    assert "scaling::flagship_8m_ok" not in fresh, sorted(fresh)
    print(f"scaling history OK: {len(fresh)} records this run -> "
          f"{hist_file}")

    # the report renders the Scaling section (per-n_devices trend
    # table) and the TPU-gated threshold rows read 'no data' on CPU
    from consensus_specs_tpu.telemetry import report as bw_report

    report_md = HERE / "out" / "smoke_shard_report.md"
    rc = bw_report.main(["--repo", str(HERE), "--history",
                         str(hist_file), "--out", str(report_md),
                         "--no-update"])
    assert rc == 0, f"benchwatch report exited {rc}"
    text = report_md.read_text()
    assert "## Scaling (mesh-sharded flagship)" in text, text[:2000]
    assert "| 8192 | 8 |" in text, text
    assert "Latest full-mesh efficiency:" in text
    result = bw_report.build_report(
        repo=HERE, history_path=hist_file, snapshots=[],
        durations_path=None, top_n=5, strict=False,
        max_regress_pct=0.0, update_history=False)
    rows = {t["id"]: t for t in result["thresholds"]}
    assert rows["scaling-efficiency"]["status"] == "no data", \
        rows["scaling-efficiency"]
    assert rows["flagship-8m"]["status"] == "no data", rows["flagship-8m"]
    print(f"shard report OK: Scaling section rendered, TPU-gated rows "
          f"read 'no data' on CPU -> {report_md}")
    print("shard smoke: PASS")


def das_main():
    """The das-smoke lane (`make das-smoke` / CI): the PeerDAS
    cell-proof sweep at the 128x8 sampling matrix on CPU, asserting
    the `"das"` block schema, the `das::*` history-record round-trip,
    the report's DAS section render, and the threshold-row wiring —
    `das-speedup` must PASS on CPU (the >= 2x acceptance criterion is
    shape-bound: the oracle pays a per-cell Lagrange interpolation the
    device route never does), `das-throughput` must read 'no data'
    (a chip number).  The same worker run also covers the FK20
    producer + damaged-matrix recover round: the `"das_producer"`
    block schema, byte-parity vs the closed form, the >= 4x
    `das-producer-speedup` floor vs the D_u MSM route and the >= 2x
    `das-recover-speedup` floor vs the pure-Python recover oracle —
    both shape-bound, so they PASS on CPU too."""
    from consensus_specs_tpu.telemetry import (validate_das_block,
                                               validate_das_producer_block)

    hist_env = os.environ.get("CST_BENCHWATCH_HISTORY")
    hist_file = Path(hist_env) if hist_env \
        else HERE / "out" / "smoke_das_history.jsonl"
    hist_file.parent.mkdir(exist_ok=True)
    if not hist_env and hist_file.exists():
        hist_file.unlink()
    das_t0 = time.time()
    out = _run(["bench.py", "--worker", "das"],
               {"CST_DAS_MATRIX": "128x8", "CST_DAS_ORACLE_CELLS": "8",
                "CST_DAS_PRODUCE_ITERS": "1", "CST_DAS_DU_MSMS": "1",
                "CST_DAS_RECOVER_ORACLE_COSETS": "1",
                "CST_TELEMETRY": "1"},
               timeout=3600)
    last = out[-1]
    rec = last.get("das_cell_proof_batch_128x8_verify_wall")
    assert isinstance(rec, dict) and rec.get("value", 0) > 0, last
    block = rec.get("das")
    problems = validate_das_block(block)
    assert not problems, (problems, json.dumps(block)[:500])
    assert block["matrix"] == {"columns": 128, "blobs": 8,
                               "cells": 1024}, block
    assert block["rung"] == 1024, block
    # the acceptance criterion: >= 2x over the pure-Python oracle at
    # the 128x8 matrix, on this CPU
    assert block["speedup"] >= 2.0, block
    assert rec["vs_baseline"] == block["speedup"], rec
    # the mixed-invalid arc isolated exactly the bad cell, and the
    # coset-barycentric evaluation cross-check agreed
    assert block["isolate"]["isolated"] is True, block
    assert block["eval_crosscheck"] is True, block
    _check_telemetry(rec, "das worker")
    print("das worker JSON OK:", json.dumps(
        {k: v for k, v in rec.items() if k != "telemetry"}))

    # the FK20 producer + damaged-matrix recover round: block schema,
    # byte-parity/roundtrip, and the two CPU-evaluable speedup floors
    prec = last.get("das_fk20_produce_wall")
    assert isinstance(prec, dict) and prec.get("value", 0) > 0, last
    pblock = prec.get("das_producer")
    problems = validate_das_producer_block(pblock)
    assert not problems, (problems, json.dumps(pblock)[:500])
    assert pblock["parity"] is True, pblock
    # the acceptance criteria: >= 4x vs the D_u MSM route for the
    # producer, >= 2x vs the pure-Python oracle for recovery
    assert pblock["producer_speedup"] >= 4.0, pblock
    assert prec["vs_baseline"] == pblock["producer_speedup"], prec
    assert pblock["recover"]["roundtrip"] is True, pblock
    assert pblock["recover"]["speedup"] >= 2.0, pblock
    print("das producer JSON OK:", json.dumps(
        {k: v for k, v in prec.items() if k != "telemetry"}))

    # the das record kind round-trips through the store (the parent
    # appends, like the driver does for extras workers)
    prev_hist = os.environ.get("CST_BENCHWATCH_HISTORY")
    os.environ["CST_BENCHWATCH_HISTORY"] = str(hist_file)
    try:
        benchwatch.append_emission(
            dict(rec, metric="das_cell_proof_batch_128x8_verify_wall",
                 platform=last.get("platform", "cpu")),
            ts=time.time())
        benchwatch.append_emission(
            dict(prec, metric="das_fk20_produce_wall",
                 platform=last.get("platform", "cpu")),
            ts=time.time())
    finally:
        if prev_hist is None:
            os.environ.pop("CST_BENCHWATCH_HISTORY", None)
        else:
            os.environ["CST_BENCHWATCH_HISTORY"] = prev_hist
    hist_records, skipped, warns = benchwatch.load_history(hist_file)
    fresh = {r["metric"]: r for r in hist_records
             if isinstance(r.get("ts"), (int, float))
             and r["ts"] >= das_t0 - 5}
    for name in ("das_cell_proof_batch_128x8_verify_wall",
                 "das::verify_wall@128x8", "das::speedup",
                 "das::cells_per_s",
                 "das_fk20_produce_wall", "das::produce_wall",
                 "das::producer_speedup", "das::proofs_per_s",
                 "das::recover_wall", "das::recover_speedup"):
        hrec = fresh.get(name)
        assert hrec is not None, (name, sorted(fresh))
        assert not benchwatch.validate_record(hrec), hrec
        assert hrec["platform"] == "cpu", hrec
        if name.startswith("das::"):
            assert hrec["source"] == "das", hrec
    wrec = fresh["das::verify_wall@128x8"]
    assert wrec["das"]["matrix"]["cells"] == 1024, wrec
    assert wrec["vs_baseline"] >= 2.0, wrec
    pwrec = fresh["das::produce_wall"]
    assert pwrec["das_producer"]["parity"] is True, pwrec
    assert pwrec["vs_baseline"] >= 4.0, pwrec
    rwrec = fresh["das::recover_wall"]
    assert rwrec["das_recover"]["roundtrip"] is True, rwrec
    assert rwrec["vs_baseline"] >= 2.0, rwrec
    print(f"das history OK: {len(fresh)} records this run -> "
          f"{hist_file}")

    # the report renders the DAS section and the threshold rows wire
    # up: das-speedup PASSes from the CPU record, das-throughput (a
    # chip number) reads 'no data'
    from consensus_specs_tpu.telemetry import report as bw_report

    report_md = HERE / "out" / "smoke_das_report.md"
    rc = bw_report.main(["--repo", str(HERE), "--history",
                         str(hist_file), "--out", str(report_md),
                         "--no-update"])
    assert rc == 0, f"benchwatch report exited {rc}"
    text = report_md.read_text()
    assert "## DAS (PeerDAS cell-proof sampling)" in text, text[:2000]
    assert "| 128x8 | 1024 |" in text, text
    assert "Latest speedup over the pure-Python oracle:" in text
    assert "FK20 producer:" in text, text
    assert "Erasure recovery:" in text, text
    assert "Latest producer throughput:" in text, text
    result = bw_report.build_report(
        repo=HERE, history_path=hist_file, snapshots=[],
        durations_path=None, top_n=5, strict=False,
        max_regress_pct=0.0, update_history=False)
    rows = {t["id"]: t for t in result["thresholds"]}
    assert rows["das-speedup"]["status"] == "PASS", rows["das-speedup"]
    assert rows["das-producer-speedup"]["status"] == "PASS", \
        rows["das-producer-speedup"]
    assert rows["das-recover-speedup"]["status"] == "PASS", \
        rows["das-recover-speedup"]
    assert rows["das-throughput"]["status"] == "no data", \
        rows["das-throughput"]
    print(f"das report OK: DAS section rendered, das-speedup + "
          f"das-producer-speedup + das-recover-speedup PASS, "
          f"TPU-gated das-throughput reads 'no data' on CPU -> "
          f"{report_md}")
    print("das smoke: PASS")


def forkchoice_main():
    """The fc-smoke lane (`make fc-smoke` / CI): the device LMD-GHOST
    sweep on a tiny CPU tree, asserting the `"forkchoice"` block
    schema, the >= 2x `fc-speedup` acceptance vs the phase0 spec
    oracle (shape-bound: the oracle walks every active validator per
    child in pure Python), bit-exact head parity, the `forkchoice::*`
    history-record round-trip, and the report's Fork choice section —
    `fc-speedup` must PASS on CPU, `fc-head-throughput` (a chip
    number) must read 'no data'."""
    from consensus_specs_tpu.telemetry import validate_forkchoice_block

    hist_env = os.environ.get("CST_BENCHWATCH_HISTORY")
    hist_file = Path(hist_env) if hist_env \
        else HERE / "out" / "smoke_fc_history.jsonl"
    hist_file.parent.mkdir(exist_ok=True)
    if not hist_env and hist_file.exists():
        hist_file.unlink()
    fc_t0 = time.time()
    out = _run(["bench.py", "--worker", "forkchoice"],
               {"CST_FC_MATRIX": "64x1024",
                "CST_FC_ORACLE_VALIDATORS": "256",
                "CST_TELEMETRY": "1"},
               timeout=900)
    last = out[-1]
    rec = last.get("forkchoice_lmd_ghost_64x1024_head_wall")
    assert isinstance(rec, dict) and rec.get("value", 0) > 0, last
    block = rec.get("forkchoice")
    problems = validate_forkchoice_block(block)
    assert not problems, (problems, json.dumps(block)[:500])
    assert block["tree"]["blocks"] == 64, block
    assert block["tree"]["validators"] == 1024, block
    assert block["rungs"]["blocks"] == 64, block
    # the acceptance criteria: >= 2x over the spec oracle on this CPU,
    # with the device head bit-identical to the oracle's
    assert block["speedup"] >= 2.0, block
    assert block["parity"] is True, block
    assert rec["vs_baseline"] == block["speedup"], rec
    _check_telemetry(rec, "forkchoice worker")
    print("forkchoice worker JSON OK:", json.dumps(
        {k: v for k, v in rec.items() if k != "telemetry"}))

    # the forkchoice record kind round-trips through the store (the
    # parent appends, like the driver does for extras workers)
    prev_hist = os.environ.get("CST_BENCHWATCH_HISTORY")
    os.environ["CST_BENCHWATCH_HISTORY"] = str(hist_file)
    try:
        benchwatch.append_emission(
            dict(rec, metric="forkchoice_lmd_ghost_64x1024_head_wall",
                 platform=last.get("platform", "cpu")),
            ts=time.time())
    finally:
        if prev_hist is None:
            os.environ.pop("CST_BENCHWATCH_HISTORY", None)
        else:
            os.environ["CST_BENCHWATCH_HISTORY"] = prev_hist
    hist_records, skipped, warns = benchwatch.load_history(hist_file)
    fresh = {r["metric"]: r for r in hist_records
             if isinstance(r.get("ts"), (int, float))
             and r["ts"] >= fc_t0 - 5}
    for name in ("forkchoice_lmd_ghost_64x1024_head_wall",
                 "forkchoice::head_wall@64x1024", "forkchoice::speedup",
                 "forkchoice::heads_per_s"):
        hrec = fresh.get(name)
        assert hrec is not None, (name, sorted(fresh))
        assert not benchwatch.validate_record(hrec), hrec
        assert hrec["platform"] == "cpu", hrec
        if name.startswith("forkchoice::"):
            assert hrec["source"] == "forkchoice", hrec
    wrec = fresh["forkchoice::head_wall@64x1024"]
    assert wrec["forkchoice"]["tree"]["blocks"] == 64, wrec
    assert wrec["vs_baseline"] >= 2.0, wrec
    print(f"forkchoice history OK: {len(fresh)} records this run -> "
          f"{hist_file}")

    # the report renders the Fork choice section and the threshold
    # rows wire up: fc-speedup PASSes from the CPU record,
    # fc-head-throughput (a chip number) reads 'no data'
    from consensus_specs_tpu.telemetry import report as bw_report

    report_md = HERE / "out" / "smoke_fc_report.md"
    rc = bw_report.main(["--repo", str(HERE), "--history",
                         str(hist_file), "--out", str(report_md),
                         "--no-update"])
    assert rc == 0, f"benchwatch report exited {rc}"
    text = report_md.read_text()
    assert "## Fork choice (device LMD-GHOST)" in text, text[:2000]
    assert "| 64x1024 |" in text, text
    assert "Latest head speedup over the phase0 spec oracle:" in text
    result = bw_report.build_report(
        repo=HERE, history_path=hist_file, snapshots=[],
        durations_path=None, top_n=5, strict=False,
        max_regress_pct=0.0, update_history=False)
    rows = {t["id"]: t for t in result["thresholds"]}
    assert rows["fc-speedup"]["status"] == "PASS", rows["fc-speedup"]
    assert rows["fc-head-throughput"]["status"] == "no data", \
        rows["fc-head-throughput"]
    print(f"forkchoice report OK: Fork choice section rendered, "
          f"fc-speedup PASS, TPU-gated fc-head-throughput reads "
          f"'no data' on CPU -> {report_md}")
    print("forkchoice smoke: PASS")


if __name__ == "__main__":
    if "--chaos-mesh" in sys.argv:
        chaos_main(mesh=True)
    elif "--chaos" in sys.argv:
        chaos_main()
    elif "--shard" in sys.argv:
        shard_main()
    elif "--das" in sys.argv:
        das_main()
    elif "--forkchoice" in sys.argv:
        forkchoice_main()
    else:
        main()
