"""BLS batch benchmarks — BASELINE.md configs #2 and #3.

#2: 128 aggregate-attestation verifications (FastAggregateVerify-style
    statements, 64-strong committees) — device RLC batch (129 pairings
    through ONE shared Fq12 Miller accumulator, one final
    exponentiation, message hash-to-curve on device) vs the pure-Python
    oracle loop.
#3: one 512-member sync-committee aggregate (eth_fast_aggregate_verify
    hot path) — device pairing check with host-precomputed fixed-argument
    Miller lines vs oracle.

Prints one JSON line per metric:
  {"metric": ..., "value": ..., "unit": "s", "vs_baseline": ...}

Oracle costs are measured from ONE representative verify and scaled
(each verify is an independent 2-pairing check; the loop is linear), and
persisted in bench_bls_baseline.json next to this file.

With CST_TELEMETRY=1 each metric line also carries a `"telemetry"`
sub-object (compile_s/run_s split, bucket-padding waste, MSM + h2c
routing counts — `consensus_specs_tpu.telemetry.bench_block`), and a
third metric probes the G1 MSM host/device break-even
(`_MSM_DEVICE_MIN`): host-oracle vs device-kernel wall at the sizes in
CST_BLS_BENCH_MSM_SIZES (default "6,16" — config #5's size-6 MSMs and
the current routing threshold), the ROADMAP's open routing question.
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
from consensus_specs_tpu.telemetry import history as benchwatch  # noqa: E402
from consensus_specs_tpu.utils.jaxtools import (  # noqa: E402
    device_fields, enable_compile_cache)

enable_compile_cache()

BASELINE_FILE = Path(__file__).resolve().parent / "bench_bls_baseline.json"

# env knobs let the smoke path run on CPU; the measured configs are the
# defaults (BASELINE.md #2/#3 shapes) on the real chip
N_ATTESTATIONS = int(os.environ.get("CST_BLS_BENCH_N", 128))
COMMITTEE_SIZE = int(os.environ.get("CST_BLS_BENCH_COMMITTEE", 64))
SYNC_COMMITTEE_SIZE = int(os.environ.get("CST_BLS_BENCH_SYNC", 512))
# MSM break-even probe sizes; "" disables the probe
MSM_PROBE_SIZES = tuple(
    int(s) for s in os.environ.get("CST_BLS_BENCH_MSM_SIZES",
                                   "6,16").split(",") if s.strip())


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def _build_tasks(n_tasks: int, keys_per_task: int, seed_base: int):
    """Valid FastAggregateVerify statements as (agg_pk, msg, sig) points."""
    from consensus_specs_tpu.ops.bls import ciphersuite as cs
    from consensus_specs_tpu.ops.bls.curve import g1, g2
    from consensus_specs_tpu.ops.bls.hash_to_curve import DST_G2, hash_to_g2

    tasks = []
    raw = []
    for t in range(n_tasks):
        msg = (seed_base + t).to_bytes(32, "little")
        h = hash_to_g2(msg, DST_G2)
        # aggregate secret key -> one scalar mult for pk and sig each;
        # statements are identical in shape to real per-key aggregation
        agg_sk = sum(seed_base + t * keys_per_task + i + 1
                     for i in range(keys_per_task))
        pk = g1.mul(cs.G1_GEN, agg_sk)
        sig = g2.mul(h, agg_sk)
        tasks.append((pk, msg, sig))
        raw.append((cs.g1_to_bytes(pk), msg, cs.g2_to_bytes(sig)))
    return tasks, raw


def _measure_oracle_single(raw_task) -> float:
    from consensus_specs_tpu.ops.bls import ciphersuite as cs

    pk_b, msg, sig_b = raw_task
    t0 = time.perf_counter()
    assert cs.FastAggregateVerify([pk_b], msg, sig_b)
    return time.perf_counter() - t0


def _baselines() -> dict:
    if BASELINE_FILE.exists() and not os.environ.get("CST_BENCH_REMEASURE"):
        return json.loads(BASELINE_FILE.read_text())
    log("measuring oracle baselines (one verify each)...")
    _, raw_att = _build_tasks(1, COMMITTEE_SIZE, seed_base=1000)
    att_single = _measure_oracle_single(raw_att[0])
    _, raw_sync = _build_tasks(1, SYNC_COMMITTEE_SIZE, seed_base=2000)
    sync_single = _measure_oracle_single(raw_sync[0])
    data = {
        "oracle_seconds_per_fast_aggregate_verify": att_single,
        "oracle_seconds_per_sync_aggregate_verify": sync_single,
        "measured_at": time.strftime("%Y-%m-%d"),
    }
    try:
        BASELINE_FILE.write_text(json.dumps(data, indent=2) + "\n")
    except OSError as e:
        log(f"baseline not persisted: {e}")
    return data


def _emit(record: dict) -> None:
    """Print one metric line, with the per-config `"telemetry"`
    sub-object embedded on telemetry rounds.  When
    CST_BENCHWATCH_HISTORY names a path, the same record also lands in
    the longitudinal store as a normalized history record
    (`telemetry.history`) — the stdout contract is unchanged."""
    record = telemetry.embed_bench_block({**record, **device_fields()})
    benchwatch.append_emission(record, ts=time.time())
    print(json.dumps(record), flush=True)


def msm_breakeven_probe(sizes=MSM_PROBE_SIZES, iters: int = 3):
    """Host-oracle vs device-kernel G1 MSM wall per batch size, plus the
    route `ops.bls.multi_exp` actually takes at that size — the data the
    ROADMAP's `_MSM_DEVICE_MIN = 16` open item asks for.  Returns the
    per-size detail dict (empty when disabled via
    CST_BLS_BENCH_MSM_SIZES="")."""
    from consensus_specs_tpu.ops import bls
    from consensus_specs_tpu.ops.bls import ciphersuite as cs
    from consensus_specs_tpu.ops.bls.curve import g1
    from consensus_specs_tpu.ops.bls.fields import R
    from consensus_specs_tpu.ops.bls_batch import g1_multi_exp_device

    detail = {}
    for n in sizes:
        pts = [g1.mul(cs.G1_GEN, 3 * i + 2) for i in range(n)]
        ks = [pow(5, i + 1, R) for i in range(n)]
        tagged = [(1, p) for p in pts]

        t0 = time.perf_counter()
        host_out = cs.multi_exp(tagged, ks)
        host_dt = time.perf_counter() - t0

        t0 = time.perf_counter()
        dev_out = g1_multi_exp_device(pts, ks)
        compile_dt = time.perf_counter() - t0
        t0 = time.perf_counter()
        for _ in range(iters):
            dev_out = g1_multi_exp_device(pts, ks)
        dev_dt = (time.perf_counter() - t0) / iters
        assert g1.eq_points(host_out[1], dev_out), f"MSM mismatch at n={n}"

        # where the facade's threshold actually routes this size, read
        # back from the routing counters the call just incremented (one
        # source of truth with the telemetry block); the global backend
        # is restored — the probe must not change what any later
        # measurement runs on
        prev_backend = bls.backend_name()
        dev_before = telemetry.counter_value("msm.route.device")
        try:
            bls.use_backend("jax")
            bls.multi_exp(tagged, ks)
        finally:
            bls.use_backend(prev_backend)
        dev_after = telemetry.counter_value("msm.route.device")
        # counters are the source of truth when collecting; without
        # telemetry (counters frozen) fall back to the threshold itself
        routed_dev = (dev_after > dev_before if telemetry.enabled()
                      else n >= bls._MSM_DEVICE_MIN)
        detail[str(n)] = {
            "host_s": round(host_dt, 4),
            "device_s": round(dev_dt, 4),
            "device_compile_first_s": round(compile_dt, 4),
            # ratio from the UNROUNDED walls: at sub-ms device times the
            # 4-dp display rounding would distort the number the
            # _MSM_DEVICE_MIN decision rides on
            "host_over_device": round(host_dt / dev_dt, 2) if dev_dt
            else None,
            "routed": "device" if routed_dev else "host",
        }
        log(f"msm probe n={n}: host {host_dt:.4f}s device {dev_dt:.4f}s "
            f"(compile+first {compile_dt:.1f}s) -> routed "
            f"{detail[str(n)]['routed']}")
    return detail


def msm_probe_record() -> dict:
    """Run the break-even probe and shape it as one bench metric record
    (metric/value/unit/vs_baseline + per-size detail) — the ONE shape
    this metric has, whether emitted standalone here or embedded in
    bench.py's extras."""
    from consensus_specs_tpu.ops import bls

    detail = msm_breakeven_probe()
    smallest = str(min(MSM_PROBE_SIZES))
    d = detail[smallest]
    return {
        "metric": f"g1_msm_breakeven_probe_n{smallest}",
        "value": d["device_s"],
        "unit": "s",
        # >1.0 means the device kernel beats the host oracle at the
        # smallest probed size => _MSM_DEVICE_MIN should drop
        "vs_baseline": d["host_over_device"],
        "detail": detail,
        "msm_device_min": bls._MSM_DEVICE_MIN,
    }


def costmodel_kernel_sweep():
    """Tiny-shape exercises of the device kernels that do NOT sit on
    this bench's measured path — the sha256 merkle reduction and the
    KZG barycentric evaluator — so a CST_COSTMODEL round's Utilization
    table covers the whole kernel surface, not just the BLS configs.
    Cost records are per-process facts (they survive the per-config
    telemetry resets), so running this during setup is free for the
    measured configs."""
    import numpy as np

    from consensus_specs_tpu.ops import fr_batch, sha256_jax

    words = np.arange(8 * 8, dtype=np.uint32).reshape(8, 8)
    sha256_jax.merkleize_words_jax(words, 3)
    roots = [pow(5, i, fr_batch.R_MODULUS) for i in range(4)]
    fr_batch.barycentric_eval([1, 2, 3, 4], roots, 7)
    telemetry.costmodel.sample_watermark("bench_bls.cost_sweep")


def main():
    from consensus_specs_tpu.ops.bls_batch import (
        batch_verify, pairing_check_device)
    from consensus_specs_tpu.ops.bls import ciphersuite as cs
    from consensus_specs_tpu.ops.bls.curve import g1
    from consensus_specs_tpu.ops.bls.hash_to_curve import DST_G2, hash_to_g2

    base = _baselines()
    if telemetry.costmodel.enabled():
        costmodel_kernel_sweep()
    if telemetry.enabled():
        telemetry.reset()   # drop setup-phase counters; per-config blocks

    # config #2: attestation batch
    tasks, _ = _build_tasks(N_ATTESTATIONS, COMMITTEE_SIZE, seed_base=1000)
    t0 = time.perf_counter()
    assert batch_verify(tasks)
    log(f"attestation batch compile+first: {time.perf_counter() - t0:.1f}s")
    iters = 3
    t0 = time.perf_counter()
    for _ in range(iters):
        assert batch_verify(tasks)
    dt = (time.perf_counter() - t0) / iters
    baseline = (base["oracle_seconds_per_fast_aggregate_verify"]
                * N_ATTESTATIONS)
    _emit({
        "metric": f"attestation_batch_{N_ATTESTATIONS}x"
                  f"{COMMITTEE_SIZE}_verify_wall",
        "value": round(dt, 4),
        "unit": "s",
        "vs_baseline": round(baseline / dt, 1),
    })

    # config #3: sync aggregate (one 512-member statement)
    sync_tasks, _ = _build_tasks(1, SYNC_COMMITTEE_SIZE, seed_base=2000)
    pk, msg, sig = sync_tasks[0]
    h = hash_to_g2(msg, DST_G2)
    pairs = [(pk, h), (g1.neg(cs.G1_GEN), sig)]
    t0 = time.perf_counter()
    assert pairing_check_device(pairs)
    log(f"sync aggregate compile+first: {time.perf_counter() - t0:.1f}s")
    t0 = time.perf_counter()
    for _ in range(iters):
        assert pairing_check_device(pairs)
    dt = (time.perf_counter() - t0) / iters
    baseline = base["oracle_seconds_per_sync_aggregate_verify"]
    _emit({
        "metric": f"sync_aggregate_{SYNC_COMMITTEE_SIZE}_verify_wall",
        "value": round(dt, 4),
        "unit": "s",
        "vs_baseline": round(baseline / dt, 1),
    })

    # MSM break-even probe (telemetry rounds only: it exists to produce
    # routing data, and keeping it out of the default path holds the
    # CST_TELEMETRY-unset bench wall identical to the pre-telemetry one)
    if telemetry.enabled() and MSM_PROBE_SIZES:
        _emit(msm_probe_record())


if __name__ == "__main__":
    main()
