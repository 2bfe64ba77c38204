"""Benchmark: mainnet-preset epoch-processing sweep @ 1M validators.

North-star config #4 (BASELINE.md): the per-validator epoch pipeline
(rewards/penalties + slashings + effective-balance updates) plus the
registry-scale merkleization (balances list root + validator registry
root), with BLS batch (configs #2/#3) extras folded into the same JSON
line when the time budget allows.

- TPU path: `parallel.epoch_sweep` + device merkle kernels, one fused XLA
  program over a 2**20-validator struct-of-arrays registry.
- Baseline: the executable spec's pure-Python pipeline + SSZ engine
  hash_tree_root, measured on a 1024-validator mainnet state and scaled
  linearly (the pipeline is O(N)).  The measured per-validator cost is
  persisted in `bench_baseline.json` (checked in) so the driver run does
  not re-pay ~95s of pure-Python sweeps; delete the file to re-measure.

Prints ONE JSON line:
  {"metric": ..., "value": ..., "unit": ..., "vs_baseline": ...,
   "platform": ..., "device_kind": ..., "device_count": ...}

Every measurement runs in a fresh worker subprocess that holds the
device alone; the parent never imports JAX.  The device fields come
from the worker's own `jax.devices()`.  There is no retry and no
fallback: a failed flagship worker makes the run exit non-zero with the
worker's error in the JSON line, and a failed extras worker lets the
later extras run but still fails the run at the end.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from pathlib import Path

# stdlib-only (never initializes a backend in the parent process)
from consensus_specs_tpu.telemetry import history as benchwatch
from consensus_specs_tpu.utils.jaxtools import device_fields

HERE = Path(__file__).resolve().parent
BASELINE_FILE = HERE / "bench_baseline.json"

N_VALIDATORS = int(os.environ.get("CST_BENCH_N", 1 << 20))
ATTEMPT_TIMEOUT = int(os.environ.get("CST_BENCH_ATTEMPT_TIMEOUT", 420))
# an extras worker (merkle / bls / kzg / spec) only starts while elapsed
# < this, so the flagship line cannot be lost to an external driver timeout
EXTRAS_DEADLINE = int(os.environ.get("CST_BENCH_EXTRAS_DEADLINE", 420))


def _merkle_fracs() -> list[float]:
    """The dirty-fraction sweep (CST_MERKLE_DIRTY_FRAC, comma list).
    The FIRST value is also the flagship's incremental dirty fraction."""
    raw = os.environ.get("CST_MERKLE_DIRTY_FRAC", "0.01,0.1,1.0")
    fracs = [float(f) for f in raw.split(",") if f.strip()]
    assert fracs and all(0.0 < f <= 1.0 for f in fracs), raw
    return fracs


def log(*a):
    print(*a, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# CPU baselines (pure-Python spec pipeline; persisted, no jax involved)
# ---------------------------------------------------------------------------

def _measure_baseline(n: int = 1024, repeats: int = 3) -> dict:
    """Pure-Python spec pipeline + SSZ HTR, per validator."""
    from consensus_specs_tpu.models.builder import build_spec
    from consensus_specs_tpu.testlib.context import (
        default_activation_threshold)
    from consensus_specs_tpu.testlib.helpers.attestations import (
        prepare_state_with_attestations)
    from consensus_specs_tpu.testlib.helpers.genesis import (
        create_genesis_state)
    from consensus_specs_tpu.utils.ssz.ssz_impl import hash_tree_root

    spec = build_spec("phase0", "mainnet")
    balances = [spec.MAX_EFFECTIVE_BALANCE] * n
    state = create_genesis_state(
        spec, balances, default_activation_threshold(spec))
    prepare_state_with_attestations(spec, state)

    best = float("inf")
    for _ in range(repeats):
        st = state.copy()
        t0 = time.perf_counter()
        spec.process_justification_and_finalization(st)
        spec.process_rewards_and_penalties(st)
        spec.process_slashings(st)
        spec.process_effective_balance_updates(st)
        hash_tree_root(st.balances)
        hash_tree_root(st.validators)
        best = min(best, time.perf_counter() - t0)
    return {
        "seconds_per_validator": best / n,
        "validators_measured": n,
        "repeats": repeats,
        "measured_at": time.strftime("%Y-%m-%d"),
        "pipeline": ("process_justification_and_finalization + "
                     "process_rewards_and_penalties + process_slashings + "
                     "process_effective_balance_updates + "
                     "hash_tree_root(balances) + hash_tree_root(validators)"),
    }


def baseline_cpu_seconds_per_validator() -> float:
    """The checked-in `bench_baseline.json`, read as it is;
    CST_BENCH_REMEASURE=1 re-measures it here and rewrites the file."""
    if not os.environ.get("CST_BENCH_REMEASURE"):
        data = json.loads(BASELINE_FILE.read_text())
        log(f"baseline (persisted {data.get('measured_at')}): "
            f"{data['seconds_per_validator'] * 1e6:.1f} us/validator "
            f"@ {data['validators_measured']} validators")
        return data["seconds_per_validator"]
    data = _measure_baseline()
    BASELINE_FILE.write_text(json.dumps(data, indent=2) + "\n")
    log(f"baseline (measured, persisted to {BASELINE_FILE.name}): "
        f"{data['seconds_per_validator'] * 1e6:.1f} us/validator")
    return data["seconds_per_validator"]


# ---------------------------------------------------------------------------
# workers (run in fresh subprocesses; print one JSON line on success)
# ---------------------------------------------------------------------------

def _worker_setup_jax():
    import jax

    jax.config.update("jax_enable_x64", True)
    from consensus_specs_tpu.utils.jaxtools import enable_compile_cache

    enable_compile_cache()
    return jax


def worker_epoch(n: int) -> None:
    """Config #4, rewired through incremental merkleization: the epoch
    sweep's balance/effective-balance deltas apply to a host-known
    dirty subset (CST_MERKLE_DIRTY_FRAC's first value), the persisted
    layer-stack forests (`parallel.incremental.MerkleForest`) re-hash
    only the dirty root-to-leaf paths, and the roots settle through
    `merkleize_dirty_async` futures — O(dirty · log N) sha256 per step
    instead of the full O(N) rebuild (which is also what the reference
    pays: remerkleable's pointer tree only re-hashes changed paths).
    Full-rebuild parity is asserted against `balances_list_root` /
    `validator_registry_root` every CST_MERKLE_PARITY_EVERY steps.

    With CST_TELEMETRY=1 the JSON carries a `"telemetry"` sub-object
    splitting the flagship wall into compile_s (trace + XLA compile +
    initial forest builds, measured from the first call) vs run_s."""
    import numpy as np

    from consensus_specs_tpu import telemetry

    jax = _worker_setup_jax()
    import jax.numpy as jnp
    from consensus_specs_tpu.models.builder import build_spec
    from consensus_specs_tpu.parallel import (
        EpochParams, EpochScalars, ValidatorLeaves, balances_list_root,
        epoch_sweep, incremental, validator_records_root,
        validator_registry_root)

    from __graft_entry__ import _synthetic_registry

    t0 = time.perf_counter()
    dev = jax.devices()[0]
    log(f"device claim: {time.perf_counter() - t0:.1f}s -> {dev}")

    assert n & (n - 1) == 0, f"flagship wants a pow2 registry, got {n}"
    params = EpochParams.from_spec(build_spec("phase0", "mainnet"))
    reg = _synthetic_registry(n)
    sc = EpochScalars(current_epoch=np.uint64(100_000),
                      finality_delay=np.uint64(2),
                      slashings_sum=np.uint64(32_000_000_000))
    rng = np.random.RandomState(7)
    pk_root = rng.randint(0, 2**32, (n, 8), dtype=np.uint64).astype(np.uint32)
    cred = rng.randint(0, 2**32, (n, 8), dtype=np.uint64).astype(np.uint32)
    # resident once: steps must not re-upload the ~100-byte-per-validator
    # registry every iteration
    reg = jax.device_put(reg)
    sc = jax.device_put(sc)
    pk_root = jnp.asarray(pk_root)
    cred = jnp.asarray(cred)

    frac = _merkle_fracs()[0]
    parity_every = max(1, int(os.environ.get("CST_MERKLE_PARITY_EVERY", 5)))
    m = max(1, int(frac * n))
    dirty_val = np.sort(rng.choice(n, m, replace=False)).astype(np.uint32)
    mask = np.zeros(n, dtype=bool)
    mask[dirty_val] = True
    chunk_idx = incremental.dirty_chunks_from_validators(dirty_val)

    _pad_idx = incremental.pad_dirty_idx

    @jax.jit
    def sweep_step(reg, sc, mask):
        new_bal, new_eff = epoch_sweep(reg, sc, params, axis_name=None)
        return (jnp.where(mask, new_bal, reg.balance),
                jnp.where(mask, new_eff, reg.effective_balance))

    def record_roots_all(eff, slashed):
        return validator_records_root(
            ValidatorLeaves(pk_root, cred), eff, slashed,
            reg.activation_eligibility_epoch, reg.activation_epoch,
            reg.exit_epoch, reg.withdrawable_epoch)

    @jax.jit
    def dirty_record_roots(eff, slashed, aee, ae, ee, we, pk, cr, idx):
        safe = jnp.minimum(idx, jnp.uint32(eff.shape[0] - 1))
        return validator_records_root(
            ValidatorLeaves(pk[safe], cr[safe]), eff[safe], slashed[safe],
            aee[safe], ae[safe], ee[safe], we[safe])

    t0 = time.perf_counter()
    with telemetry.span("bench.epoch.compile_first", n=n):
        # initial full builds: the persisted layer stacks the steps
        # re-hash incrementally (paid once, attributed to compile+first)
        rec_all = record_roots_all(reg.effective_balance, reg.slashed)
        bal_forest = incremental.balances_forest(reg.balance, n)
        reg_forest = incremental.registry_forest(np.asarray(rec_all), n)
        chunk_idx_p = _pad_idx(chunk_idx, bal_forest.capacity)
        val_idx_p = _pad_idx(dirty_val, reg_forest.capacity)
        chunk_idx_dev = jnp.asarray(chunk_idx_p)
        val_idx_dev = jnp.asarray(val_idx_p)
        mask_dev = jnp.asarray(mask)

        def step():
            """One epoch step: masked sweep -> dirty leaf gather ->
            dirty-path re-hash on both forests -> root futures (the
            only host syncs of the step)."""
            bal, eff = sweep_step(reg, sc, mask_dev)
            leaves = incremental.dirty_balance_leaves(bal, chunk_idx_dev)
            rec = dirty_record_roots(
                eff, reg.slashed, reg.activation_eligibility_epoch,
                reg.activation_epoch, reg.exit_epoch,
                reg.withdrawable_epoch, pk_root, cred, val_idx_dev)
            bal_fut = incremental.merkleize_dirty_async(
                bal_forest, chunk_idx_p, leaves)
            reg_fut = incremental.merkleize_dirty_async(
                reg_forest, val_idx_p, rec)
            return bal, eff, bal_fut.result(), reg_fut.result()

        out = step()
    compile_dt = time.perf_counter() - t0
    log(f"compile+first run {compile_dt:.1f}s "
        f"(incl. forest builds; dirty_frac={frac}, {m} validators)")
    # flagship cost record (CST_COSTMODEL rounds): the sweep's XLA
    # flop/byte budget + a device-memory watermark sample — no-op flag
    # checks otherwise (the merkle_incr@/merkle_build@ kernels record
    # their own entries through the incremental module's seams).  Keyed
    # `epoch_sweep` — the analyzed program is the sweep kernel alone,
    # and its run_s comes from the capture-time probe; the composite
    # step wall (sweep + dirty re-hash + root settles) is observed
    # under `epoch_step`, which deliberately has NO cost record so the
    # roofline join never divides sweep-only flops by the step wall
    telemetry.costmodel.capture(f"epoch_sweep@{n}", sweep_step,
                                (reg, sc, mask_dev))
    telemetry.costmodel.sample_watermark("bench.epoch.compile_first")

    full_bal_root = jax.jit(lambda bal: balances_list_root(
        bal, jnp.uint64(n)))
    full_reg_root = jax.jit(lambda rec: validator_registry_root(
        rec, jnp.uint64(n)))

    def parity_check(bal, eff):
        """Full-rebuild parity: the incremental roots must be bit-exact
        vs the classic O(N) kernels on the same arrays."""
        want_b = np.asarray(full_bal_root(bal))
        got_b = bal_forest.root()
        assert np.array_equal(want_b, got_b), (want_b, got_b)
        rec = record_roots_all(eff, reg.slashed)
        want_r = np.asarray(full_reg_root(rec))
        got_r = reg_forest.root()
        assert np.array_equal(want_r, got_r), (want_r, got_r)

    iters = 5
    steps_done = 1
    parity_checks = 0
    dt_sum = 0.0
    with telemetry.span("bench.epoch.steady", n=n, iters=iters):
        for _ in range(iters):
            t1 = time.perf_counter()
            out = step()
            dt_sum += time.perf_counter() - t1
            steps_done += 1
            # parity rides between timed steps so the flagship number
            # stays a pure incremental-step wall
            if steps_done % parity_every == 0:
                parity_check(out[0], out[1])
                parity_checks += 1
    dt = dt_sum / iters
    if not parity_checks:       # never skip parity entirely
        parity_check(out[0], out[1])
        parity_checks += 1
    # the composite step wall (no cost record joins it — see the
    # epoch_sweep capture above); the watermark is sampled here while
    # the step outputs are still resident so the high-water mark
    # reflects the working set, not an idle device
    telemetry.observe(f"kernel.epoch_step@{n}.run_s", dt)
    telemetry.costmodel.sample_watermark("bench.epoch.steady")
    log(f"{dt * 1e3:.1f} ms/step @ {n} validators "
        f"({parity_checks} parity check(s) ok, root {out[3][:2]})")
    result = {"seconds": dt, **device_fields(),
              "dirty_frac": frac, "dirty_validators": int(m),
              "parity_checks": parity_checks}
    if telemetry.enabled():
        result["telemetry"] = telemetry.bench_block(
            compile_s=compile_dt, run_s=dt)
    print(json.dumps(result), flush=True)


def worker_merkle() -> None:
    """Dirty-fraction sweep of the incremental merkleization kernels:
    one `merkle_incr::update@frac<f>` record per CST_MERKLE_DIRTY_FRAC
    value (incremental update+root wall, `vs_baseline` = speedup over a
    full re-merkleize of the same CST_MERKLE_N-leaf tree) plus a
    `merkle_incr::proofs@<batch>` record for batched SSZ single-proof
    emission.  Every fraction's root is parity-checked against a fresh
    full build, and one emitted proof batch is verified against the
    host SSZ oracle's branch check."""
    import numpy as np

    from consensus_specs_tpu import telemetry

    jax = _worker_setup_jax()
    from consensus_specs_tpu.parallel import incremental

    n = int(os.environ.get("CST_MERKLE_N", 1 << 20))
    fracs = _merkle_fracs()
    proof_batch = int(os.environ.get("CST_MERKLE_PROOF_BATCH", 1024))
    proof_batch = max(1, min(proof_batch, n))
    rng = np.random.RandomState(11)
    words = rng.randint(0, 2**32, (n, 8), dtype=np.uint64).astype(np.uint32)

    t0 = time.perf_counter()
    forest = incremental.MerkleForest(words, 38, n)
    root0 = forest.root()
    log(f"forest build @ {n} leaves: {time.perf_counter() - t0:.1f}s")

    # full-rebuild baseline: the pre-incremental O(N) path — the
    # device depth-d reduction over a leaf array that is resident ONCE
    # outside the clock, root fetched per call (exactly what the
    # incremental loop pays at `root()`).  The `merkleize_words_jax`
    # facade is NOT timed here: it ingests host numpy (pad + upload
    # per call), which would bill a full-tree transfer to the baseline
    # and inflate the reported speedup — the very ratio the
    # merkle-incremental-speedup threshold row gates on
    import jax.numpy as jnp
    from consensus_specs_tpu.ops.sha256_jax import merkle_root_pow2
    d = max(n - 1, 0).bit_length()
    padded = np.zeros((1 << d, 8), dtype=np.uint32)   # pow2 pad, once
    padded[:n] = words
    words_dev = jnp.asarray(padded)
    iters = 3
    t0 = time.perf_counter()
    np.asarray(merkle_root_pow2(words_dev, d))
    log(f"full re-merkleize compile+first: {time.perf_counter() - t0:.1f}s")
    t0 = time.perf_counter()
    for _ in range(iters):
        np.asarray(merkle_root_pow2(words_dev, d))
    full_dt = (time.perf_counter() - t0) / iters
    log(f"full re-merkleize: {full_dt:.3f}s")

    out = {}
    cur = words
    for frac in fracs:
        m = max(1, int(frac * n))
        idx = np.sort(rng.choice(n, m, replace=False)).astype(np.uint32)
        new_leaves = rng.randint(0, 2**32, (m, 8),
                                 dtype=np.uint64).astype(np.uint32)
        forest.update(idx, new_leaves)
        forest.root()                      # warm this rung's executables
        t0 = time.perf_counter()
        for _ in range(iters):
            forest.update(idx, new_leaves)
            root = forest.root()
        dt = (time.perf_counter() - t0) / iters
        # parity: a fresh full build over the mutated leaves must land
        # the identical root
        cur = cur.copy()
        cur[idx] = new_leaves
        want = incremental.MerkleForest(cur, 38, n).root()
        assert np.array_equal(root, want), (frac, root, want)
        rung = incremental._bucket(m)
        log(f"dirty frac={frac:g} ({m} leaves, rung {rung}): {dt:.4f}s "
            f"({full_dt / dt:.1f}x vs full)")
        out[f"merkle_incr::update@frac{frac:g}"] = {
            "value": round(dt, 4), "unit": "s",
            "vs_baseline": round(full_dt / dt, 1),
            "detail": {"n_leaves": n, "dirty": m, "rung": rung,
                       "full_remerkleize_s": round(full_dt, 4)},
        }

    # batched proof emission from the persisted layers (the stateless-
    # client / light-client serving workload)
    indices = list(range(0, n, max(1, n // proof_batch)))[:proof_batch]
    forest.emit_proofs(indices)            # warm
    t0 = time.perf_counter()
    for _ in range(iters):
        proofs = forest.emit_proofs(indices)
    proof_dt = (time.perf_counter() - t0) / iters
    root_bytes = forest.root_bytes()
    assert all(incremental.verify_proof(p, root_bytes)
               for p in proofs[:8]), "emitted proof failed oracle check"
    log(f"proofs x{len(indices)}: {proof_dt:.4f}s "
        f"({proof_dt / len(indices) * 1e6:.1f} us/proof)")
    out[f"merkle_incr::proofs@{len(indices)}"] = {
        "value": round(proof_dt, 4), "unit": "s",
        "vs_baseline": None,
        "detail": {"n_leaves": n, "batch": len(indices),
                   "us_per_proof": round(proof_dt / len(indices) * 1e6, 1)},
    }
    _ = root0
    if telemetry.enabled():
        out = {k: telemetry.embed_bench_block(dict(v))
               for k, v in out.items()}
        # one block per line is enough — keep the superset line small
        for k in list(out)[1:]:
            out[k].pop("telemetry", None)
    out.update(device_fields())
    print(json.dumps(out), flush=True)


def worker_scaling() -> None:
    """Mesh-sharded flagship rungs (the ROADMAP scale-out item): the
    partition-registry epoch step (`parallel.partition`: sweep with
    psum totals + sharded balances/registry merkle roots, shard_map
    specs from the rule table) measured at 2M/8M/16M validators, each
    rung gated on the device count keeping the per-chip shard at or
    under the single-chip flagship's 2**21 validators.

    Per rung the worker measures the sharded step wall over the full
    mesh AND a single-chip reference at the same per-chip shard size
    (weak scaling), so the record carries per-chip throughput and the
    scaling efficiency the `scaling-efficiency` benchwatch row gates
    (>= 70% retention at the full mesh).  An 8M+ rung that completes
    flips `ok_8m` — the `flagship-8m` no-OOM gate.

    Knobs: CST_SHARD_RUNGS (comma list of validator counts, default
    2M,8M,16M), CST_SHARD_DEVICES (cap the mesh width; quantized to a
    power of two via `mesh_rung`), CST_SHARD_ITERS (steady-state
    iterations per rung)."""
    import numpy as np

    from consensus_specs_tpu import telemetry

    jax = _worker_setup_jax()
    from consensus_specs_tpu.models.builder import build_spec
    from consensus_specs_tpu.parallel import (
        EpochParams, EpochScalars, partition)

    from __graft_entry__ import _synthetic_registry

    raw = os.environ.get("CST_SHARD_RUNGS",
                         f"{1 << 21},{1 << 23},{1 << 24}")
    rungs = [int(r) for r in raw.split(",") if r.strip()]
    assert rungs and all(r & (r - 1) == 0 for r in rungs), (
        f"CST_SHARD_RUNGS wants power-of-two validator counts: {raw}")
    iters = max(1, int(os.environ.get("CST_SHARD_ITERS", 3)))
    cap = int(os.environ.get("CST_SHARD_DEVICES", 0)) or None

    pool = partition.available_devices()
    n_dev = partition.mesh_rung(min(pool, cap) if cap else pool)
    # per-chip shard cap: the single-chip flagship shape (2**21 on the
    # real chip; tiny smoke rungs always pass)
    per_chip_cap = max(1 << 21, rungs[0])
    params = EpochParams.from_spec(build_spec("phase0", "mainnet"))
    sc = EpochScalars(current_epoch=np.uint64(100_000),
                      finality_delay=np.uint64(2),
                      slashings_sum=np.uint64(32_000_000_000))
    sc = jax.device_put(sc)

    def measure(step, reg_s, length, pk_s, cred_s):
        t0 = time.perf_counter()
        out = jax.block_until_ready(step(reg_s, sc, length, pk_s, cred_s))
        compile_dt = time.perf_counter() - t0
        t0 = time.perf_counter()
        for _ in range(iters):
            out = jax.block_until_ready(
                step(reg_s, sc, length, pk_s, cred_s))
        return (time.perf_counter() - t0) / iters, compile_dt, out

    def build_inputs(n, mesh):
        rng = np.random.RandomState(7)
        reg = _synthetic_registry(n)
        pk = rng.randint(0, 2**32, (n, 8),
                         dtype=np.uint64).astype(np.uint32)
        cred = rng.randint(0, 2**32, (n, 8),
                           dtype=np.uint64).astype(np.uint32)
        rules = partition.epoch_state_rules()
        reg_s = partition.shard_tree(mesh, reg, rules)
        leaves = partition.shard_tree(
            mesh, {"pubkey_root": pk, "credentials": cred}, rules)
        return reg_s, leaves["pubkey_root"], leaves["credentials"]

    block = {"n_devices": n_dev, "rungs": [], "ok_8m": None}
    # single-chip reference per distinct per-chip shard size (weak
    # scaling baseline: same step machinery on a 1-device mesh)
    single_cache: dict[int, float] = {}
    mesh1 = partition.build_mesh(n_devices=1, require_pow2=True)
    step1 = partition.sharded_epoch_step(mesh1, params)
    mesh = partition.build_mesh(n_devices=n_dev, require_pow2=True)
    step = partition.sharded_epoch_step(mesh, params)
    # the worker must hand back whatever it measured instead of eating
    # the whole extras budget: stop ADDING rungs once ~60% of the
    # per-attempt timeout is gone (a timed-out subprocess would lose
    # every completed rung AND starve the later extras workers)
    worker_t0 = time.perf_counter()
    rung_deadline = 0.6 * ATTEMPT_TIMEOUT
    for n in rungs:
        needed = max(1, n // per_chip_cap)
        if n_dev < needed:
            log(f"rung {n}: skipped (needs >= {needed} devices, "
                f"have {n_dev})")
            continue
        if block["rungs"] and \
                time.perf_counter() - worker_t0 > rung_deadline:
            log(f"rung {n}: skipped (scaling budget "
                f"{rung_deadline:.0f}s spent)")
            break
        try:
            n_local = n // n_dev
            if n_local not in single_cache:
                r1, p1, c1 = build_inputs(n_local, mesh1)
                dt1, cdt1, _ = measure(step1, r1, np.uint64(n_local),
                                       p1, c1)
                single_cache[n_local] = dt1
                log(f"single-chip reference @ {n_local}: {dt1 * 1e3:.1f} "
                    f"ms/step (compile+first {cdt1:.1f}s)")
            dt1 = single_cache[n_local]
            reg_s, pk_s, cred_s = build_inputs(n, mesh)
            dt, cdt, out = measure(step, reg_s, np.uint64(n),
                                   pk_s, cred_s)
            per_chip = n / dt / n_dev
            single_vps = n_local / dt1
            eff = per_chip / single_vps if single_vps > 0 else 0.0
            log(f"rung {n} @ {n_dev} devices: {dt * 1e3:.1f} ms/step "
                f"(compile+first {cdt:.1f}s), {per_chip:.0f} "
                f"validators/s/chip, efficiency {eff * 100:.0f}% "
                f"(root {np.asarray(out[2])[:2]})")
            rung = {"n_validators": n, "n_devices": n_dev,
                    "wall_s": round(dt, 5),
                    "per_chip_vps": round(per_chip, 1),
                    "total_vps": round(n / dt, 1),
                    "single_chip_wall_s": round(dt1, 5),
                    "single_chip_vps": round(single_vps, 1),
                    "efficiency": round(eff, 4)}
            block["rungs"].append(rung)
            if n >= (1 << 23):
                block["ok_8m"] = True
        except Exception as e:               # OOM / compile failure
            log(f"rung {n} FAILED: {type(e).__name__}: {e}")
            if n >= (1 << 23) and block["ok_8m"] is None:
                block["ok_8m"] = False
            break
    assert block["rungs"], "no scaling rung completed"
    telemetry.costmodel.sample_watermark("bench.scaling")
    top = block["rungs"][-1]
    out = {"flagship_scaling": {
        "value": top["per_chip_vps"], "unit": "validators/s/chip",
        "vs_baseline": top["efficiency"], "scaling": block}}
    if telemetry.enabled():
        out["flagship_scaling"] = telemetry.embed_bench_block(
            out["flagship_scaling"])
    out.update(device_fields())
    print(json.dumps(out), flush=True)


def worker_das() -> None:
    """The PeerDAS workload: batched cell-proof verification over a
    full sampling matrix (CST_DAS_MATRIX, default 128x2 and 128x8 —
    128 columns x N blobs, the largest device batch in the repo; the
    old config #5 verified six blobs).  Per matrix the device route
    (`das.verify`: one fr_batch coset-interpolation dispatch, Pippenger
    MSMs, one multi-pairing) is measured steady-state and compared
    against the pure-Python fulu oracle
    (`spec.verify_cell_kzg_proof_batch`), which pays a Lagrange
    interpolation per cell — the oracle wall is measured on
    CST_DAS_ORACLE_CELLS cells (default 16) and scaled linearly, the
    same subset-scaling the flagship baseline uses.

    The matrix rows are closed-form degree-65 polynomials
    (`das.ciphersuite.closed_form_matrix`): real, distinct commitments
    and non-infinity proofs from three scalar multiplications per row,
    so matrix construction never dominates the measured verification.
    Each sweep also runs the mixed-invalid isolation arc (one bad cell
    fails the RLC batch, the per-statement recheck isolates exactly
    it) and the coset-barycentric evaluation cross-check."""
    from consensus_specs_tpu import telemetry

    _worker_setup_jax()
    from consensus_specs_tpu.das import ciphersuite as das_cs
    from consensus_specs_tpu.das import verify as das_verify
    from consensus_specs_tpu.models.builder import build_spec
    from consensus_specs_tpu.ops import bls

    raw = os.environ.get("CST_DAS_MATRIX", "128x2,128x8")
    shapes = []
    for part in raw.split(","):
        if not part.strip():
            continue
        cols, blobs = part.lower().split("x")
        shapes.append((int(cols), int(blobs)))
    assert shapes and all(1 <= c <= 128 and b >= 1 for c, b in shapes), raw
    oracle_cells = max(1, int(os.environ.get("CST_DAS_ORACLE_CELLS", 16)))
    iters = 3

    spec = build_spec("fulu", "mainnet")
    prev_active = bls.bls_active
    bls.bls_active = True
    out = {}
    try:
        max_cols = max(c for c, _ in shapes)
        max_blobs = max(b for _, b in shapes)
        t0 = time.perf_counter()
        matrix = das_cs.closed_form_matrix(
            max_blobs, columns=range(max_cols))
        log(f"closed-form matrix {max_cols}x{max_blobs}: "
            f"{time.perf_counter() - t0:.1f}s")

        def cut(cols, blobs):
            # the matrix is row-major: entry r * max_cols + c is
            # (row r, column c)
            com, idx, cells, proofs = matrix
            keep = [r * max_cols + c
                    for r in range(blobs) for c in range(cols)]
            return ([com[k] for k in keep], [idx[k] for k in keep],
                    [cells[k] for k in keep], [proofs[k] for k in keep])

        # ONE oracle measurement (per-cell cost is shape-independent),
        # scaled per matrix below — the pure-python interpolation makes
        # a full-matrix oracle run minutes-to-hours
        com, idx, cells, proofs = cut(max_cols, max_blobs)
        n_o = min(oracle_cells, len(idx))
        bls.use_backend("py")
        t0 = time.perf_counter()
        assert spec.verify_cell_kzg_proof_batch(
            com[:n_o], idx[:n_o],
            [spec.Cell(c) for c in cells[:n_o]], proofs[:n_o])
        oracle_sub = time.perf_counter() - t0
        log(f"oracle verify @ {n_o} cells: {oracle_sub:.1f}s")

        if telemetry.enabled():
            telemetry.reset()   # count only the device-backend phase
        for cols, blobs in shapes:
            com, idx, cells, proofs = cut(cols, blobs)
            n = len(idx)
            t0 = time.perf_counter()
            assert das_verify.verify_cell_proof_batch(
                com, idx, cells, proofs, device=True)
            compile_first = time.perf_counter() - t0
            log(f"das {cols}x{blobs} compile+first: {compile_first:.1f}s")
            t0 = time.perf_counter()
            for _ in range(iters):
                assert das_verify.verify_cell_proof_batch(
                    com, idx, cells, proofs, device=True)
            wall = (time.perf_counter() - t0) / iters
            oracle_wall = oracle_sub / n_o * n
            speedup = oracle_wall / wall
            log(f"das {cols}x{blobs} ({n} cells): {wall:.2f}s device "
                f"vs {oracle_wall:.1f}s oracle ({speedup:.1f}x)")

            # mixed-invalid isolation arc on a small slice (rung 16)
            s_com, s_idx, s_cells, s_proofs = (com[:8], idx[:8],
                                               list(cells[:8]),
                                               proofs[:8])
            bad = 3
            s_cells[bad] = s_cells[bad][:-32] + int.to_bytes(
                7, 32, "big")
            batch_ok, per = das_verify.verify_and_isolate(
                s_com, s_idx, s_cells, s_proofs, device=True)
            isolated = (not batch_ok
                        and [i for i, v in enumerate(per) if not v]
                        == [bad])
            # coset-evaluation cross-check: device barycentric over the
            # shifted domain vs the host interpolant
            z = 0xDA5_0001
            crosscheck = (das_verify.evaluate_cells_at(
                cells[:4], idx[:4], z, device=True)
                == das_verify.evaluate_cells_at(
                    cells[:4], idx[:4], z, device=False))

            block = {
                "matrix": {"columns": cols, "blobs": blobs, "cells": n},
                "verify_wall_s": round(wall, 4),
                "cells_per_s": round(n / wall, 1),
                "oracle_wall_s": round(oracle_wall, 2),
                "oracle_cells_measured": n_o,
                "speedup": round(speedup, 1),
                "rung": das_verify.das_rung(n),
                "compile_first_s": round(compile_first, 2),
                "batch_verdict": True,
                "isolate": {"bad_cells": 1, "isolated": isolated},
                "eval_crosscheck": bool(crosscheck),
            }
            rec = {"value": round(wall, 4), "unit": "s",
                   "vs_baseline": round(speedup, 1), "das": block}
            if telemetry.enabled():
                rec = telemetry.embed_bench_block(rec)
            out[f"das_cell_proof_batch_{cols}x{blobs}_verify_wall"] = rec

        # --- FK20 producer + erasure recovery (the super-node path) --
        # The producer measures the FK20 pipeline steady-state against
        # the D_u partial route it replaced; the D_u wall is
        # subset-scaled (CST_DAS_DU_MSMS of its 63 wide MSMs measured,
        # the rest scaled by their pad rung — a full D_u run is ~40
        # device-minutes).  Recovery measures the device decode +
        # FK20 re-prove against the pure-Python oracle with the
        # oracle's 128 per-coset proofs subset-scaled the same way
        # (CST_DAS_RECOVER_ORACLE_COSETS measured).  Parity rides a
        # degree-65 closed-form blob: its recovered cells and proofs
        # are known without any oracle run.
        from consensus_specs_tpu.das import compute as das_compute
        from consensus_specs_tpu.das import recover as das_recover
        from consensus_specs_tpu.models.builder import build_spec as _bs
        from consensus_specs_tpu.ops.bls_batch import _bucket

        produce_iters = max(1, int(os.environ.get(
            "CST_DAS_PRODUCE_ITERS", 2)))
        du_msms = max(1, int(os.environ.get("CST_DAS_DU_MSMS", 2)))
        oracle_cosets = max(1, int(os.environ.get(
            "CST_DAS_RECOVER_ORACLE_COSETS", 1)))
        n_ext = das_cs.CELLS_PER_EXT_BLOB
        m_blob = das_cs.FIELD_ELEMENTS_PER_BLOB
        p_mod = das_cs.BLS_MODULUS

        c2, c1, c0 = 90001, 80001, 70001
        roots = das_cs.roots_of_unity(m_blob)
        evals = [(c2 * pow(roots[das_cs.reverse_bits(i, m_blob)], 65,
                           p_mod)
                  + c1 * pow(roots[das_cs.reverse_bits(i, m_blob)], 64,
                             p_mod) + c0) % p_mod
                 for i in range(m_blob)]
        blob = das_cs._encode_evals(evals)
        _, per_cell = das_cs.closed_form_row(c2, c1, c0, range(n_ext))
        true_cells = [per_cell[k][0] for k in range(n_ext)]
        true_proofs = [per_cell[k][1] for k in range(n_ext)]

        t0 = time.perf_counter()
        fk_cells, fk_proofs = das_compute.compute_cells_and_kzg_proofs(
            blob, device=True, route="fk20")
        produce_first = time.perf_counter() - t0
        parity = (fk_cells == true_cells and fk_proofs == true_proofs)
        log(f"fk20 compile+setup+first: {produce_first:.1f}s "
            f"(closed-form parity: {parity})")
        t0 = time.perf_counter()
        for _ in range(produce_iters):
            das_compute.compute_cells_and_kzg_proofs(
                blob, device=True, route="fk20")
        produce_wall = (time.perf_counter() - t0) / produce_iters

        # D_u baseline, subset-scaled by pad rung: sizes M - 64u for
        # u = 1..63 (the wide partials) plus 128 rung-64 column MSMs
        coeffs = das_compute.poly_coefficients(blob, device=True)
        wide_pts = [das_cs.setup_g1_point(t) for t in range(m_blob - 64)]
        das_compute._msm(wide_pts, coeffs[64:], True)      # warm
        t0 = time.perf_counter()
        for _ in range(du_msms):
            das_compute._msm(wide_pts, coeffs[64:], True)
        t_wide = (time.perf_counter() - t0) / du_msms
        das_compute._msm(wide_pts[:63], coeffs[:63], True)  # warm rung 64
        t0 = time.perf_counter()
        das_compute._msm(wide_pts[:63], coeffs[:63], True)
        t_narrow = time.perf_counter() - t0
        sizes = [m_blob - 64 * u for u in range(1, m_blob // 64)]
        rung_scale = sum(_bucket(s) for s in sizes) / _bucket(sizes[0])
        du_wall = t_wide * rung_scale + n_ext * t_narrow
        producer_speedup = du_wall / produce_wall
        log(f"fk20 produce: {produce_wall:.1f}s vs D_u {du_wall:.1f}s "
            f"({producer_speedup:.1f}x; wide MSM {t_wide:.1f}s x "
            f"{rung_scale:.1f} rung-scaled, measured {du_msms})")

        # recovery: exactly half the cells survive (worst recoverable)
        keep = [k for k in range(n_ext) if k % 2 == 0]
        kept_cells = [true_cells[k] for k in keep]
        t0 = time.perf_counter()
        rc_cells, rc_proofs = das_recover.recover_cells_and_kzg_proofs(
            keep, kept_cells, device=True)
        recover_first = time.perf_counter() - t0
        roundtrip = (rc_cells == true_cells and rc_proofs == true_proofs)
        t0 = time.perf_counter()
        das_recover.recover_cells_and_kzg_proofs(keep, kept_cells,
                                                 device=True)
        recover_wall = time.perf_counter() - t0
        log(f"device recover first: {recover_first:.1f}s, steady: "
            f"{recover_wall:.1f}s (closed-form roundtrip: {roundtrip})")

        # oracle baseline: full pure-Python decode, subset-scaled
        # per-coset re-prove
        fulu = _bs("fulu", "mainnet")
        o_evals = [fulu.cell_to_coset_evals(c) for c in kept_cells]
        t0 = time.perf_counter()
        o_coeffs = fulu.recover_polynomialcoeff(keep, o_evals)
        decode_oracle = time.perf_counter() - t0
        t0 = time.perf_counter()
        for k in range(oracle_cosets):
            fulu.compute_kzg_proof_multi_impl(
                o_coeffs, fulu.coset_for_cell(fulu.CellIndex(k)))
        prove_oracle = (time.perf_counter() - t0) / oracle_cosets
        recover_oracle_wall = decode_oracle + n_ext * prove_oracle
        recover_speedup = recover_oracle_wall / recover_wall
        log(f"oracle recover: decode {decode_oracle:.1f}s + 128 x "
            f"{prove_oracle:.1f}s/coset = {recover_oracle_wall:.1f}s "
            f"({recover_speedup:.1f}x, measured {oracle_cosets} cosets)")

        producer_block = {
            "produce_wall_s": round(produce_wall, 3),
            "produce_first_s": round(produce_first, 2),
            "proofs_per_s": round(n_ext / produce_wall, 2),
            "du_wall_s": round(du_wall, 2),
            "du_msms_measured": du_msms,
            "producer_speedup": round(producer_speedup, 1),
            "parity": parity,
            "recover": {
                "cells_in": len(keep),
                "missing": n_ext - len(keep),
                "wall_s": round(recover_wall, 3),
                "oracle_wall_s": round(recover_oracle_wall, 2),
                "oracle_cosets_measured": oracle_cosets,
                "speedup": round(recover_speedup, 1),
                "roundtrip": roundtrip,
            },
        }
        rec = {"value": round(produce_wall, 4), "unit": "s",
               "vs_baseline": round(producer_speedup, 1),
               "das_producer": producer_block}
        if telemetry.enabled():
            rec = telemetry.embed_bench_block(rec)
        out["das_fk20_produce_wall"] = rec
    finally:
        bls.bls_active = prev_active
    out.update(device_fields())
    print(json.dumps(out), flush=True)


def worker_forkchoice() -> None:
    """The fork-choice workload: device LMD-GHOST over proto-array
    stores (CST_FC_MATRIX, default 256x16384 and 1024x262144 —
    <blocks>x<validators> tree shapes).  Per shape the device route
    (`forkchoice.store`: batched latest-message folds + the
    pointer-jumping head kernel) is measured steady-state — apply wall
    per attestation batch, head wall per poll, heads/s — and compared
    against the phase0 spec oracle's `get_head`, which walks every
    active validator per child in pure Python: the oracle wall is
    measured on a CST_FC_ORACLE_VALIDATORS-validator store over the
    SAME block tree (the per-poll cost is linear in the validator
    count — the active-set loop dominates) and scaled linearly, the
    same subset-scaling the DAS and flagship baselines use.  The
    oracle store also pins bit-exact parity: the device head at the
    measured subset size must equal the spec oracle's."""
    from consensus_specs_tpu import telemetry

    _worker_setup_jax()
    from consensus_specs_tpu.forkchoice import (
        FC_BATCH_STEPS,
        FC_BLOCK_STEPS,
        FC_VALIDATOR_STEPS,
        fc_rung,
    )

    raw = os.environ.get("CST_FC_MATRIX", "256x16384,1024x262144")
    shapes = []
    for part in raw.split(","):
        if not part.strip():
            continue
        blocks, validators = part.lower().split("x")
        shapes.append((int(blocks), int(validators)))
    assert shapes and all(b >= 2 and v >= 8 for b, v in shapes), raw
    oracle_v = max(8, int(os.environ.get("CST_FC_ORACLE_VALIDATORS",
                                         2048)))
    iters = 5
    n_batches = 8

    def build_store(n_blocks, n_validators, seed=29):
        """The shared synthetic workload (`forkchoice.synthetic` —
        same builder the serve loadgen's fc lane drives), with the
        first `n_batches` of its attestation stream materialized."""
        import itertools

        from consensus_specs_tpu.forkchoice.synthetic import (
            attestation_stream,
            synthetic_store,
        )

        store, roots = synthetic_store(n_blocks, n_validators,
                                       seed=seed)
        batch = 1024 if n_validators >= 4096 else 64
        batches = list(itertools.islice(
            attestation_stream(roots, n_validators, batch, seed=seed),
            n_batches))
        return store, batches

    out = {}
    if telemetry.enabled():
        telemetry.reset()
    for n_blocks, n_validators in shapes:
        store, batches = build_store(n_blocks, n_validators)
        n_msgs = sum(len(b[0]) for b in batches)

        t0 = time.perf_counter()
        store.apply_attestations(*batches[0])
        head = store.get_head()
        compile_first = time.perf_counter() - t0
        log(f"forkchoice {n_blocks}x{n_validators} compile+first: "
            f"{compile_first:.1f}s")

        apply_wall = head_wall = 0.0
        polls = 0
        for _ in range(iters):
            for b in batches:
                t0 = time.perf_counter()
                store.apply_attestations(*b)
                apply_wall += time.perf_counter() - t0
                t0 = time.perf_counter()
                head = store.get_head()
                head_wall += time.perf_counter() - t0
                polls += 1
        apply_wall /= iters * n_batches
        head_wall = max(head_wall / polls, 1e-9)
        heads_per_s = 1.0 / head_wall

        # the spec-oracle baseline + bit-exact parity, at the measured
        # subset size over the SAME tree (per-poll oracle cost is
        # linear in the validator count)
        v_o = min(oracle_v, n_validators)
        o_store, o_batches = build_store(n_blocks, v_o)
        for b in o_batches:
            o_store.apply_attestations(*b)
        dev_head = o_store.get_head()
        # untimed oracle warmup: the first get_head_host of the
        # process pays the one-time spec-namespace build, which must
        # not land in the scaled baseline (the device route's
        # compile+first is likewise measured separately)
        oracle_head = o_store.get_head_host()
        t0 = time.perf_counter()
        oracle_head = o_store.get_head_host()
        oracle_sub = time.perf_counter() - t0
        parity = dev_head == oracle_head
        assert parity, (dev_head.hex(), oracle_head.hex())
        oracle_wall = oracle_sub * n_validators / v_o
        speedup = oracle_wall / head_wall
        log(f"forkchoice {n_blocks}x{n_validators}: head "
            f"{head_wall * 1e3:.2f}ms device vs {oracle_wall:.2f}s "
            f"oracle ({speedup:.1f}x), apply {apply_wall * 1e3:.2f}ms")

        block = {
            "tree": {"blocks": n_blocks, "validators": n_validators,
                     "messages": n_msgs},
            "apply_wall_s": round(apply_wall, 6),
            "head_wall_s": round(head_wall, 6),
            "heads_per_s": round(heads_per_s, 1),
            "oracle_head_wall_s": round(oracle_wall, 4),
            "oracle_validators_measured": v_o,
            "speedup": round(speedup, 1),
            "rungs": {"blocks": fc_rung(n_blocks, FC_BLOCK_STEPS),
                      "validators": fc_rung(n_validators,
                                            FC_VALIDATOR_STEPS),
                      "batch": fc_rung(len(batches[0][0]),
                                       FC_BATCH_STEPS)},
            "compile_first_s": round(compile_first, 2),
            "parity": bool(parity),
        }
        rec = {"value": round(head_wall, 6), "unit": "s",
               "vs_baseline": round(speedup, 1), "forkchoice": block}
        if telemetry.enabled():
            rec = telemetry.embed_bench_block(rec)
        out[f"forkchoice_lmd_ghost_{n_blocks}x{n_validators}"
            f"_head_wall"] = rec
    out.update(device_fields())
    print(json.dumps(out), flush=True)


def worker_bls() -> None:
    """Configs #2/#3: attestation RLC batch + sync-aggregate pairing.
    With CST_TELEMETRY=1 each metric carries per-config compile/run,
    padding, and routing telemetry."""
    from consensus_specs_tpu import telemetry

    _worker_setup_jax()
    import bench_bls

    base = bench_bls._baselines()
    n_att = bench_bls.N_ATTESTATIONS
    committee = bench_bls.COMMITTEE_SIZE
    sync_n = bench_bls.SYNC_COMMITTEE_SIZE

    from consensus_specs_tpu.ops.bls import ciphersuite as cs
    from consensus_specs_tpu.ops.bls.curve import g1
    from consensus_specs_tpu.ops.bls.hash_to_curve import DST_G2, hash_to_g2
    from consensus_specs_tpu.ops.bls_batch import (
        batch_verify, pairing_check_device)

    _tel = telemetry.embed_bench_block

    if telemetry.costmodel.enabled():
        bench_bls.costmodel_kernel_sweep()
    if telemetry.enabled():
        telemetry.reset()
    tasks, _ = bench_bls._build_tasks(n_att, committee, seed_base=1000)
    t0 = time.perf_counter()
    assert batch_verify(tasks)
    log(f"attestation batch compile+first: {time.perf_counter() - t0:.1f}s")
    iters = 3
    t0 = time.perf_counter()
    for _ in range(iters):
        assert batch_verify(tasks)
    att_dt = (time.perf_counter() - t0) / iters
    att_base = base["oracle_seconds_per_fast_aggregate_verify"] * n_att
    att = _tel({"value": round(att_dt, 4), "unit": "s",
                "vs_baseline": round(att_base / att_dt, 1)})

    sync_tasks, _ = bench_bls._build_tasks(1, sync_n, seed_base=2000)
    pk, msg, sig = sync_tasks[0]
    h = hash_to_g2(msg, DST_G2)
    pairs = [(pk, h), (g1.neg(cs.G1_GEN), sig)]
    t0 = time.perf_counter()
    assert pairing_check_device(pairs)
    log(f"sync aggregate compile+first: {time.perf_counter() - t0:.1f}s")
    t0 = time.perf_counter()
    for _ in range(iters):
        assert pairing_check_device(pairs)
    sync_dt = (time.perf_counter() - t0) / iters
    sync_base = base["oracle_seconds_per_sync_aggregate_verify"]
    sync = _tel({"value": round(sync_dt, 4), "unit": "s",
                 "vs_baseline": round(sync_base / sync_dt, 1)})

    out = {
        f"attestation_batch_{n_att}x{committee}_verify_wall": att,
        f"sync_aggregate_{sync_n}_verify_wall": sync,
    }
    # the ROADMAP's _MSM_DEVICE_MIN break-even question rides along on
    # telemetry rounds (host-vs-device MSM wall + routing per size),
    # same record shape as bench_bls.py's standalone emission.  A probe
    # failure (e.g. its kernel-vs-oracle assert) must not cost the two
    # already-measured config metrics — report it as a field instead.
    if telemetry.enabled() and bench_bls.MSM_PROBE_SIZES:
        try:
            probe = _tel(bench_bls.msm_probe_record())
            out[probe.pop("metric")] = probe
        except Exception as e:
            out["g1_msm_breakeven_probe_error"] = repr(e)[:300]

    out.update(device_fields())
    print(json.dumps(out), flush=True)


def worker_kzg() -> None:
    """Config #5: deneb `verify_blob_kzg_proof_batch` over 6 mainnet
    blobs — KZG pairings/MSM on device (jax backend) vs the pure-python
    oracle.  The telemetry block's `routing` counts show how many of the
    batch's G1 MSMs the `_MSM_DEVICE_MIN` threshold kept on the host —
    the ROADMAP's open question for this config."""
    from consensus_specs_tpu import telemetry

    _worker_setup_jax()

    from consensus_specs_tpu.models.builder import build_spec
    from consensus_specs_tpu.ops import bls

    spec = build_spec("deneb", "mainnet")
    modulus = int(spec.BLS_MODULUS)
    n_fe = int(spec.FIELD_ELEMENTS_PER_BLOB)
    blobs = [
        spec.Blob(b"".join(
            int.to_bytes(pow(2 + i, j + 256, modulus), 32, "big")
            for j in range(n_fe)))
        for i in range(6)
    ]
    # setup on the device backend: 12 x 4096-point MSMs would eat the
    # extras deadline on the pure-python path
    bls.use_backend("jax")
    t0 = time.perf_counter()
    commitments = [spec.blob_to_kzg_commitment(b) for b in blobs]
    proofs = [spec.compute_blob_kzg_proof(b, c)
              for b, c in zip(blobs, commitments)]
    log(f"kzg setup (6 commitments+proofs): "
        f"{time.perf_counter() - t0:.1f}s")

    def measure(iters=3):
        t0 = time.perf_counter()
        for _ in range(iters):
            assert spec.verify_blob_kzg_proof_batch(blobs, commitments,
                                                    proofs)
        return (time.perf_counter() - t0) / iters

    bls.use_backend("py")
    py_dt = measure(iters=1)
    log(f"kzg batch py oracle: {py_dt:.2f}s")
    bls.use_backend("jax")
    if telemetry.enabled():
        telemetry.reset()   # count only the device-backend phase
    first = time.perf_counter()
    assert spec.verify_blob_kzg_proof_batch(blobs, commitments, proofs)
    log(f"kzg batch device compile+first: "
        f"{time.perf_counter() - first:.1f}s")
    dev_dt = measure()

    kzg = telemetry.embed_bench_block(
        {"value": round(dev_dt, 4), "unit": "s",
         "vs_baseline": round(py_dt / dev_dt, 1)})
    print(json.dumps({
        "blob_kzg_proof_batch_6_verify_wall": kzg, **device_fields(),
    }), flush=True)


def worker_spec() -> None:
    """Config #1: minimal-preset phase0 `state_transition` on 64
    validators with signatures ON — full-spec wall per signed block,
    device (jax) backend vs the pure-python oracle."""
    from consensus_specs_tpu import telemetry

    _worker_setup_jax()

    from consensus_specs_tpu.models.builder import build_spec
    from consensus_specs_tpu.ops import bls
    from consensus_specs_tpu.testlib.helpers.block import (
        build_empty_block_for_next_slot, sign_block)
    from consensus_specs_tpu.testlib.helpers.genesis import (
        create_genesis_state)

    spec = build_spec("phase0", "minimal")
    bls.bls_active = True
    state = create_genesis_state(
        spec, [int(spec.MAX_EFFECTIVE_BALANCE)] * 64,
        int(spec.MAX_EFFECTIVE_BALANCE))

    def transition_one(st):
        block = build_empty_block_for_next_slot(spec, st)
        shadow = st.copy()
        spec.process_slots(shadow, block.slot)
        spec.process_block(shadow, block)
        block.state_root = spec.hash_tree_root(shadow)
        signed = sign_block(spec, st.copy(), block)
        spec.state_transition(st, signed)

    def measure(iters=3):
        st = state.copy()
        t0 = time.perf_counter()
        for _ in range(iters):
            transition_one(st)
        return (time.perf_counter() - t0) / iters

    bls.use_backend("py")
    py_dt = measure()
    log(f"state_transition py oracle: {py_dt:.2f}s/block")
    bls.use_backend("jax")
    if telemetry.enabled():
        telemetry.reset()   # count only the device-backend phase
    transition_one(state.copy())  # compile
    dev_dt = measure()

    rec = telemetry.embed_bench_block(
        {"value": round(dev_dt, 4), "unit": "s",
         "vs_baseline": round(py_dt / dev_dt, 1)})
    print(json.dumps({
        "minimal_phase0_state_transition_signed_block_wall": rec,
        **device_fields(),
    }), flush=True)


# ---------------------------------------------------------------------------
# driver (parent process: never initializes a jax backend)
# ---------------------------------------------------------------------------

def _run_worker(mode: str, timeout: float):
    """Run `python bench.py --worker <mode>` and parse its last stdout line.
    Returns (dict | None, error_string)."""
    try:
        proc = subprocess.run(
            [sys.executable, str(HERE / "bench.py"), "--worker", mode],
            capture_output=True, text=True, timeout=timeout, cwd=str(HERE))
    except subprocess.TimeoutExpired:
        return None, f"{mode} worker timed out after {timeout:.0f}s"
    if proc.stderr:
        sys.stderr.write(proc.stderr[-4000:])
        sys.stderr.flush()
    if proc.returncode != 0:
        tail = " | ".join((proc.stderr or "").strip().splitlines()[-2:])
        return None, (f"{mode} worker rc={proc.returncode}: "
                      + tail[-300:])
    for line in reversed((proc.stdout or "").strip().splitlines()):
        try:
            return json.loads(line), ""
        except json.JSONDecodeError:
            continue
    return None, f"{mode} worker produced no JSON"


DEVICE_FIELDS = ("platform", "device_kind", "device_count")


def main():
    start = time.time()
    per_val_cpu = baseline_cpu_seconds_per_validator()
    baseline_s = per_val_cpu * N_VALIDATORS

    result, err = _run_worker("epoch", ATTEMPT_TIMEOUT)
    out = {
        "metric": "mainnet_epoch_sweep_1m_validators_wall",
        "value": None,
        "unit": "s",
        "vs_baseline": None,
    }
    if result is None:
        log(f"FAILED: {err}")
        out["error"] = err
    else:
        out["value"] = round(result["seconds"], 4)
        out["vs_baseline"] = round(baseline_s / result["seconds"], 1)
        out.update({k: result[k] for k in DEVICE_FIELDS})
        if "dirty_frac" in result:   # the incremental-flagship contract
            out["dirty_frac"] = result["dirty_frac"]
            out["parity_checks"] = result.get("parity_checks")
        if "telemetry" in result:    # CST_TELEMETRY=1 rounds: the
            out["telemetry"] = result["telemetry"]  # compile/run split

    # the flagship line goes out FIRST so an external driver timeout during
    # the extras can never lose it; the same record is appended to the
    # benchwatch store when CST_BENCHWATCH_HISTORY is set — incrementally,
    # for the same reason
    print(json.dumps(out), flush=True)
    benchwatch.append_emission(out, ts=time.time())
    if result is None:
        sys.exit(1)

    # extras — the mesh-sharded flagship scaling rungs (scaling), the
    # incremental-merkleization dirty-fraction sweep (merkle), then
    # BASELINE configs #2/#3 (bls), #5 (kzg blob batch), #1 (minimal
    # full transition): each starts only while inside the budget; each
    # success re-prints a superset JSON line (drivers parsing the first
    # or the last line both see the flagship metric), and a failure is
    # recorded and fails the run once the later extras have run
    failed = []
    for mode in ("scaling", "merkle", "das", "forkchoice", "bls", "kzg",
                 "spec"):
        elapsed = time.time() - start
        if elapsed >= EXTRAS_DEADLINE:
            log(f"extras deadline reached before {mode}")
            break
        log(f"--- {mode} extras (elapsed {elapsed:.0f}s) ---")
        extras, err = _run_worker(mode, ATTEMPT_TIMEOUT)
        if extras is None:
            log(f"FAILED: {err}")
            failed.append(err)
            continue
        device = {k: extras[k] for k in DEVICE_FIELDS}
        out.setdefault("extra", {}).update(extras)
        print(json.dumps(out), flush=True)
        for name, rec in extras.items():
            if isinstance(rec, dict) and "value" in rec:
                benchwatch.append_emission(
                    dict(rec, metric=name, **device), ts=time.time())
    if failed:
        out["error"] = "; ".join(failed)
        print(json.dumps(out), flush=True)
        sys.exit(1)


if __name__ == "__main__":
    if len(sys.argv) >= 3 and sys.argv[1] == "--worker":
        if sys.argv[2] == "epoch":
            worker_epoch(N_VALIDATORS)
        elif sys.argv[2] == "scaling":
            worker_scaling()
        elif sys.argv[2] == "merkle":
            worker_merkle()
        elif sys.argv[2] == "das":
            worker_das()
        elif sys.argv[2] == "forkchoice":
            worker_forkchoice()
        elif sys.argv[2] == "bls":
            worker_bls()
        elif sys.argv[2] == "kzg":
            worker_kzg()
        elif sys.argv[2] == "spec":
            worker_spec()
        else:
            raise SystemExit(f"unknown worker {sys.argv[2]!r}")
    else:
        main()
