"""Sustained-load generator — mainnet-rate traffic against ServeExecutor.

Models the steady traffic a production verifier faces (ROADMAP's
"sustained-load attestation-verification service benchmark"): ~1M
validators' attestations arrive per epoch as per-slot aggregate
statements, alongside one sync-committee aggregate, blob-KZG
evaluations, and state-root merkleizations.  The generator feeds that
mix — at a multiple of the mainnet arrival rate, or in closed-loop mode
at whatever rate the device sustains — through one `ServeExecutor` and
measures windowed throughput until it reaches steady state.

Arrival model (per mainnet slot, 12 s):

    64  attestation aggregate statements (MAX_COMMITTEES_PER_SLOT —
        1,048,576 validators / 32 slots / ~512-strong committees)
     1  sync-committee aggregate (pairing check)
     6  blob-KZG barycentric evaluations (BASELINE config #5's blobs)
     1  state-root sha256 merkleization
     2  batched SSZ single-proof emissions from a persistent
        `parallel.incremental.MerkleForest` (`submit_proof_request` —
        the stateless-client proof queries light clients issue)
     2  data-column sampling checks (`submit_das_sample` — the PeerDAS
        custody columns a node re-verifies per slot; samples queued in
        the same pump fold into ONE RLC cell-proof equation;
        CST_DAS_SAMPLES_PER_SLOT overrides, 0 disables the lane)
     2  fork-choice attestation batches + 1 LMD-GHOST head poll
        (`submit_attestation_batch`/`submit_head_request` against a
        synthetic proto-array store — the per-attestation bookkeeping
        every client runs; CST_FC_ATTS_PER_SLOT overrides, 0 disables
        the lane and its head poll)
     0  damaged-blob reconstructions (`submit_recover_request` — the
        super-node path: erasure-decode a >= 50%-surviving cell set and
        FK20 re-prove it on device; the heaviest single request, so
        OPT-IN via CST_DAS_RECOVER_PER_SLOT, with CST_DAS_RECOVER_COLS
        surviving cells per ingest)

`rate <= 0` switches to closed-loop mode: the generator keeps
`max_batch * (depth + 1)` requests outstanding and the measured rate IS
the device's sustained capacity — the mode the CPU smoke uses, since a
fixed open-loop rate on an arbitrary CI host would either idle or grow
the queue without bound.

Steady state: windowed verifies/sec, steady when the last 3 windows sit
within ±20% of their mean; the run extends past the configured window
count (up to 3x) until that holds, so "reaches steady state" is a
measured property, not an assumption.  Kernel warmup (AOT precompile of
the `_bucket` rungs the load will hit) happens before the clock starts.

Knobs (all `CST_SERVE_*`, see README "Serving"): duration, rate
multiple, statement-pool size, committee size, window count, max batch,
pipeline depth.
"""

from __future__ import annotations

import itertools
import os
import time
from dataclasses import dataclass

from .. import telemetry
from ..telemetry import metrics_export, monitor, occupancy, reqtrace
from .executor import ServeExecutor

SLOT_SECONDS = 12.0
MAINNET_VALIDATORS = 1_048_576          # the Wonderboom million-scale regime
ATT_STATEMENTS_PER_SLOT = 64            # MAX_COMMITTEES_PER_SLOT aggregates
SYNC_STATEMENTS_PER_SLOT = 1
KZG_EVALS_PER_SLOT = 6
SHA_ROOTS_PER_SLOT = 1
PROOF_REQUESTS_PER_SLOT = 2             # stateless-client proof queries


# an unparseable value fails loudly at import, like every other
# CST_SERVE_* knob — a typo'd "disable" must not silently run the lane
DAS_SAMPLES_PER_SLOT = max(
    0, int(os.environ.get("CST_DAS_SAMPLES_PER_SLOT", 2)))
# fork-choice lane: attestation batches feeding the proto-array store
# per slot (each batch carries FC_BATCH_MESSAGES latest-message
# updates) plus one LMD-GHOST head poll; 0 disables the lane
FC_ATTS_PER_SLOT = max(
    0, int(os.environ.get("CST_FC_ATTS_PER_SLOT", 2)))
HEAD_POLLS_PER_SLOT = 1 if FC_ATTS_PER_SLOT else 0
FC_BATCH_MESSAGES = 64
# super-node lane: damaged-blob reconstructions per slot (ingest a
# >= 50%-surviving cell set, reconstruct + FK20 re-prove on device,
# re-serve; the breaker degrades to the pure-Python oracle).  A full
# reconstruction is the heaviest single request the executor carries,
# so the lane is OPT-IN (default 0); CST_DAS_RECOVER_COLS sets how many
# cells survive each damaged ingest (default 64 — exactly half, the
# worst recoverable case)
RECOVER_PER_SLOT = max(
    0, int(os.environ.get("CST_DAS_RECOVER_PER_SLOT", 0)))
RECOVER_COLS = min(128, max(
    64, int(os.environ.get("CST_DAS_RECOVER_COLS", 64))))
STATEMENTS_PER_SLOT = (ATT_STATEMENTS_PER_SLOT + SYNC_STATEMENTS_PER_SLOT
                       + KZG_EVALS_PER_SLOT + SHA_ROOTS_PER_SLOT
                       + PROOF_REQUESTS_PER_SLOT + DAS_SAMPLES_PER_SLOT
                       + FC_ATTS_PER_SLOT + HEAD_POLLS_PER_SLOT
                       + RECOVER_PER_SLOT)
STEADY_TOL = 0.2


@dataclass
class LoadConfig:
    duration_s: float = 45.0
    rate: float = 4.0        # multiple of the mainnet arrival rate; <= 0
                             # switches to closed-loop (device-capacity) mode
    pool: int = 32           # distinct precomputed statements to cycle
    committee: int = 64      # aggregated keys per attestation statement
    windows: int = 6         # throughput windows inside duration_s
    max_batch: int = 128     # statements per RLC dispatch (ladder rung)
    depth: int = 2           # in-flight batches (double-buffer default)

    def __post_init__(self):
        # Steady-state needs 3 windows; the clamp lives here so every
        # construction path (env, CLI flags, tests) gets it.
        self.windows = max(3, int(self.windows))
        self.pool = max(1, int(self.pool))
        self.max_batch = max(1, int(self.max_batch))
        self.depth = max(1, int(self.depth))


def config_from_env() -> LoadConfig:
    """LoadConfig with CST_SERVE_* overrides applied to the defaults."""
    d = LoadConfig()
    return LoadConfig(
        duration_s=float(os.environ.get("CST_SERVE_DURATION_S",
                                        d.duration_s)),
        rate=float(os.environ.get("CST_SERVE_RATE", d.rate)),
        pool=int(os.environ.get("CST_SERVE_POOL", d.pool)),
        committee=int(os.environ.get("CST_SERVE_COMMITTEE", d.committee)),
        windows=int(os.environ.get("CST_SERVE_WINDOWS", d.windows)),
        max_batch=int(os.environ.get("CST_SERVE_MAX_BATCH", d.max_batch)),
        depth=int(os.environ.get("CST_SERVE_DEPTH", d.depth)),
    )


def steady_state(rates, tol: float = STEADY_TOL) -> bool:
    """True when the last 3 window rates sit within ±tol of their mean."""
    if len(rates) < 3:
        return False
    last = rates[-3:]
    mean = sum(last) / 3.0
    if mean <= 0:
        return False
    return all(abs(r - mean) <= tol * mean for r in last)


def percentile_ms(latencies_s, q: float) -> float | None:
    """q-th percentile of a latency sample, in milliseconds (None on
    empty input).  Delegates to `reqtrace._percentile` — ONE
    nearest-rank implementation, so the serve block's p50/p99 and the
    attribution engine's per-kind percentiles can never diverge on the
    same round's data."""
    if not latencies_s:
        return None
    return round(reqtrace._percentile(sorted(latencies_s), q) * 1e3, 3)


WORST_EXEMPLARS = 5     # exemplar traces retained in latency_attribution


def latency_block(ex) -> tuple[float | None, float | None, dict | None]:
    """(p50_ms, p99_ms, latency_attribution) for one finished drive.

    Traced rounds (CST_TRACE_REQUESTS) compute the percentiles from the
    per-request lifecycle records — submit→complete, answered requests
    only — and attach `reqtrace.attribution()` (per-kind p50/p90/p99
    decomposed into queue_wait/batch_form/device_wall/settle/detour,
    worst-N exemplars).  Untraced rounds return the executor's
    enqueue→settle sample and no attribution.  ONE implementation so
    `run_load` and the chaos harness cannot diverge on latency
    semantics."""
    if not reqtrace.enabled():
        return (percentile_ms(ex.latencies_s, 0.50),
                percentile_ms(ex.latencies_s, 0.99), None)
    recs = reqtrace.records()
    answered = [r["e2e_s"] for r in recs
                if r.get("e2e_s") is not None
                and r.get("outcome") in reqtrace.ANSWERED]
    return (percentile_ms(answered, 0.50),
            percentile_ms(answered, 0.99),
            reqtrace.attribution(recs, worst_n=WORST_EXEMPLARS))


# --- request payload pools ---------------------------------------------------


def build_statement_pool(n_tasks: int, keys_per_task: int,
                         seed_base: int = 7000):
    """Valid FastAggregateVerify statements as (agg_pk, msg, sig) oracle
    points — the aggregate-secret-key shortcut (one scalar mult per
    side), identical in shape to real per-key aggregation."""
    from ..ops.bls import ciphersuite as cs
    from ..ops.bls.curve import g1, g2
    from ..ops.bls.hash_to_curve import DST_G2, hash_to_g2

    tasks = []
    for t in range(n_tasks):
        msg = (seed_base + t).to_bytes(32, "little")
        h = hash_to_g2(msg, DST_G2)
        agg_sk = sum(seed_base + t * keys_per_task + i + 1
                     for i in range(keys_per_task))
        tasks.append((g1.mul(cs.G1_GEN, agg_sk), msg, g2.mul(h, agg_sk)))
    return tasks


def _pairing_payload(task):
    """A sync-aggregate-shaped pairing check for one pool statement —
    the shared FastAggregateVerify identity."""
    from ..ops.bls.ciphersuite import fast_aggregate_pairs

    return fast_aggregate_pairs(task)


def _fr_payload(width: int = 4):
    """A width-W barycentric evaluation (minimal-preset blob shape)."""
    from ..ops.fr_batch import R_MODULUS

    g = pow(7, (R_MODULUS - 1) // width, R_MODULUS)
    roots = [pow(g, i, R_MODULUS) for i in range(width)]
    poly = [(3 * i + 2) % R_MODULUS for i in range(width)]
    return (poly, roots, 0x1234567)


def _sha_payload():
    import numpy as np

    return (np.arange(64, dtype=np.uint32).reshape(8, 8), 3)


def _das_payloads(n_blobs: int = 2, columns=(0, 17)):
    """A tiny closed-form sampling matrix cut into per-column
    `DasSample`s (cycled by the das lane) — real pairing statements,
    zero MSM setup cost (`das.ciphersuite.closed_form_matrix`)."""
    from ..das.ciphersuite import closed_form_matrix
    from ..das.sampling import sample_from_matrix

    matrix = closed_form_matrix(n_blobs, columns=columns)
    return [sample_from_matrix(*matrix, column) for column in columns]


def _fc_payload(n_blocks: int = 48, n_validators: int = 256,
                batch: int = FC_BATCH_MESSAGES):
    """A synthetic proto-array store plus an infinite attestation-batch
    stream — the `submit_attestation_batch`/`submit_head_request`
    lane's payload (`forkchoice.synthetic`)."""
    from ..forkchoice.synthetic import attestation_stream, synthetic_store

    store, roots = synthetic_store(n_blocks, n_validators, seed=53)
    return store, attestation_stream(roots, n_validators, batch,
                                     seed=53)


def _recover_payloads(n_patterns: int = 3, survive: int = RECOVER_COLS,
                      seed: int = 4100):
    """Damaged-blob ingests for the super-node lane: one low-degree
    (closed-form) blob's full cell set, cut down to `survive` cells
    under `n_patterns` distinct damage patterns (cycled by the lane).
    The blob is degree-65 so building the ground-truth cells costs two
    host FFTs, not an MSM."""
    import random

    from ..das import ciphersuite as dcs
    from ..das import compute as dc

    roots = dcs.roots_of_unity(dcs.FIELD_ELEMENTS_PER_BLOB)
    evals = []
    for i in range(dcs.FIELD_ELEMENTS_PER_BLOB):
        x = roots[dcs.reverse_bits(i, dcs.FIELD_ELEMENTS_PER_BLOB)]
        evals.append((seed * pow(x, 65, dcs.BLS_MODULUS)
                      + (seed + 1) * pow(x, 64, dcs.BLS_MODULUS)
                      + seed + 2) % dcs.BLS_MODULUS)
    cells = dc.compute_cells(dcs._encode_evals(evals), device=False)
    rng = random.Random(seed)
    out = []
    for _ in range(n_patterns):
        keep = sorted(rng.sample(range(dcs.CELLS_PER_EXT_BLOB),
                                 survive))
        out.append((keep, [cells[k] for k in keep]))
    return out


def _proof_payload(n_leaves: int = 256, batch: int = 16):
    """A persistent `MerkleForest` plus one index batch — the
    `submit_proof_request` payload shape (the forest is built once and
    shared across every proof request of the run, exactly the
    stateless-client serving posture)."""
    import numpy as np

    from ..parallel.incremental import MerkleForest

    rng = np.random.RandomState(31)
    words = rng.randint(0, 2**32, (n_leaves, 8),
                        dtype=np.uint64).astype(np.uint32)
    forest = MerkleForest(words, 10, n_leaves)
    return (forest, [int(i) for i in rng.choice(n_leaves, batch,
                                                replace=False)])


# self-scrape artifact (written whenever the CST_METRICS_PORT endpoint
# is live during a measured load): the exposition text exactly as an
# external Prometheus would have seen it.  The round scrapes mid-load
# and again after the drain; the kept snapshot is the latest one, so
# the artifact carries every served kind as a labeled series
SCRAPE_ARTIFACT = "out/metrics_scrape.txt"


def scrape_live_endpoint() -> str | None:
    """One GET against the process's own exposition endpoint — the
    mid-round scrape.  Returns the exposition text, or None when the
    endpoint is down (never raises: a failed scrape must not fail the
    measured load)."""
    port = metrics_export.serving_port()
    if port is None:
        return None
    import urllib.request

    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=10) as resp:
            return resp.read().decode("utf-8")
    except Exception as exc:
        # recorded, not raised: a failed scrape must not fail the round
        telemetry.count("serve.scrape_failed")
        telemetry.add_event("serve.scrape_failed", 0.0,
                            error=type(exc).__name__)
        return None


def write_scrape_artifact(text: str, path: str = SCRAPE_ARTIFACT) -> str:
    dirname = os.path.dirname(path)
    if dirname:
        os.makedirs(dirname, exist_ok=True)
    with open(path, "w") as f:
        f.write(text)
    return path


# --- the load loop -----------------------------------------------------------


def make_submitter(ex, pool, payloads, track=None):
    """The ONE implementation of the mainnet per-slot arrival mix (see
    module docstring): returns `(submit_next, kinds_submitted)` where
    each `submit_next()` call submits the next request of the cycled
    slot schedule to `ex`.  `track(kind, future)`, when given, sees
    every submitted handle — the chaos harness's correctness-tracking
    hook.  Shared by `run_load` and `resilience.chaos.run_chaos_load`
    so the two drives cannot diverge on the traffic shape."""
    schedule = itertools.cycle(
        ["verify"] * ATT_STATEMENTS_PER_SLOT
        + ["pairing"] * SYNC_STATEMENTS_PER_SLOT
        + ["fr"] * KZG_EVALS_PER_SLOT
        + ["sha256"] * SHA_ROOTS_PER_SLOT
        + ["proof"] * PROOF_REQUESTS_PER_SLOT
        + ["das"] * DAS_SAMPLES_PER_SLOT
        + ["fc_atts"] * FC_ATTS_PER_SLOT
        + ["head"] * HEAD_POLLS_PER_SLOT
        + ["recover"] * RECOVER_PER_SLOT)
    pool_iter = itertools.cycle(pool)
    das_iter = itertools.cycle(payloads["das"]) if payloads.get("das") \
        else None
    recover_iter = itertools.cycle(payloads["recover"]) \
        if payloads.get("recover") else None
    fc_store, fc_batches = payloads["fc"] if payloads.get("fc") \
        else (None, None)
    kinds_submitted = {k: 0 for k in ("verify", "pairing", "fr",
                                      "sha256", "proof", "das",
                                      "recover", "fc_atts", "head")}

    def submit_next():
        kind = next(schedule)
        kinds_submitted[kind] += 1
        if kind == "verify":
            fut = ex.submit_verify_task(next(pool_iter))
        elif kind == "pairing":
            fut = ex.submit_pairing(payloads["pairing"])
        elif kind == "fr":
            fut = ex.submit_barycentric(*payloads["fr"])
        elif kind == "sha256":
            fut = ex.submit_sha256_root(*payloads["sha256"])
        elif kind == "das":
            fut = ex.submit_das_sample(next(das_iter))
        elif kind == "recover":
            fut = ex.submit_recover_request(*next(recover_iter))
        elif kind == "fc_atts":
            fut = ex.submit_attestation_batch(fc_store,
                                              *next(fc_batches))
        elif kind == "head":
            fut = ex.submit_head_request(fc_store)
        else:
            fut = ex.submit_proof_request(*payloads["proof"])
        if track is not None:
            track(kind, fut)

    return submit_next, kinds_submitted


def drive_closed_loop(ex, submit_next, target_outstanding: int,
                      window_end: float) -> None:
    """One closed-loop drive window: keep `target_outstanding`
    requests outstanding and pump until `window_end`
    (`time.perf_counter()` deadline) — the device-capacity mode both
    the CPU smoke and the chaos phases measure."""
    while time.perf_counter() < window_end:
        while ex.outstanding() < target_outstanding:
            submit_next()
        ex.pump()


def _warm_kernels(cfg: LoadConfig, pool, payloads) -> float:
    """AOT-compile every executable the load will hit, OUTSIDE the
    measured window; returns the warmup wall."""
    from ..ops.bls_batch import (
        _BUCKET_STEPS,
        _bucket,
        batch_verify_async,
        pairing_check_device_async,
    )
    from ..ops.fr_batch import barycentric_eval_async
    from ..ops.sha256_jax import merkleize_words_jax_async
    from ..parallel.incremental import emit_proofs_async

    t0 = time.perf_counter()
    # verify chunks are `max_batch`-sized plus one arbitrary remainder,
    # so EVERY ladder rung up to _bucket(max_batch) is reachable inside
    # the measured window — warm them all (power-of-two rungs past the
    # ladder top for oversized max_batch), or the first chunk landing
    # on a cold rung pays XLA compile inside a throughput window
    top = _bucket(cfg.max_batch)
    rungs = {s for s in _BUCKET_STEPS if s <= top} | {top}
    r = max(_BUCKET_STEPS)
    while r < top:
        r <<= 1
        rungs.add(r)
    for rung in sorted(rungs):
        batch_verify_async([pool[0]] * rung).result()
    pairing_check_device_async(payloads["pairing"]).result()
    barycentric_eval_async(*payloads["fr"]).result()
    merkleize_words_jax_async(*payloads["sha256"]).result()
    emit_proofs_async(*payloads["proof"]).result()
    if payloads.get("das"):
        from ..das.sampling import verify_sample_async

        verify_sample_async(payloads["das"][0], device=True).result()
    if payloads.get("recover"):
        from ..das.recover import recover_cells_and_kzg_proofs_async

        recover_cells_and_kzg_proofs_async(
            *payloads["recover"][0], device=True).result()
    if payloads.get("fc"):
        fc_store, fc_batches = payloads["fc"]
        fc_store.apply_attestations_async(*next(fc_batches)).result()
        fc_store.get_head_async().result()
    return time.perf_counter() - t0


def _default_executor(cfg: LoadConfig) -> ServeExecutor:
    """The load's executor.  With a fault plan active
    (`resilience.faults`), the recovery policies arm automatically —
    retry with backoff plus per-(kind, rung) breakers routing to the
    oracle fallback — so a faulted `make serve-smoke` degrades to
    correct-but-slow answers instead of poisoning requests.  Without a
    plan the executor keeps the plain fail-fast shape (zero resilience
    machinery on the healthy path)."""
    from ..resilience import faults

    retry = breakers = None
    if faults.active():
        from ..resilience.chaos import CHAOS_BREAKER, CHAOS_RETRY
        from ..resilience.policies import BreakerRegistry, RetryPolicy

        retry = RetryPolicy(**CHAOS_RETRY)
        breakers = BreakerRegistry(**CHAOS_BREAKER)
    return ServeExecutor(max_batch=cfg.max_batch, depth=cfg.depth,
                         retry=retry, breakers=breakers)


def run_load(cfg: LoadConfig | None = None, executor=None) -> dict:
    """Drive the serve executor with the configured load; returns the
    `"serve"` block (schema pinned by
    `telemetry.export.validate_serve_block`).

    `CST_SERVE_CHAOS=1` delegates to the chaos harness
    (`resilience.chaos.run_chaos_load`: baseline → fault plan live →
    recovery-to-steady), whose block additionally carries the
    `"resilience"` sub-object."""
    cfg = cfg if cfg is not None else config_from_env()
    if executor is None \
            and os.environ.get("CST_SERVE_CHAOS", "0") not in ("", "0"):
        from ..resilience.chaos import run_chaos_load

        return run_chaos_load(cfg)
    pool = build_statement_pool(cfg.pool, cfg.committee)
    payloads = {"pairing": _pairing_payload(pool[0]),
                "fr": _fr_payload(), "sha256": _sha_payload(),
                "proof": _proof_payload(),
                "das": (_das_payloads() if DAS_SAMPLES_PER_SLOT else []),
                "recover": (_recover_payloads() if RECOVER_PER_SLOT
                            else []),
                "fc": (_fc_payload() if FC_ATTS_PER_SLOT else None)}
    warm_s = _warm_kernels(cfg, pool, payloads)
    # a CST_FAULTS plan goes live only AFTER warmup: AOT precompile is
    # setup, not served traffic — the plan's fault budget must land on
    # the measured load (where the executor's recovery ladder answers),
    # not crash the warmup's direct kernel settles
    from ..resilience import faults

    faults.install_from_env()
    ex = executor if executor is not None else _default_executor(cfg)
    # request tracing (CST_TRACE_REQUESTS): scope the lifecycle-record
    # registry to THIS measured load — warmup settles and any earlier
    # run's records must not pollute the attribution
    if reqtrace.enabled():
        reqtrace.reset()
    # occupancy ledger (CST_OCCUPANCY): same scoping rule — the busy /
    # bubble attribution must cover the measured load only, so warmup
    # dispatch stamps are discarded here
    if occupancy.enabled():
        occupancy.reset()
    # live monitoring arms with the measured load (same placement rule
    # as the fault plan: warmup is setup, not served traffic) — the
    # CST_METRICS_PORT endpoint starts scraping this executor's status
    # and the CST_SLO_RULES watchdog begins its tick
    watchdog = monitor.install_from_env(status_provider=ex.status)
    # deterministic per-slot arrival mix (see module docstring)
    submit_next, kinds_submitted = make_submitter(ex, pool, payloads)

    closed_loop = cfg.rate <= 0
    rate_per_s = cfg.rate * STATEMENTS_PER_SLOT / SLOT_SECONDS
    target_outstanding = cfg.max_batch * (cfg.depth + 1)
    window_s = cfg.duration_s / cfg.windows

    rates: list[float] = []
    t0 = time.perf_counter()
    settled_prev = 0
    arrived = 0
    scrape_text = None
    for wi in range(3 * cfg.windows):       # extend (≤3x) until steady
        # Anchor each window at its actual start and divide by the wall
        # it really spanned: a single pump that overruns the nominal
        # boundary (one full RLC settle can) must not fabricate a
        # zero-rate window that defeats the steady-state check.
        win_t0 = time.perf_counter()
        window_end = win_t0 + window_s
        if closed_loop:
            drive_closed_loop(ex, submit_next, target_outstanding,
                              window_end)
        else:
            while time.perf_counter() < window_end:
                due = (time.perf_counter() - t0) * rate_per_s
                while arrived < due:
                    submit_next()
                    arrived += 1
                ex.pump()
                time.sleep(0.002)
        win_elapsed = time.perf_counter() - win_t0
        settled_now = ex.stats()["settled"]
        rates.append((settled_now - settled_prev) / win_elapsed)
        settled_prev = settled_now
        # the mid-round scrape: once, after traffic has flowed for half
        # the configured windows — the exposition snapshot an external
        # scraper would see while the service is under load
        if scrape_text is None and wi + 1 >= max(1, cfg.windows // 2):
            scrape_text = scrape_live_endpoint()
        if wi + 1 >= cfg.windows and steady_state(rates):
            break
    measured_s = time.perf_counter() - t0
    ex.drain()
    # close the occupancy window AFTER the drain so the post-load tail
    # shows up as the `drain` bubble cause instead of vanishing
    occ_block = (occupancy.block(window=(t0, time.perf_counter()),
                                 depth=cfg.depth)
                 if occupancy.enabled() else None)
    # a final live scrape supersedes the mid-round one when it lands:
    # the endpoint and status provider are still wired, and with the
    # queue drained every served kind has completed — so the artifact
    # always carries the full per-kind `cst_serve_requests_total`
    # series set (a slow-to-warm kind can be absent from the mid-round
    # snapshot)
    scrape_text = scrape_live_endpoint() or scrape_text
    if scrape_text is not None:
        write_scrape_artifact(scrape_text)
    # finalize the watchdog BEFORE tearing down the status provider so
    # its last tick still sees the live executor
    slo_block = monitor.clear() if watchdog is not None else None
    metrics_export.set_status_provider(None)

    st = ex.stats()
    steady = steady_state(rates)
    steady_rate = (sum(rates[-3:]) / 3.0 if len(rates) >= 3
                   else (st["settled"] / measured_s if measured_s else 0.0))
    # latency basis (the serve-block schema's `latency_source` field):
    # on traced rounds the percentiles are PER-REQUEST, submit→complete
    # from the RequestContext timestamps — the batch-settle-granularity
    # numbers understate the request tail by collapsing every member of
    # a batch onto one settle stamp (and miss retry/fallback detours
    # entirely).  Untraced rounds keep the executor's enqueue→settle
    # sample so the metric never goes dark.
    p50_ms, p99_ms, latency_attribution = latency_block(ex)
    block = {
        "verifies_per_s": round(steady_rate, 2),
        "p50_ms": p50_ms,
        "p99_ms": p99_ms,
        "latency_source": ("reqtrace" if latency_attribution is not None
                          else "executor"),
        "steady": steady,
        "windows": [round(r, 2) for r in rates],
        "window_s": round(window_s, 3),
        "duration_s": round(measured_s, 3),
        "warmup_s": round(warm_s, 3),
        "mode": "closed" if closed_loop else "open",
        "rate_multiple": cfg.rate,
        "offered_per_s": None if closed_loop else round(rate_per_s, 3),
        "pool": cfg.pool,
        "committee": cfg.committee,
        "max_batch": cfg.max_batch,
        "depth": cfg.depth,
        "kinds": kinds_submitted,
        "submitted": st["submitted"],
        "settled": st["settled"],
        "failed": st["failed"],
        "rechecks": st["rechecks"],
        "batches": st["batches"],
        "queue_depth": st["queue_depth"],
        "inflight_max": st["inflight_max"],
    }
    if latency_attribution is not None:
        block["latency_attribution"] = latency_attribution
    if occ_block is not None:
        block["occupancy"] = occ_block
    if slo_block is not None:
        block["slo"] = slo_block
    return block
