"""The resilience layer (`consensus_specs_tpu/resilience/`):
deterministic fault injection at the sanctioned seams, retry/breaker/
degraded-mode recovery in the serve executor, deadline shedding, typed
bounded futures waits, self-healing Merkle state, the chaos round and
the `resilience` block schema.

Executor-layer tests run against stubbed dispatchers (the
tests/test_serve.py pattern) so blast-radius/retry/breaker contracts
are pinned cheaply; the oracle-fallback bit-identity and the chaos
round run real kernels on shapes tier-1 already compiles.
"""

from __future__ import annotations

import json
import textwrap
import time

import numpy as np
import pytest

from consensus_specs_tpu.resilience import faults
from consensus_specs_tpu.resilience.faults import (
    FaultInjected,
    MeshDeviceLost,
)
from consensus_specs_tpu.resilience.policies import (
    CLOSED,
    HALF_OPEN,
    OPEN,
    BreakerRegistry,
    CircuitBreaker,
    DeadlineExceeded,
    RetryPolicy,
)
from consensus_specs_tpu.serve.executor import ServeExecutor
from consensus_specs_tpu.serve.futures import (
    DeviceFuture,
    FutureError,
    FutureTimeout,
    value_future,
)
from consensus_specs_tpu.telemetry import validate_resilience_block


@pytest.fixture(autouse=True)
def _no_leaked_plan():
    """Every test starts and ends with fault injection OFF."""
    faults.clear()
    yield
    faults.clear()


# --- fault plans: schema, parsing, determinism -------------------------------


def test_plan_spec_string_round_trips():
    plan = faults.load_plan(
        "seed=9;dispatch:raise:key=rlc_*:count=3:after=1;"
        "serve_pump:latency:latency_ms=5:p=0.5")
    d = plan.describe()
    assert d["seed"] == 9
    assert d["faults"][0] == {"site": "dispatch", "kind": "raise",
                              "key": "rlc_*", "count": 3, "after": 1}
    assert d["faults"][1]["latency_ms"] == 5.0
    # the JSON form loads identically
    again = faults.load_plan(json.dumps(d))
    assert again.describe() == d


def test_invalid_plans_are_rejected_with_every_problem():
    problems = faults.validate_plan(
        {"seed": "x", "faults": [{"site": "nope", "kind": "raise"},
                                 {"site": "dispatch", "kind": "latency"}]})
    assert any("'seed'" in p for p in problems)
    assert any("'site'" in p for p in problems)
    assert any("latency_ms" in p for p in problems)
    with pytest.raises(ValueError, match="invalid fault plan"):
        faults.load_plan("dispatch:raise:key=")
    with pytest.raises(ValueError, match="site"):
        faults.load_plan("gpu:raise")
    with pytest.raises(ValueError):
        faults.load_plan("dispatch:raise:count=many")


def test_inactive_by_default_and_injection_is_gated():
    assert not faults.active()
    faults.maybe_inject("dispatch", "rlc_h2c@8")     # no plan: no-op
    assert faults.corrupt("dispatch", "k", 7) == 7
    assert faults.injections() == []


def test_disabled_overhead_bound():
    """The disabled seam (one maybe_inject + one corrupt per iteration,
    the shape of an instrumented dispatch) must stay a module-global
    read: 50k iterations well under 1.5s — same pattern and budget as
    telemetry's disabled-path bound."""
    assert not faults.active()
    n = 50_000
    t0 = time.perf_counter()
    for i in range(n):
        if faults.active():
            faults.maybe_inject("dispatch", "k")
        if faults.active():
            faults.corrupt("dispatch", "k", i)
    dt = time.perf_counter() - t0
    assert dt < 1.5, f"disabled fault seam too expensive: {dt:.3f}s"


def test_count_after_and_site_tagging():
    faults.install("dispatch:raise:key=rlc_*:count=2:after=1")
    faults.maybe_inject("dispatch", "rlc_h2c@8")        # after=1: skipped
    faults.maybe_inject("serve_pump", "verify")          # wrong site
    faults.maybe_inject("dispatch", "msm_pippenger@8w4")  # key mismatch
    for _ in range(2):
        with pytest.raises(FaultInjected) as ei:
            faults.maybe_inject("dispatch", "rlc_h2c@8")
        assert ei.value.site == "dispatch"
        assert ei.value.key == "rlc_h2c@8"
    faults.maybe_inject("dispatch", "rlc_h2c@8")        # count exhausted
    assert [i["site"] for i in faults.injections()] == ["dispatch"] * 2


def test_seeded_probability_replays_bit_for_bit():
    def fire_pattern():
        faults.install({"seed": 42, "faults": [
            {"site": "serve_pump", "kind": "raise", "p": 0.5}]})
        pattern = []
        for _ in range(32):
            try:
                faults.maybe_inject("serve_pump", "verify")
                pattern.append(0)
            except FaultInjected:
                pattern.append(1)
        faults.clear()
        return pattern

    a, b = fire_pattern(), fire_pattern()
    assert a == b
    assert 0 < sum(a) < 32      # actually probabilistic, actually seeded


def test_compile_fail_fires_once_per_key():
    faults.install("dispatch:compile_fail:key=rlc_*")
    with pytest.raises(FaultInjected):
        faults.maybe_inject("dispatch", "rlc_h2c@8")
    faults.maybe_inject("dispatch", "rlc_h2c@8")        # same key: passes
    with pytest.raises(FaultInjected):
        faults.maybe_inject("dispatch", "rlc_h2c@32")   # new shape: fires


def test_device_loss_is_typed():
    faults.install("dispatch:device_loss:count=1")
    with pytest.raises(MeshDeviceLost):
        faults.maybe_inject("dispatch", "anything")


def test_latency_fault_sleeps():
    faults.install("future_settle:latency:latency_ms=30:count=1")
    t0 = time.perf_counter()
    faults.maybe_inject("future_settle", "device")
    assert time.perf_counter() - t0 >= 0.025
    faults.maybe_inject("future_settle", "device")      # exhausted: fast


def test_corrupt_bitflips_ints_and_bools_nans_floats():
    faults.install({"faults": [
        {"site": "dispatch", "kind": "corrupt", "count": 4}]})
    flipped = faults.corrupt("dispatch", "k", np.arange(4, dtype=np.uint32))
    assert (flipped == np.arange(4, dtype=np.uint32) ^ 1).all()
    assert faults.corrupt("dispatch", "k", np.array(True)) == np.array(False)
    assert np.isnan(faults.corrupt("dispatch", "k", np.float32(1.5)))
    # tuples corrupt their LAST element (a layer stack's root layer)
    tup = (np.zeros(2, np.uint32), np.ones(2, np.uint32))
    out = faults.corrupt("dispatch", "k", tup)
    assert (out[0] == tup[0]).all() and (out[1] == tup[1] ^ 1).all()


# --- fault seams: dispatch + future settle -----------------------------------


def test_dispatch_seam_raises_and_corrupts(monkeypatch):
    """The `_dispatch` seam: a raise fault surfaces from the kernel
    dispatch; a corrupt fault flips the (device) output."""
    from consensus_specs_tpu.ops import bls_batch

    calls = []

    def fake_kernel(x):
        calls.append(x)
        return np.array(True)

    faults.install("dispatch:raise:key=fake@*:count=1")
    with pytest.raises(FaultInjected):
        bls_batch._dispatch("fake@8", fake_kernel, (1,))
    assert not calls                    # failed before the kernel ran
    faults.install("dispatch:corrupt:key=fake@*:count=1")
    out = bls_batch._dispatch("fake@8", fake_kernel, (2,))
    assert out == np.array(False)       # verdict flipped on "device"
    faults.clear()
    assert bls_batch._dispatch("fake@8", fake_kernel, (3,)) == np.array(True)


def test_future_settle_seam_poisons_exactly_that_future():
    faults.install("future_settle:raise:count=1")
    poisoned = value_future(np.array(7))
    healthy = value_future(np.array(8))
    with pytest.raises(FaultInjected) as ei:
        poisoned.result()
    assert ei.value.site == "future_settle"
    assert poisoned.exception() is ei.value     # settled failed, cached
    assert healthy.result() == 8                # blast radius: one future


# --- DeviceFuture timeouts ---------------------------------------------------


def test_unsettleable_waiter_is_lifecycle_error_not_timeout():
    """A waiter that gives back instantly without settling hit the
    lifecycle wall — reporting that as a retryable FutureTimeout would
    spin retry loops on a dead handle forever."""
    fut = DeviceFuture(waiter=lambda f: None)
    with pytest.raises(FutureError) as ei:
        fut.result(timeout=5.0)
    assert not isinstance(ei.value, FutureTimeout)
    assert not fut.done()
    with pytest.raises(FutureError):
        fut.result()                    # untimed contract unchanged


def test_budget_burning_waiter_raises_futuretimeout():
    def waiter(f, timeout=None):
        time.sleep(timeout)             # budget spent, still pending

    fut = DeviceFuture(waiter=waiter)
    with pytest.raises(FutureTimeout):
        fut.result(timeout=0.02)
    assert not fut.done()               # a timeout never settles


def test_result_timeout_passes_budget_to_timeout_aware_waiter():
    seen = {}

    def waiter(f, timeout=None):
        seen["timeout"] = timeout
        f.set_result("ok")

    fut = DeviceFuture(waiter=waiter)
    assert fut.result(timeout=2.5) == "ok"
    assert seen["timeout"] == 2.5


def test_exception_timeout_reraises_futuretimeout():
    def waiter(f, timeout=None):
        time.sleep(timeout)

    fut = DeviceFuture(waiter=waiter)
    with pytest.raises(FutureTimeout):
        fut.exception(timeout=0.01)


class _SlowDeviceValue:
    """A device value whose host fetch blocks (a wedged transfer)."""

    def __init__(self, delay_s):
        self.delay_s = delay_s

    def __array__(self, dtype=None, copy=None):
        time.sleep(self.delay_s)
        return np.array(123)


def test_device_backed_timeout_then_join_same_fetch():
    fut = value_future(_SlowDeviceValue(0.3), convert=int)
    t0 = time.perf_counter()
    with pytest.raises(FutureTimeout):
        fut.result(timeout=0.05)
    assert time.perf_counter() - t0 < 0.25      # actually bounded
    assert fut.result() == 123                  # joins the SAME fetch
    assert fut.done()


def test_executor_settle_until_respects_timeout(monkeypatch):
    """A wedged device batch must not block `.result(timeout=)` through
    the executor waiter chain — the one previously un-boundable wait."""
    from consensus_specs_tpu.serve import executor as ex_mod

    class _WedgedOps:
        def batch_verify_async(self, tasks, block=True):
            return value_future(_SlowDeviceValue(0.5), convert=bool)

    monkeypatch.setattr(ex_mod, "_ops_bls_batch", lambda: _WedgedOps())
    ex = ServeExecutor(max_batch=4)
    fut = ex.submit_verify_task(("pk", b"m", "sig"))
    t0 = time.perf_counter()
    with pytest.raises(FutureTimeout):
        fut.result(timeout=0.05)
    assert time.perf_counter() - t0 < 0.4
    assert ex.outstanding() == 1        # batch re-queued, not dropped
    assert fut.result() is True         # untimed settle still works


# --- executor: blast radius, retry, breaker, fallback, deadline --------------


class _ScriptedOps:
    """ops.bls_batch stand-in: immediate-settled verdicts (True unless
    scripted), counting dispatches."""

    def __init__(self):
        self.batches = []
        self.verdicts = []

    def _next(self):
        return self.verdicts.pop(0) if self.verdicts else True

    def batch_verify_async(self, tasks, block=True):
        self.batches.append(len(tasks))
        v = self._next()
        if isinstance(v, Exception):
            return DeviceFuture.failed(v)
        return DeviceFuture.settled(v)

    def pairing_check_device_async(self, pairs, block=True):
        return DeviceFuture.settled(self._next())


@pytest.fixture()
def scripted_ops(monkeypatch):
    from consensus_specs_tpu.serve import executor as ex_mod

    stub = _ScriptedOps()
    monkeypatch.setattr(ex_mod, "_ops_bls_batch", lambda: stub)
    return stub


def test_injected_fault_blast_radius_is_exactly_one_batch(scripted_ops):
    """A serve_pump fault on verify batch N fails exactly batch N's
    handles; batches N-1 and N+1 settle normally."""
    ex = ServeExecutor(max_batch=2)
    futs = [ex.submit_verify_task(i) for i in range(6)]  # 3 batches of 2
    faults.install("serve_pump:raise:key=verify:count=1:after=1")
    ex.drain()
    ok = [f for f in futs if f.exception() is None]
    failed = [f for f in futs if f.exception() is not None]
    assert len(failed) == 2 and len(ok) == 4
    assert failed == futs[2:4]          # exactly batch N (the second)
    assert all(isinstance(f.exception(), FaultInjected) for f in failed)
    assert all(f.result() is True for f in ok)
    assert [i["site"] for i in faults.injections()] == ["serve_pump"]


def test_retry_with_backoff_recovers_transient_fault(scripted_ops):
    ex = ServeExecutor(max_batch=4,
                       retry=RetryPolicy(max_attempts=3,
                                         base_backoff_s=0.001))
    futs = [ex.submit_verify_task(i) for i in range(4)]
    faults.install("serve_pump:raise:key=verify:count=2")
    ex.drain()
    assert all(f.result() is True for f in futs)    # healed by retry
    st = ex.stats()
    assert st["retries"] == 2 and st["failed"] == 0
    assert scripted_ops.batches == [4]  # third attempt reached the stub


def test_retry_policy_backoff_is_capped():
    p = RetryPolicy(max_attempts=5, base_backoff_s=0.1, max_backoff_s=0.3)
    assert [p.backoff_s(k) for k in (1, 2, 3, 4)] == [0.1, 0.2, 0.3, 0.3]
    assert p.should_retry(4) and not p.should_retry(5)


def test_breaker_state_machine_with_fake_clock():
    clock = [0.0]
    br = CircuitBreaker("k", threshold=2, cooldown_s=10.0,
                        clock=lambda: clock[0])
    assert br.state == CLOSED and br.allow()
    br.record_failure()
    assert br.state == CLOSED           # below threshold
    br.record_failure()
    assert br.state == OPEN and br.trips == 1
    assert not br.allow()               # cooling down
    clock[0] = 10.1
    assert br.allow()                   # the half-open probe
    assert br.state == HALF_OPEN
    assert not br.allow()               # one probe at a time
    br.record_failure()                 # probe failed
    assert br.state == OPEN and br.trips == 2
    clock[0] = 20.3
    assert br.allow()
    br.record_success()                 # probe succeeded
    assert br.state == CLOSED and br.allow()


def test_breaker_trip_routes_to_fallback_and_reclose(scripted_ops,
                                                     monkeypatch):
    """Persistent faults trip the (kind, rung) breaker; while OPEN the
    executor answers on the oracle (correct results, no poisoning);
    after the faults stop a half-open probe re-closes the breaker and
    traffic returns to the device."""
    from consensus_specs_tpu.serve import executor as ex_mod

    oracle_calls = []
    monkeypatch.setattr(
        ex_mod, "_oracle_compute",
        lambda kind, payload: oracle_calls.append((kind, payload)) or True)
    clock = [0.0]
    breakers = BreakerRegistry(threshold=2, cooldown_s=5.0,
                               clock=lambda: clock[0])
    ex = ServeExecutor(max_batch=4, breakers=breakers,
                       retry=RetryPolicy(max_attempts=2,
                                         base_backoff_s=0.0))
    faults.install("serve_pump:raise:key=verify:count=2")
    futs = [ex.submit_verify_task(i) for i in range(4)]
    ex.drain()
    # attempt 1 + retry both faulted -> breaker OPEN -> oracle served
    assert all(f.result() is True for f in futs)
    assert breakers.get("verify@4").state == OPEN
    assert len(oracle_calls) == 4 and ex.stats()["fallbacks"] == 4
    # still OPEN: more traffic stays on the oracle, device untouched
    futs = [ex.submit_verify_task(i) for i in range(4)]
    ex.drain()
    assert all(f.result() is True for f in futs)
    assert len(oracle_calls) == 8 and scripted_ops.batches == []
    # cooldown elapses; the probe goes to the (healed) device and the
    # breaker re-closes — device serves again
    clock[0] = 5.1
    futs = [ex.submit_verify_task(i) for i in range(4)]
    ex.drain()
    assert all(f.result() is True for f in futs)
    assert breakers.get("verify@4").state == CLOSED
    assert scripted_ops.batches == [4]
    assert len(oracle_calls) == 8       # no more fallback
    tos = [t["to"] for t in breakers.transitions]
    assert tos == ["open", "half_open", "closed"]


def test_deadline_sheds_oldest_with_typed_error(scripted_ops):
    ex = ServeExecutor(max_batch=4, deadline_ms=20.0)
    old = [ex.submit_verify_task(i) for i in range(2)]
    time.sleep(0.05)
    fresh = [ex.submit_verify_task(i) for i in range(2)]
    ex.drain()
    for f in old:
        exc = f.exception()
        assert isinstance(exc, DeadlineExceeded)
        assert exc.kind == "verify" and exc.age_s > exc.deadline_s
    assert all(f.result() is True for f in fresh)
    st = ex.stats()
    assert st["shed"] == 2 and st["failed"] == 2
    assert scripted_ops.batches == [2]  # only the fresh pair dispatched


def test_deadline_env_knob_arms_executor(monkeypatch):
    monkeypatch.setenv("CST_SERVE_DEADLINE_MS", "250")
    assert ServeExecutor().deadline_s == 0.25
    monkeypatch.setenv("CST_SERVE_DEADLINE_MS", "0")
    assert ServeExecutor().deadline_s is None


# --- oracle fallback bit-identity (real kernels) -----------------------------


@pytest.mark.slow
def test_oracle_fallback_verify_bit_identical_to_device():
    """Breaker-open degraded mode must return exactly the device
    verdicts: valid and invalid statements, via the real RLC kernel vs
    the pure-Python oracle.  `slow` like every RLC-compiling test
    (tier-1 pins the chaos fault plan's verify fallback at four
    statements, and the sha256/fr fallback identities, below)."""
    from consensus_specs_tpu.ops.bls_batch import batch_verify
    from consensus_specs_tpu.serve.executor import _oracle_compute
    from consensus_specs_tpu.serve.loadgen import build_statement_pool

    good = build_statement_pool(2, 2)
    pk, msg, sig = good[0]
    bad = (pk, b"\x13" * 32, sig)          # signature over another msg
    for task in (*good, bad):
        assert _oracle_compute("verify", task) == batch_verify([task])


@pytest.mark.parametrize("invalid", [False, True],
                         ids=["all_valid", "one_invalid"])
def test_chaos_fault_plan_leaves_no_wrong_or_dropped_answer(invalid,
                                                            monkeypatch):
    """The chaos round's canned plan (dispatch failures into the RLC
    kernel, at its real dispatch seam) against the recovery policies the
    round runs with, at four wire-format statements: every answer is the
    oracle's and none is dropped, while the breaker trips onto the
    oracle fallback and re-closes once the faults stop.  Parse, batch
    preparation and dispatch are the program's; the kernel is an exact
    stand-in that refuses a batch holding the invalid statement's
    message (the kernel itself is checked against the oracle by
    `tests/test_chip_smoke.py`)."""
    import jax.numpy as jnp

    from consensus_specs_tpu.ops import bls_batch
    from consensus_specs_tpu.ops.bls import ciphersuite as cs
    from consensus_specs_tpu.ops.bls_batch.h2c_jax import msgs_to_words
    from consensus_specs_tpu.resilience.chaos import (
        CHAOS_BREAKER,
        CHAOS_RETRY,
        DEFAULT_CHAOS_SPEC,
    )
    from consensus_specs_tpu.serve.loadgen import build_statement_pool

    wire = [(cs.g1_to_bytes(pk), msg, cs.g2_to_bytes(sig))
            for pk, msg, sig in build_statement_pool(4, 2, seed_base=8800)]
    if invalid:
        wire[2] = (wire[2][0], wire[2][1], wire[3][2])
    want = [cs.FastAggregateVerify([pk], msg, sig) for pk, msg, sig in wire]
    assert want.count(False) == int(invalid)
    bad_words = [msgs_to_words([m])[0]
                 for (_, m, _), ok in zip(wire, want) if not ok]

    def kernel(*args):
        (words,) = [np.asarray(a) for a in args
                    if np.asarray(a).dtype == np.uint32
                    and np.asarray(a).shape[-1:] == (8,)]
        return jnp.asarray(not any((words == w).all(axis=-1).any()
                                   for w in bad_words))

    monkeypatch.setattr(bls_batch, "_rlc_kernel_h2c", lambda batch: kernel)
    clock = [0.0]
    breakers = BreakerRegistry(**CHAOS_BREAKER, clock=lambda: clock[0])
    ex = ServeExecutor(max_batch=4, retry=RetryPolicy(**CHAOS_RETRY),
                       breakers=breakers)
    faults.install(DEFAULT_CHAOS_SPEC)
    try:
        for _ in range(4):
            futs = [ex.submit_fast_aggregate_verify([pk], msg, sig)
                    for pk, msg, sig in wire]
            ex.drain()
            assert [f.result() for f in futs] == want
            clock[0] += CHAOS_BREAKER["cooldown_s"] + 0.1
    finally:
        injected = faults.injections()
        faults.clear()
    assert len(injected) == 4
    assert {i["site"] for i in injected} == {"dispatch"}
    assert all(i["key"].startswith("rlc_h2c@") for i in injected)
    st = ex.stats()
    assert st["failed"] == 0 and st["settled"] == st["submitted"] == 16
    assert st["fallbacks"] >= 4
    tos = [t["to"] for t in breakers.transitions]
    assert "open" in tos and tos[-1] == "closed"


def test_flagship_breaker_arc_keeps_every_verdict(monkeypatch):
    """The chaos round's block-executor segment: a dispatch fault trips
    the settle breaker onto the pure-Python spec oracle, an OPEN breaker
    settles on the oracle alone, and the half-open probe re-closes;
    every settle answers as the statements are (valid), and both
    degraded settles are counted.  The kernel is an exact stand-in
    (every statement of the segment is valid)."""
    import jax.numpy as jnp

    from consensus_specs_tpu.ops import bls_batch
    from consensus_specs_tpu.resilience.chaos import _flagship_segment

    monkeypatch.setattr(bls_batch, "_rlc_kernel_h2c",
                        lambda batch: lambda *args: jnp.asarray(True))
    block = _flagship_segment()
    assert block["wrong_results"] == 0 and block["checked_settles"] == 4
    assert block["degraded_steps"] == 2
    assert block["breaker"]["trips"] == 1
    assert block["recovered"]
    assert not faults.active()


def test_oracle_fallback_sha256_and_fr_bit_identical():
    from consensus_specs_tpu.ops.fr_batch import barycentric_eval
    from consensus_specs_tpu.ops.sha256_jax import merkleize_words_jax
    from consensus_specs_tpu.serve.executor import _oracle_compute
    from consensus_specs_tpu.serve.loadgen import _fr_payload, _sha_payload

    words, limit = _sha_payload()
    assert (np.asarray(_oracle_compute("sha256", (words, limit)))
            == np.asarray(merkleize_words_jax(words, limit))).all()
    fr = _fr_payload()
    assert _oracle_compute("fr", fr) == barycentric_eval(*fr)
    # and the in-domain short-circuit agrees with the evaluation form
    poly, roots, _ = fr
    assert _oracle_compute("fr", (poly, roots, roots[1])) == poly[1]


# --- self-healing Merkle state -----------------------------------------------


def _forest(n=128, seed=11, limit_depth=9):
    from consensus_specs_tpu.parallel.incremental import MerkleForest

    rng = np.random.RandomState(seed)
    words = rng.randint(0, 2**32, (n, 8), dtype=np.uint64).astype(np.uint32)
    return MerkleForest(words, limit_depth, n), words


def test_corrupt_update_diverges_and_heals_to_ssz_oracle():
    from consensus_specs_tpu.resilience import healing

    forest, words = _forest()
    clean_root_before = forest.root_bytes()
    new_leaf = np.full((1, 8), 7, dtype=np.uint32)
    faults.install("merkle_update:corrupt:count=1")
    forest.update([5], new_leaf)
    faults.clear()
    assert healing.forest_diverged(forest)
    report = healing.heal_forest(forest)
    assert report.diverged and report.recovery_s > 0
    assert not forest.quarantined
    # the healed root matches an honest forest over the mutated leaves
    # AND the pure-Python SSZ oracle path
    words[5] = new_leaf[0]
    honest, _ = _forest()
    honest.update([5], new_leaf)
    assert forest.root_bytes() == honest.root_bytes() != clean_root_before
    from consensus_specs_tpu.resilience.healing import _reference_root_bytes
    assert forest.root_bytes() == _reference_root_bytes(forest)
    # proofs emitted from the healed stack verify against its root
    proofs = forest.emit_proofs([0, 5, 63])
    from consensus_specs_tpu.parallel import incremental
    assert all(incremental.verify_proof(p, forest.root_bytes())
               for p in proofs)


def test_clean_forest_heal_is_a_noop():
    from consensus_specs_tpu.resilience import healing

    forest, _ = _forest(n=32, limit_depth=6)
    root = forest.root_bytes()
    report = healing.heal_forest(forest)
    assert not report.diverged and report.recovery_s is None
    assert report.root == root == forest.root_bytes()


def test_heal_with_authoritative_leaves_repairs_leaf_corruption():
    """Source-state damage: the persisted leaves themselves drifted
    from the authority (a corrupted scatter applied consistently).  A
    rebuild from the PERSISTED leaves would keep the damage — the
    caller-supplied authority heals it."""
    from consensus_specs_tpu.resilience import healing

    forest, words = _forest(n=64, limit_depth=8)
    root = forest.root_bytes()
    forest.update([3], np.full((1, 8), 0xDEAD, dtype=np.uint32))
    # self-consistent but WRONG vs the authority
    assert not healing.forest_diverged(forest)
    assert healing.forest_diverged(forest, leaf_words=words)
    report = healing.heal_forest(forest, leaf_words=words)
    assert report.diverged and forest.root_bytes() == root


def test_quarantined_balances_forest_rebuild_matches_ssz_oracle():
    """The satellite contract verbatim: a corrupt fault diverges a
    balances forest mid-update; the quarantine/rebuild converges back
    to the pure-Python SSZ oracle's `hash_tree_root` of the same
    `List[uint64, N]` value."""
    import jax.numpy as jnp

    from consensus_specs_tpu.parallel import incremental
    from consensus_specs_tpu.resilience import healing
    from consensus_specs_tpu.utils.ssz.ssz_impl import hash_tree_root
    from consensus_specs_tpu.utils.ssz.ssz_typing import List, uint64

    rng = np.random.RandomState(23)
    bal = rng.randint(0, 2**63, 100, dtype=np.uint64)
    f = incremental.balances_forest(bal, 100, limit_depth=8)
    dirty = np.asarray([2, 41, 97], dtype=np.uint32)
    bal[dirty] = rng.randint(0, 2**63, 3, dtype=np.uint64)
    chunks = incremental.dirty_chunks_from_validators(dirty)
    leaves = incremental.dirty_balance_leaves(jnp.asarray(bal), chunks)
    faults.install("merkle_update:corrupt:count=1")
    f.update(chunks, leaves)
    faults.clear()
    oracle = bytes(hash_tree_root(List[uint64, 1024](
        *(int(b) for b in bal))))
    assert f.root_bytes() != oracle             # corrupt fault landed
    report = healing.heal_forest(f)
    assert report.diverged
    assert f.root_bytes() == report.root == oracle


# --- the chaos round + resilience records ------------------------------------


@pytest.mark.slow
def test_chaos_round_acceptance_arc():
    """The acceptance criterion, as a test: dispatch failures into the
    RLC kernel — zero wrong results, breaker trips, oracle fallback
    serves, breaker re-closes after the faults stop, finite recovery
    latency, schema-valid resilience block."""
    from consensus_specs_tpu.resilience.chaos import run_chaos_load
    from consensus_specs_tpu.serve.loadgen import LoadConfig
    from consensus_specs_tpu.telemetry import validate_serve_block

    cfg = LoadConfig(duration_s=6.0, rate=0.0, pool=2, committee=2,
                     windows=3, max_batch=8, depth=2)
    block = run_chaos_load(
        cfg, plan="seed=1234;dispatch:raise:key=rlc_*:count=4")
    assert not validate_serve_block(block)
    res = block["resilience"]
    assert not validate_resilience_block(res)
    assert res["faults_injected"] >= 1
    assert res["wrong_results"] == 0 and res["failed_requests"] == 0
    assert res["fallbacks"] >= 1
    assert res["breaker"]["trips"] >= 1
    # every breaker that saw post-fault traffic re-closed (a rung the
    # closed-loop batching never revisits keeps its open breaker — not
    # a failed recovery, which the recovered/steady asserts pin)
    assert any(t["from"] == "half_open" and t["to"] == "closed"
               for t in res["breaker"]["transitions"])
    assert any(s == "closed" for s in res["breaker"]["states"].values())
    assert res["recovered"] and 0 < res["recovery_latency_s"] < 300
    assert res["heal"]["diverged"] and res["heal"]["recovery_s"] > 0
    assert block["failed"] == 0
    assert not faults.active()          # the harness cleaned up


def _canned_resilience_block():
    return {
        "chaos": True, "faults_injected": 4,
        "injected_sites": {"dispatch": 4}, "wrong_results": 0,
        "failed_requests": 0, "checked_results": 500,
        "baseline_verifies_per_s": 16.7,
        "degraded_verifies_per_s": 11.5, "recovery_latency_s": 7.4,
        "recovered": True,
        "breaker": {"states": {"verify@8": "closed"}, "trips": 1,
                    "transitions": [
                        {"key": "verify@8", "from": "closed",
                         "to": "open"},
                        {"key": "verify@8", "from": "open",
                         "to": "half_open"},
                        {"key": "verify@8", "from": "half_open",
                         "to": "closed"}]},
        "retries": 2, "fallbacks": 120, "shed": 0,
        "heal": {"detected": True, "diverged": True,
                 "recovery_s": 0.02, "n_chunks": 256},
        "plan": {"seed": 1, "faults": [{"site": "dispatch",
                                        "kind": "raise"}]},
    }


def test_validate_resilience_block_flags_problems():
    assert validate_resilience_block("x")
    good = _canned_resilience_block()
    assert not validate_resilience_block(good)
    bad = dict(good, wrong_results=-1)
    assert any("wrong_results" in p
               for p in validate_resilience_block(bad))
    bad = dict(good, recovered=True, recovery_latency_s=None)
    assert any("recovery_latency_s" in p
               for p in validate_resilience_block(bad))
    bad = dict(good, breaker={"transitions": [{"key": "k"}],
                              "states": {}})
    assert validate_resilience_block(bad)
    bad = dict(good, heal={"diverged": True, "recovery_s": None})
    assert any("recovery_s" in p for p in validate_resilience_block(bad))


# --- the analyzer rule -------------------------------------------------------


def _analyze(src: str):
    from consensus_specs_tpu.analysis import analyze_source

    return analyze_source(textwrap.dedent(src), "snippet.py")


def _rules(report):
    return [(f.rule, f.line) for f in report.unsuppressed]


def test_exc_swallow_bare_and_broad_fire():
    report = _analyze("""\
        def f(batch):
            try:
                return dispatch(batch)
            except:
                return None

        def g(batch):
            try:
                return dispatch(batch)
            except Exception:
                pass
    """)
    assert ("exc-swallow-device", 4) in _rules(report)
    assert ("exc-swallow-device", 10) in _rules(report)


def test_exc_swallow_sanctioned_shapes_are_clean():
    report = _analyze("""\
        def poisons(reqs):
            try:
                dispatch(reqs)
            except Exception as exc:
                for req in reqs:
                    req.future.set_exception(exc)

        def stores(self):
            try:
                return fetch(self)
            except BaseException as exc:
                self._exc = exc

        def reraises(x):
            try:
                return go(x)
            except Exception:
                cleanup()
                raise

        def narrow(x):
            try:
                return int(x)
            except ValueError:
                return 0
    """)
    assert not [r for r in _rules(report) if r[0] == "exc-swallow-device"]


def test_exc_swallow_bound_but_unused_fires_and_suppression_works():
    report = _analyze("""\
        def f(x):
            try:
                return go(x)
            except Exception as exc:
                return None
    """)
    assert [r[0] for r in _rules(report)] == ["exc-swallow-device"]
    report = _analyze("""\
        def f(x):
            try:
                return go(x)
            # cst: allow(exc-swallow-device): default is the contract
            except Exception as exc:
                return None
    """)
    assert not report.unsuppressed
    assert report.suppressed[0][1] == "default is the contract"


def test_exc_swallow_scans_serve_and_resilience_tree_files():
    from pathlib import Path

    from consensus_specs_tpu.analysis.core import PKG_ROOT, _tree_files

    scanned = {str(p.relative_to(PKG_ROOT.parent))
               for p, roles in _tree_files(Path(PKG_ROOT))}
    assert "consensus_specs_tpu/serve/executor.py" in scanned
    assert "consensus_specs_tpu/serve/futures.py" in scanned
    assert "consensus_specs_tpu/resilience/faults.py" in scanned
