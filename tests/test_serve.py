"""The serving subsystem (`consensus_specs_tpu/serve/`): deferred-result
futures, the batching executor, the sustained-load generator, and the
`"serve"` block schema.

Executor tests run against stubbed dispatchers (no jax, no kernels) so
the pipeline/batching/poisoning contracts are pinned cheaply; one
integration test drives real sha256 + barycentric kernels through the
executor on shapes tier-1 already compiles.  `DeferredBatch` edge cases
(empty settle, double verify, record-after-settle, exception
propagation) ride the same futures contract.
"""

from __future__ import annotations

import os
from types import SimpleNamespace

import numpy as np
import pytest

from consensus_specs_tpu.serve import (
    DeviceFuture,
    FutureError,
    bool_future,
    value_future,
)
from consensus_specs_tpu.serve.executor import ServeExecutor, _depth_bucket
from consensus_specs_tpu.telemetry import validate_serve_block


# --- DeviceFuture ------------------------------------------------------------


def test_settled_future_is_done_immediately():
    fut = DeviceFuture.settled(41)
    assert fut.done()
    assert fut.result() == 41
    assert fut.exception() is None


def test_failed_future_reraises_on_every_result():
    exc = ValueError("poisoned")
    fut = DeviceFuture.failed(exc)
    assert fut.done()
    for _ in range(2):
        with pytest.raises(ValueError, match="poisoned"):
            fut.result()
    assert fut.exception() is exc


def test_set_result_twice_raises():
    fut = DeviceFuture(waiter=lambda f: None)
    fut.set_result(True)
    with pytest.raises(FutureError):
        fut.set_result(False)
    with pytest.raises(FutureError):
        fut.set_exception(RuntimeError("x"))
    assert fut.result() is True


def test_pending_without_waiter_or_device_raises():
    with pytest.raises(FutureError, match="serve executor"):
        DeviceFuture().result()


def test_waiter_must_settle():
    fut = DeviceFuture(waiter=lambda f: None)
    with pytest.raises(FutureError, match="without settling"):
        fut.result()


def test_waiter_pumps_until_settled():
    calls = []

    def waiter(f):
        calls.append(1)
        f.set_result("ok")

    fut = DeviceFuture(waiter=waiter)
    assert not fut.done()
    assert fut.result() == "ok"
    assert fut.result() == "ok"     # cached, waiter not re-invoked
    assert calls == [1]


def test_value_future_fetches_and_converts_once():
    conversions = []

    def convert(host):
        conversions.append(host)
        return int(host) + 1

    fut = value_future(np.int64(41), convert=convert)
    assert fut.result() == 42
    assert fut.result() == 42
    assert conversions == [np.int64(41)]


def test_value_future_fetch_recurses_point_tuples():
    fut = value_future((np.int64(1), [np.int64(2), np.int64(3)]))
    got = fut.result()
    assert isinstance(got, tuple) and isinstance(got[1], tuple)
    assert got[0] == 1 and tuple(int(v) for v in got[1]) == (2, 3)


def test_bool_future_yields_python_bool():
    assert bool_future(np.bool_(True)).result() is True
    assert bool_future(np.bool_(False)).result() is False


def test_value_future_failed_convert_caches_exception():
    def convert(_host):
        raise RuntimeError("convert blew up")

    fut = value_future(np.int64(1), convert=convert)
    with pytest.raises(RuntimeError, match="convert blew up"):
        fut.result()
    with pytest.raises(RuntimeError, match="convert blew up"):
        fut.result()                # cached, not re-fetched


# --- DeferredBatch edge cases ------------------------------------------------


def _bls():
    from consensus_specs_tpu.ops import bls
    return bls


def _valid_statement(sk: int, msg: bytes):
    from consensus_specs_tpu.ops.bls import ciphersuite as cs

    return [cs.SkToPk(sk)], msg, cs.Sign(sk, msg)


def test_deferred_empty_batch_settles_true_idempotently():
    batch = _bls().DeferredBatch()
    assert batch.verify(device=False) is True
    assert batch.verify(device=False) is True
    assert batch.handles == []


def test_deferred_eager_reject_settles_handle_false():
    batch = _bls().DeferredBatch()
    assert batch.record([], b"m", b"\x00" * 96) is False
    assert batch.handles[-1].result() is False
    assert batch.verify(device=False) is False


def test_deferred_double_verify_dispatches_once(monkeypatch):
    from consensus_specs_tpu.ops import bls_batch

    batch = _bls().DeferredBatch()
    assert batch.record(*_valid_statement(7, b"serve-test")) is True
    calls = []
    monkeypatch.setattr(bls_batch, "batch_verify",
                        lambda tasks: calls.append(len(tasks)) or True)
    assert batch.verify(device=True) is True
    assert batch.verify(device=True) is True    # cached, no re-dispatch
    assert calls == [1]
    assert batch.handles[-1].result() is True


def test_deferred_record_after_settle_raises():
    batch = _bls().DeferredBatch()
    assert batch.verify(device=False) is True
    with pytest.raises(RuntimeError, match="already settled"):
        batch.record(*_valid_statement(7, b"late"))


def test_deferred_eager_reject_short_circuits_pending_handles():
    """One eager-invalid record fails the whole batch (block
    semantics): verify() never dispatches and every pending handle
    settles False alongside the rejected one."""
    batch = _bls().DeferredBatch()
    assert batch.record(*_valid_statement(7, b"one")) is True
    assert batch.record([], b"m", b"\x00" * 96) is False   # eager reject
    assert batch.verify(device=True) is False              # no dispatch
    assert [h.result() for h in batch.handles] == [False, False]


def test_deferred_failed_device_batch_poisons_every_handle(monkeypatch):
    from consensus_specs_tpu.ops import bls_batch

    batch = _bls().DeferredBatch()
    assert batch.record(*_valid_statement(7, b"one")) is True
    assert batch.record(*_valid_statement(8, b"two")) is True

    def boom(tasks):
        raise RuntimeError("device batch crashed")

    monkeypatch.setattr(bls_batch, "batch_verify", boom)
    with pytest.raises(RuntimeError, match="device batch crashed"):
        batch.verify(device=True)
    # verify() stays settled on its cached exception...
    with pytest.raises(RuntimeError, match="device batch crashed"):
        batch.verify(device=True)
    # ...and every pending handle got the device exception
    for handle in batch.handles:
        with pytest.raises(RuntimeError, match="device batch crashed"):
            handle.result()
        assert isinstance(handle.exception(), RuntimeError)


# --- ServeExecutor (stubbed dispatchers) -------------------------------------


class _StubOps:
    """Stand-in for ops.bls_batch: records dispatches, settles from a
    scripted verdict queue (True by default)."""

    def __init__(self):
        self.batches: list[int] = []
        self.verdicts: list[object] = []

    def _next(self, default=True):
        return self.verdicts.pop(0) if self.verdicts else default

    def batch_verify_async(self, tasks, block=True):
        self.batches.append(len(tasks))
        v = self._next()
        if isinstance(v, Exception):
            return DeviceFuture.failed(v)
        return DeviceFuture.settled(v)

    def pairing_check_device_async(self, pairs, block=True):
        return DeviceFuture.settled(self._next())

    def g1_multi_exp_device_async(self, points, scalars, block=True):
        return DeviceFuture.settled(("msm", len(points)))


@pytest.fixture()
def stub_ops(monkeypatch):
    from consensus_specs_tpu.serve import executor as ex_mod

    stub = _StubOps()
    monkeypatch.setattr(ex_mod, "_ops_bls_batch", lambda: stub)
    return stub


def test_executor_batches_verifies_to_max_batch(stub_ops):
    ex = ServeExecutor(max_batch=2, depth=1)
    futs = [ex.submit_verify_task(("pk", b"m", "sig")) for _ in range(5)]
    assert all(not f.done() for f in futs)
    ex.drain()
    assert stub_ops.batches == [2, 2, 1]
    assert all(f.result() is True for f in futs)
    st = ex.stats()
    assert st["submitted"] == st["settled"] == 5
    assert st["batches"] == 3 and st["failed"] == 0
    assert ex.outstanding() == 0


def test_executor_pipeline_holds_depth_batches_in_flight(stub_ops):
    ex = ServeExecutor(max_batch=1, depth=2)
    futs = [ex.submit_verify_task(("pk", b"m", "sig")) for _ in range(4)]
    ex.pump()
    # 4 single-statement batches dispatched; only the overflow beyond
    # depth=2 settles on a plain pump — the rest stay in flight so the
    # host can keep preparing work while the device runs
    assert [f.done() for f in futs] == [True, True, False, False]
    assert ex.outstanding() == 2
    ex.drain()
    assert all(f.done() for f in futs)


def test_executor_result_pumps_via_waiter(stub_ops):
    ex = ServeExecutor(max_batch=4, depth=2)
    fut = ex.submit_verify_task(("pk", b"m", "sig"))
    # no explicit pump(): result() reaches the waiter, which dispatches
    # the queue and settles through the executor
    assert fut.result() is True
    assert stub_ops.batches == [1]


def test_executor_false_batch_rechecks_per_statement(stub_ops,
                                                     monkeypatch):
    ex = ServeExecutor(max_batch=2, depth=1)
    monkeypatch.setattr(ServeExecutor, "_verify_single",
                        lambda self, task: task[0] == "good")
    f_good = ex.submit_verify_task(("good", b"m", "sig"))
    f_bad = ex.submit_verify_task(("bad", b"m", "sig"))
    stub_ops.verdicts = [False]
    ex.drain()
    assert f_good.result() is True
    assert f_bad.result() is False
    assert ex.stats()["rechecks"] == 1


def test_executor_failed_batch_poisons_handles_but_keeps_serving(stub_ops):
    ex = ServeExecutor(max_batch=2, depth=1)
    f1 = ex.submit_verify_task(("pk", b"m", "sig"))
    f2 = ex.submit_verify_task(("pk", b"m", "sig"))
    stub_ops.verdicts = [RuntimeError("batch died")]
    ex.drain()
    for f in (f1, f2):
        with pytest.raises(RuntimeError, match="batch died"):
            f.result()
    assert ex.stats()["failed"] == 2
    # the poisoned batch must not take the service down
    f3 = ex.submit_verify_task(("pk", b"m", "sig"))
    ex.drain()
    assert f3.result() is True
    assert ex.stats()["settled"] == 1


def test_executor_failed_recheck_poisons_handles_but_keeps_serving(
        stub_ops, monkeypatch):
    """A device error INSIDE the per-statement recheck path must follow
    the same poison-and-keep-serving contract as a failed batch — not
    escape pump() and strand the popped batch's handles."""
    ex = ServeExecutor(max_batch=2, depth=1)

    def boom(self, task):
        raise RuntimeError("recheck died")

    monkeypatch.setattr(ServeExecutor, "_verify_single", boom)
    f1 = ex.submit_verify_task(("pk", b"m", "sig"))
    f2 = ex.submit_verify_task(("pk", b"m", "sig"))
    stub_ops.verdicts = [False]          # False batch -> recheck path
    ex.drain()                           # must not raise
    for f in (f1, f2):
        with pytest.raises(RuntimeError, match="recheck died"):
            f.result()
    assert ex.stats()["failed"] == 2
    f3 = ex.submit_verify_task(("pk", b"m", "sig"))
    ex.drain()
    assert f3.result() is True


def test_executor_mixed_kinds_settle_independently(stub_ops, monkeypatch):
    from consensus_specs_tpu.ops import fr_batch, sha256_jax

    monkeypatch.setattr(
        sha256_jax, "merkleize_words_jax_async",
        lambda words, limit_depth: DeviceFuture.settled(("root", limit_depth)))
    monkeypatch.setattr(
        fr_batch, "barycentric_eval_async",
        lambda p, r, z: DeviceFuture.settled(z + 1))
    ex = ServeExecutor(max_batch=4, depth=1)
    fv = ex.submit_verify_task(("pk", b"m", "sig"))
    fp = ex.submit_pairing([("p", "q")])
    fm = ex.submit_msm(["P1", "P2"], [1, 2])
    fs = ex.submit_sha256_root(np.zeros((2, 8), np.uint32), 3)
    fr_ = ex.submit_barycentric([1, 2], [3, 4], 41)
    ex.drain()
    assert fv.result() is True and fp.result() is True
    assert fm.result() == ("msm", 2)
    assert fs.result() == ("root", 3)
    assert fr_.result() == 42
    st = ex.stats()
    assert st["settled"] == 5 and st["batches"] == 5


def test_executor_empty_fast_aggregate_verify_settles_false(stub_ops):
    ex = ServeExecutor()
    fut = ex.submit_fast_aggregate_verify([], b"msg", b"\x00" * 96)
    assert fut.done() and fut.result() is False
    assert ex.stats()["submitted"] == 0


def test_fast_aggregate_validation_shared_with_block_path(stub_ops):
    """Serve and DeferredBatch.record share ONE eager-validation helper
    (`ciphersuite.parse_fast_aggregate_task`) — garbage wire inputs are
    rejected identically on both paths, without touching a kernel."""
    from consensus_specs_tpu.ops.bls.ciphersuite import (
        parse_fast_aggregate_task,
    )

    assert parse_fast_aggregate_task([], b"m", b"\x00" * 96) is None
    assert parse_fast_aggregate_task([b"junk"], b"m", b"\x00" * 96) is None
    ex = ServeExecutor()
    fut = ex.submit_fast_aggregate_verify([b"junk"], b"m", b"\x00" * 96)
    assert fut.done() and fut.result() is False
    assert ex.stats()["submitted"] == 0 and stub_ops.batches == []


def test_dispatch_block_false_skips_sync_after_first_call(monkeypatch):
    """The serve pipeline's double-buffering must survive instrumented
    rounds: with telemetry ON, only the FIRST dispatch of a (kernel,
    shape) key blocks (compile attribution); later `block=False`
    dispatches enqueue without `block_until_ready` and observe
    `dispatch_s`, not `run_s`."""
    import jax

    from consensus_specs_tpu import telemetry
    from consensus_specs_tpu.ops.bls_batch import _dispatch

    synced = []
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: (synced.append(1), x)[1])
    was_enabled = telemetry.enabled()
    telemetry.configure(enabled=True)
    try:
        telemetry.reset(full=True)
        fn = lambda v: v + 1
        key = "serve_async_probe@8"
        assert _dispatch(key, fn, (1,), block=False) == 2
        assert synced == [1]            # first call blocks (compile split)
        assert _dispatch(key, fn, (2,), block=False) == 3
        assert synced == [1]            # pipelined call: enqueue only
        assert _dispatch(key, fn, (3,)) == 4
        assert synced == [1, 1]         # sync default still blocks
        hists = telemetry.snapshot()["histograms"]
        assert hists[f"kernel.{key}.compile_first_s"]["count"] == 1
        assert hists[f"kernel.{key}.dispatch_s"]["count"] == 1
        assert hists[f"kernel.{key}.run_s"]["count"] == 1
    finally:
        telemetry.reset(full=True)
        telemetry.configure(enabled=was_enabled)


def test_warm_kernels_covers_every_reachable_rung(monkeypatch):
    """Closed-loop verify chunks are `max_batch`-sized plus an arbitrary
    remainder, so warmup must hit EVERY `_bucket` ladder rung up to
    _bucket(max_batch) — a cold intermediate rung would compile inside
    a measured throughput window."""
    from consensus_specs_tpu.ops import bls_batch, fr_batch, sha256_jax
    from consensus_specs_tpu.serve import loadgen

    warmed = []
    monkeypatch.setattr(
        bls_batch, "batch_verify_async",
        lambda tasks, block=True:
        (warmed.append(len(tasks)), DeviceFuture.settled(True))[1])
    monkeypatch.setattr(bls_batch, "pairing_check_device_async",
                        lambda pairs, block=True: DeviceFuture.settled(True))
    monkeypatch.setattr(fr_batch, "barycentric_eval_async",
                        lambda p, r, z: DeviceFuture.settled(0))
    monkeypatch.setattr(sha256_jax, "merkleize_words_jax_async",
                        lambda w, d: DeviceFuture.settled("root"))
    from consensus_specs_tpu.parallel import incremental

    monkeypatch.setattr(incremental, "emit_proofs_async",
                        lambda forest, idx: DeviceFuture.settled([]))
    cfg = loadgen.LoadConfig(max_batch=512)
    loadgen._warm_kernels(cfg, [("pk", b"m", "sig")],
                          {"pairing": [("p", "q")], "fr": ([1], [1], 0),
                           "sha256": (None, 3), "proof": ("forest", [0])})
    assert sorted(warmed) == [8, 32, 128, 512]


def test_depth_bucket_labels():
    assert [_depth_bucket(n) for n in (0, 1, 2, 3, 4, 5, 9)] == \
        ["0", "1", "2", "4", "4", "8", "16"]


def test_executor_queue_depth_histogram(stub_ops):
    ex = ServeExecutor(max_batch=8, depth=1)
    for _ in range(3):
        ex.submit_verify_task(("pk", b"m", "sig"))
    ex.drain()
    st = ex.stats()
    assert st["queue_depth"]["max"] == 3
    # one sample per submit (depths 1, 2, 3) + one at 0 after dispatch
    assert st["queue_depth"]["hist"] == {"1": 1, "2": 1, "4": 1, "0": 1}


# --- loadgen -----------------------------------------------------------------


def test_steady_state_window_rule():
    from consensus_specs_tpu.serve.loadgen import steady_state

    assert not steady_state([10.0, 10.0])            # needs 3 windows
    assert steady_state([3.0, 10.0, 10.0, 10.0])     # ramp then flat
    assert steady_state([10.0, 11.9, 9.1])           # inside ±20%
    assert not steady_state([10.0, 13.0, 7.0])       # outside
    assert not steady_state([0.0, 0.0, 0.0])         # dead service


def test_percentile_ms_nearest_rank():
    from consensus_specs_tpu.serve.loadgen import percentile_ms

    assert percentile_ms([], 0.5) is None
    lat = [i / 1000.0 for i in range(1, 101)]        # 1..100 ms
    assert percentile_ms(lat, 0.0) == 1.0
    assert percentile_ms(lat, 1.0) == 100.0
    assert abs(percentile_ms(lat, 0.5) - 51.0) <= 1.0
    assert abs(percentile_ms(lat, 0.99) - 99.0) <= 1.0


def test_config_from_env_overrides(monkeypatch):
    from consensus_specs_tpu.serve.loadgen import config_from_env

    for k, v in (("CST_SERVE_DURATION_S", "2.5"), ("CST_SERVE_RATE", "0"),
                 ("CST_SERVE_POOL", "3"), ("CST_SERVE_COMMITTEE", "5"),
                 ("CST_SERVE_WINDOWS", "1"), ("CST_SERVE_MAX_BATCH", "7"),
                 ("CST_SERVE_DEPTH", "4")):
        monkeypatch.setenv(k, v)
    cfg = config_from_env()
    assert (cfg.duration_s, cfg.rate, cfg.pool, cfg.committee,
            cfg.max_batch, cfg.depth) == (2.5, 0.0, 3, 5, 7, 4)
    assert cfg.windows == 3                          # floor of 3


def test_run_load_closed_loop_reaches_steady_state(stub_ops, monkeypatch):
    """The full loadgen loop against stubbed dispatchers: tiny closed
    loop, schema-valid block, steady on a deterministic service."""
    from consensus_specs_tpu.serve import loadgen

    monkeypatch.setattr(loadgen, "build_statement_pool",
                        lambda n, k, seed_base=0: [("pk", b"m", "sig")] * n)
    monkeypatch.setattr(loadgen, "_pairing_payload",
                        lambda task: [("p", "q")])
    monkeypatch.setattr(loadgen, "_warm_kernels",
                        lambda cfg, pool, payloads: 0.0)
    from consensus_specs_tpu.ops import fr_batch, sha256_jax

    monkeypatch.setattr(sha256_jax, "merkleize_words_jax_async",
                        lambda w, d: DeviceFuture.settled(("root", d)))
    monkeypatch.setattr(fr_batch, "barycentric_eval_async",
                        lambda p, r, z: DeviceFuture.settled(0))
    from consensus_specs_tpu.parallel import incremental

    monkeypatch.setattr(loadgen, "_proof_payload",
                        lambda: ("forest", [1, 2]))
    monkeypatch.setattr(incremental, "emit_proofs_async",
                        lambda forest, idx: DeviceFuture.settled(
                            [("proof", i) for i in idx]))
    cfg = loadgen.LoadConfig(duration_s=0.9, rate=0.0, pool=2,
                             committee=2, windows=3, max_batch=4, depth=2)
    block = loadgen.run_load(cfg)
    assert validate_serve_block(block) == []
    assert block["mode"] == "closed"
    assert block["steady"] is True
    assert block["verifies_per_s"] > 0
    assert block["settled"] == block["submitted"] > 0
    assert block["failed"] == 0
    assert len(block["windows"]) >= 3
    # the arrival mix follows the per-slot schedule ratios
    kinds = block["kinds"]
    assert kinds["verify"] > kinds["fr"] > 0
    assert kinds["pairing"] >= 1 and kinds["sha256"] >= 1
    # the stateless-client lane rides the same pipeline
    assert kinds["proof"] >= 1


# --- serve block schema ------------------------------------------------------


def _good_block():
    return {
        "verifies_per_s": 123.4, "p50_ms": 10.0, "p99_ms": 25.0,
        "steady": True, "windows": [120.0, 125.0, 124.0],
        "submitted": 100, "settled": 100, "failed": 0,
        "queue_depth": {"max": 7, "hist": {"4": 3, "8": 2}},
        "mode": "closed",
    }


def test_validate_serve_block_accepts_good():
    assert validate_serve_block(_good_block()) == []


@pytest.mark.parametrize("mutate, needle", [
    (lambda b: b.update(verifies_per_s=-1), "verifies_per_s"),
    (lambda b: b.update(p99_ms=5.0), "p99_ms"),          # below p50
    (lambda b: b.update(steady="yes"), "steady"),
    (lambda b: b.update(windows="fast"), "windows"),
    (lambda b: b.update(settled=-2), "settled"),
    (lambda b: b.update(queue_depth={"hist": {}}), "queue_depth"),
    (lambda b: b.update(queue_depth={"max": 1, "hist": {"4": "x"}}),
     "hist"),
    (lambda b: b.update(mode="burst"), "mode"),
])
def test_validate_serve_block_rejects_bad(mutate, needle):
    block = _good_block()
    mutate(block)
    problems = validate_serve_block(block)
    assert problems and any(needle in p for p in problems), problems


def test_validate_serve_block_null_latencies_ok():
    block = _good_block()
    block["p50_ms"] = block["p99_ms"] = None     # zero settled requests
    assert validate_serve_block(block) == []


def test_validate_serve_block_non_dict():
    assert validate_serve_block([1, 2]) != []


# --- telemetry gauges (serve counter tracks) ---------------------------------


@pytest.fixture()
def _gauge_registry():
    from consensus_specs_tpu import telemetry
    from consensus_specs_tpu.telemetry import core

    saved = core._save_state()
    was_enabled = telemetry.enabled()
    telemetry.configure(enabled=True)
    telemetry.reset(full=True)
    yield telemetry
    telemetry.configure(enabled=was_enabled)
    core._restore_state(saved)


def test_gauge_aggregates_and_chrome_counter_track(_gauge_registry):
    telemetry = _gauge_registry
    for v in (3, 7, 2):
        telemetry.gauge("serve.queue_depth", v)
    snap = telemetry.snapshot()
    g = snap["gauges"]["serve.queue_depth"]
    assert g == {"last": 2.0, "min": 2.0, "max": 7.0, "count": 3}
    trace = telemetry.chrome_trace()
    counters = [e for e in trace["traceEvents"]
                if e.get("ph") == "C" and e["name"] == "serve.queue_depth"]
    assert [c["args"]["value"] for c in counters] == [3.0, 7.0, 2.0]
    # samples are timeline events: monotonically non-decreasing stamps
    ts = [c["ts"] for c in counters]
    assert ts == sorted(ts)


def test_gauge_reset_semantics(_gauge_registry):
    telemetry = _gauge_registry
    telemetry.gauge("serve.inflight_batches", 4)
    telemetry.reset()                    # per-config reset: aggregates go
    assert telemetry.snapshot()["gauges"] == {}
    trace = telemetry.chrome_trace()     # ...but the timeline survives
    assert any(e.get("ph") == "C" and e["name"] == "serve.inflight_batches"
               for e in trace["traceEvents"])
    telemetry.reset(full=True)           # full reset wipes the timeline
    assert not any(e.get("ph") == "C"
                   and e["name"] == "serve.inflight_batches"
                   for e in telemetry.chrome_trace()["traceEvents"])


def test_gauge_disabled_is_noop():
    from consensus_specs_tpu import telemetry
    from consensus_specs_tpu.telemetry import core

    saved = core._save_state()
    was_enabled = telemetry.enabled()
    telemetry.configure(enabled=False)
    try:
        # a CST_TELEMETRY session reaches here with gauges already
        # recorded by earlier serve tests — wipe the registry so the
        # no-op assertion sees only THIS gauge() call
        core.reset(full=True)
        telemetry.gauge("serve.queue_depth", 9)
        assert "serve.queue_depth" not in \
            telemetry.snapshot().get("gauges", {})
    finally:
        telemetry.configure(enabled=was_enabled)
        core._restore_state(saved)


# --- real kernels through the executor (shapes tier-1 already compiles) ------


def test_executor_real_sha256_and_barycentric_roundtrip():
    from consensus_specs_tpu.ops.fr_batch import R_MODULUS
    from consensus_specs_tpu.ops.sha256_np import merkleize_words

    rng = np.random.default_rng(42)
    words = rng.integers(0, 2**32, size=(8, 8), dtype=np.uint32)

    width = 8
    g = pow(7, (R_MODULUS - 1) // width, R_MODULUS)
    roots = [pow(g, i, R_MODULUS) for i in range(width)]
    poly = [(3 * i + 2) % R_MODULUS for i in range(width)]
    z = 0xCAFEBABE
    # spec evaluation loop (the oracle): sum_i poly_i * (roots_i/width)
    # * (z^width - 1) / (z - roots_i)
    expected = 0
    for i in range(width):
        num = poly[i] * roots[i] % R_MODULUS
        den = (z - roots[i]) % R_MODULUS
        expected = (expected + num * pow(den, -1, R_MODULUS)) % R_MODULUS
    expected = (expected * (pow(z, width, R_MODULUS) - 1)
                * pow(width, -1, R_MODULUS)) % R_MODULUS

    ex = ServeExecutor(max_batch=4, depth=2)
    f_root = ex.submit_sha256_root(words, 4)
    f_eval = ex.submit_barycentric(poly, roots, z)
    ex.drain()
    assert np.array_equal(f_root.result(), merkleize_words(words, 4))
    assert f_eval.result() == expected
    assert ex.stats()["settled"] == 2
