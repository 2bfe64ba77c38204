"""Mesh-resilient sharded verification (`resilience/mesh.py`):
shard-loss re-bucket recovery, the half-open re-admission state
machine, degraded-mode lane counters, and the `mesh` block.

State-machine and counter contracts run against a STUB dispatcher
(the tests/test_serve.py pattern) with an injectable clock, so tier-1
pins them without compiling mesh executables; the real sharded-kernel
parity arc (`device_loss` into `batch_verify_sharded`, recovery on the
surviving 8-host-device mesh, verdict parity vs the single-chip path)
is `@slow` like every other RLC-compiling test.
"""

from __future__ import annotations

import pytest

from consensus_specs_tpu.resilience import faults
from consensus_specs_tpu.resilience.faults import (
    FaultInjected,
    MeshDeviceLost,
)
from consensus_specs_tpu.resilience.mesh import (
    MeshState,
    MeshVerifier,
    is_device_failure,
)
from consensus_specs_tpu.serve.futures import DeviceFuture
from consensus_specs_tpu.telemetry import validate_mesh_block


@pytest.fixture(autouse=True)
def _no_leaked_plan():
    faults.clear()
    yield
    faults.clear()


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _verifier(n=4, cooldown=1.0, fail_widths=None, calls=None,
              clock=None):
    """A MeshVerifier over a stub dispatcher: dispatches whose device
    set's WIDTH is in `fail_widths` raise `MeshDeviceLost`; everything
    else settles True.  `calls` collects the device-id tuples."""
    fail_widths = fail_widths if fail_widths is not None else set()
    calls = calls if calls is not None else []
    clock = clock or FakeClock()

    def dispatch(tasks, rng, ids):
        calls.append(tuple(ids))
        if len(ids) in fail_widths:
            raise MeshDeviceLost("dispatch",
                                 f"rlc_sharded@{len(ids)}x8",
                                 "device_loss")
        return DeviceFuture.settled(True)

    return MeshVerifier(n_devices=n, readmit_cooldown_s=cooldown,
                        clock=clock, dispatch_fn=dispatch,
                        available_fn=lambda: n), calls, clock, fail_widths


# --- failure classification --------------------------------------------------


def test_device_failure_classification():
    assert is_device_failure(MeshDeviceLost("dispatch", "k",
                                            "device_loss"))
    assert not is_device_failure(ValueError("bad payload"))
    assert not is_device_failure(FaultInjected("dispatch", "k", "raise"))

    class XlaRuntimeError(RuntimeError):
        """Name-matched like jaxlib's (which this test must not import)."""

    assert is_device_failure(XlaRuntimeError("device dead"))


def test_non_device_exceptions_propagate_untouched():
    def dispatch(tasks, rng, ids):
        raise ValueError("malformed batch")

    mv = MeshVerifier(n_devices=4, dispatch_fn=dispatch,
                      available_fn=lambda: 4)
    with pytest.raises(ValueError):
        mv.verify(["t"])
    # no loss was recorded: a caller bug is not a dead device
    assert not mv.state.degraded()
    assert mv.state.lost_events == 0


# --- shard loss + re-bucket --------------------------------------------------


def test_loss_rebuckets_same_statements_over_survivors():
    mv, calls, _, fail = _verifier(n=4, fail_widths={4})
    assert mv.verify(["a", "b", "c"]) is True
    # first attempt on the full mesh, the recovery re-dispatch on the
    # 3 survivors — same statements, zero dropped
    assert calls == [(0, 1, 2, 3), (0, 1, 2)]
    assert mv.state.degraded() and mv.state.surviving() == (0, 1, 2)
    assert mv.redispatches == 1 and mv.lost_statements == 0
    assert mv.verified_statements == 3
    assert mv.recovery_latencies  # the recovery wall was recorded


def test_cascading_losses_walk_down_to_one_survivor():
    mv, calls, _, fail = _verifier(n=3, fail_widths={3, 2})
    assert mv.verify(["a"]) is True
    assert calls == [(0, 1, 2), (0, 1), (0,)]
    assert mv.state.surviving() == (0,)
    assert mv.max_degraded_lanes == 2


def test_all_devices_lost_surfaces_the_failure():
    mv, calls, _, fail = _verifier(n=2, fail_widths={2, 1})
    with pytest.raises(MeshDeviceLost):
        mv.verify(["a", "b"])
    assert mv.lost_statements == 2
    assert calls == [(0, 1), (0,)]


def test_settle_time_device_failure_recovers_too():
    """A loss surfacing at the future's settle (the transfer), not the
    dispatch — the real XlaRuntimeError shape."""
    calls = []

    def dispatch(tasks, rng, ids):
        calls.append(tuple(ids))
        if len(ids) == 4:
            return DeviceFuture.failed(
                MeshDeviceLost("future_settle", "device", "device_loss"))
        return DeviceFuture.settled(True)

    mv = MeshVerifier(n_devices=4, dispatch_fn=dispatch,
                      available_fn=lambda: 4)
    assert mv.verify(["a"]) is True
    assert calls == [(0, 1, 2, 3), (0, 1, 2)]
    assert mv.state.lost_events == 1


# --- the re-admission probe state machine ------------------------------------


def test_readmission_probe_state_machine():
    mv, calls, clock, fail = _verifier(n=4, cooldown=1.0,
                                       fail_widths={4})
    mv.verify(["a"])                       # loss -> degraded (3)
    assert mv.state.degraded()
    # before the cooldown: stays on the survivors, no probe
    assert mv.verify(["a"]) is True
    assert calls[-1] == (0, 1, 2)
    # cooldown elapsed, device still dead: probe fails -> re-trip
    clock.t = 1.5
    assert mv.verify(["a"]) is True
    assert calls[-2:] == [(0, 1, 2, 3), (0, 1, 2)]
    assert mv.state.retrips == 1 and mv.state.degraded()
    # re-trip restarted the cooldown: no probe yet at +0.5
    clock.t = 2.0
    assert mv.verify(["a"]) is True
    assert calls[-1] == (0, 1, 2)
    # device recovers: the next due probe re-admits the full mesh
    clock.t = 3.0
    fail.clear()
    assert mv.verify(["a"]) is True
    assert calls[-1] == (0, 1, 2, 3)
    assert not mv.state.degraded()
    assert mv.state.readmissions == 1


def test_mesh_state_counters_and_explicit_device():
    clock = FakeClock()
    st = MeshState(4, readmit_cooldown_s=2.0, clock=clock)
    st.mark_lost(1)
    assert st.surviving() == (0, 2, 3)
    st.mark_lost()                     # no attribution: highest survivor
    assert st.surviving() == (0, 2)
    assert st.lost_events == 2
    assert not st.probe_due()
    clock.t = 2.5
    assert st.probe_due()
    st.record_probe(True)
    assert st.surviving() == (0, 1, 2, 3) and st.readmissions == 1


# --- degraded-mode lane counters / the mesh block ----------------------------


def test_block_schema_and_history_round_trip():
    mv, calls, clock, fail = _verifier(n=4, fail_widths={4})
    mv.verify(["a", "b"])
    clock.t = 0.25                     # a nonzero recovery wall
    block = mv.block()
    block.update({"wrong_results": 0, "dropped_statements": 0,
                  "checked_statements": 2, "readmitted": False})
    assert validate_mesh_block(block) == []
    assert block["recovered"] is True
    assert block["lost_statements"] == 0
    assert block["device_lost_events"] == 1
    assert block["devices"] == 4 and block["redispatches"] == 1


def test_skipped_and_malformed_mesh_blocks_yield_no_records():
    assert validate_mesh_block({"skipped": "1 device(s)"}) == []
    assert validate_mesh_block(None) == []
    assert validate_mesh_block({"devices": True})  # bool is not an int


@pytest.mark.parametrize("when", ["on_the_lost_dispatch",
                                  "while_degraded"])
def test_invalid_statement_still_rejected_across_shard_loss(when):
    """The chaos mesh segment's safety check, on a dispatcher whose
    verdict is the statements' own: an invalid statement in the batch
    that loses a shard, or in one sent while degraded, is still refused
    on the survivors; nothing is dropped; the probe re-admits the
    mesh."""
    calls = []
    lose = [True]

    def dispatch(tasks, rng, ids):
        calls.append(tuple(ids))
        if lose[0] and len(ids) == 4:
            lose[0] = False
            raise MeshDeviceLost("dispatch", f"rlc_sharded@{len(ids)}x8",
                                 "device_loss")
        return DeviceFuture.settled("bad" not in tasks)

    clock = FakeClock()
    mv = MeshVerifier(n_devices=4, readmit_cooldown_s=1.0, clock=clock,
                      dispatch_fn=dispatch, available_fn=lambda: 4)
    good = ["a", "b", "c"]
    if when == "while_degraded":
        assert mv.verify(good) is True
    assert mv.verify(good + ["bad"]) is False
    assert calls[-1] == (0, 1, 2) and mv.state.degraded()
    assert mv.verify(good) is True
    assert mv.lost_statements == 0 and mv.state.lost_events == 1
    clock.t = 1.5
    assert mv.verify(good + ["bad"]) is False
    assert calls[-1] == (0, 1, 2, 3) and not mv.state.degraded()


# --- serve executor wiring ---------------------------------------------------


def test_serve_executor_routes_verify_batches_through_mesh():
    from consensus_specs_tpu.serve.executor import ServeExecutor

    mv, calls, _, fail = _verifier(n=4, fail_widths={4})
    ex = ServeExecutor(max_batch=8, mesh=mv)
    futs = [ex.submit_verify_task(("pk", b"m", "sig")) for _ in range(3)]
    ex.drain()
    assert [f.result() for f in futs] == [True, True, True]
    # the batch went through the mesh (loss -> recovery included)
    assert calls == [(0, 1, 2, 3), (0, 1, 2)]
    st = ex.stats()
    assert st["mesh"]["device_lost_events"] == 1
    assert st["mesh"]["lost_statements"] == 0
    assert st["failed"] == 0


# --- the real sharded path (slow: compiles mesh executables) -----------------


@pytest.mark.slow
def test_device_ids_subset_matches_single_chip_verdict():
    """`batch_verify_sharded` on an explicit surviving-device subset is
    verdict-identical to the single-chip path, for valid AND invalid
    statements — the re-bucket recovery's correctness contract."""
    import jax

    from consensus_specs_tpu.ops import bls_batch
    from consensus_specs_tpu.serve.loadgen import build_statement_pool

    if len(jax.devices()) < 3:
        pytest.skip("needs >= 3 devices (conftest forces 8 on CPU)")
    pool = build_statement_pool(3, 2, seed_base=8600)
    bad = (pool[0][0], pool[0][1], pool[1][2])
    assert bls_batch.batch_verify_sharded(pool, device_ids=(0, 1)) is True
    assert bls_batch.batch_verify_sharded(pool + [bad],
                                          device_ids=(0, 1)) is False


@pytest.mark.slow
def test_injected_device_loss_recovers_on_real_mesh():
    """The chaos-mesh arc against the real sharded kernels: one
    injected `device_loss` at the sharded dispatch seam; the verifier
    re-buckets onto the survivors, answers correctly, and the log shows
    exactly one injection."""
    import jax

    from consensus_specs_tpu.serve.loadgen import build_statement_pool

    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices")
    pool = build_statement_pool(2, 2, seed_base=8700)
    mv = MeshVerifier(readmit_cooldown_s=30.0)
    faults.install({"seed": 3, "faults": [
        {"site": "dispatch", "kind": "device_loss",
         "key": "rlc_sharded@*", "count": 1}]})
    try:
        assert mv.verify(list(pool)) is True
    finally:
        injected = faults.injections()
        faults.clear()
    assert len(injected) == 1 and injected[0]["kind"] == "device_loss"
    assert mv.state.lost_events == 1 and mv.lost_statements == 0
    assert mv.verified_statements == len(pool)
