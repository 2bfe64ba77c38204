"""Both cells' code paths on the CPU at tiny sizes, past the look for a
chip: a sound run reads `correct`, and each control and each fault that a
cell can have, planted in the timed path, read not correct."""

import jax
import pytest

from benchmark import run

EPOCH_VALIDATORS = 256
SEED = 2**31 + 3


def epoch_cell():
    loaded = run.load_cell("epoch-1m")
    loaded["config"]["validators"] = EPOCH_VALIDATORS
    return loaded


def verify_cell():
    loaded = run.load_cell("verify-agg-backlog")
    loaded["traffic"].update(outstanding=8, statements_per_message=4,
                             pool_statements=16, warmup_statements=4,
                             probe_statements=8, workers=1,
                             reference_sample=2)
    return loaded


def rehearse(loaded, traced=False, seconds=1.0, **options):
    return run.run(loaded, SEED, seconds, traced=traced,
                   devices=jax.devices()[:1], **options)


@pytest.fixture
def host_kernel(monkeypatch):
    """The RLC batch kernel answered on the host by the program's oracle:
    with no `rng`, each statement checked alone (and remembered); with
    one, the batch check with the coefficients it draws."""
    from consensus_specs_tpu.ops import bls_batch
    from consensus_specs_tpu.ops.bls import ciphersuite, curve
    from consensus_specs_tpu.ops.bls.hash_to_curve import DST_G2, hash_to_g2
    from consensus_specs_tpu.serve.futures import DeviceFuture

    seen = {}

    def alone(task):
        key = (curve.g1_to_bytes(task[0]), bytes(task[1]),
               curve.g2_to_bytes(task[2]))
        if key not in seen:
            seen[key] = ciphersuite._pairing_check(
                ciphersuite.fast_aggregate_pairs(task))
        return seen[key]

    def kernel(tasks, rng=None, **kw):
        if rng is None:
            return DeviceFuture.settled(all(alone(t) for t in tasks))
        by_msg, sig_sum = {}, curve.g2.infinity()
        for pk, msg, sig in tasks:
            r = rng.getrandbits(128) | 1
            by_msg[bytes(msg)] = curve.g1.add(
                by_msg.get(bytes(msg), curve.g1.infinity()),
                curve.g1.mul(pk, r))
            sig_sum = curve.g2.add(sig_sum, curve.g2.mul(sig, r))
        pairs = [(p, hash_to_g2(m, DST_G2)) for m, p in by_msg.items()]
        pairs.append((curve.g1.neg(curve.G1_GEN), sig_sum))
        return DeviceFuture.settled(ciphersuite._pairing_check(pairs))

    monkeypatch.setattr(bls_batch, "batch_verify_async", kernel)
    return kernel


def test_no_chip_no_result(tmp_path):
    import subprocess
    import sys

    from benchmark.harness import ROOT

    proc = subprocess.run(
        [sys.executable, str(ROOT / "benchmark" / "run.py"), "--workload",
         "epoch-1m", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, cwd=tmp_path, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
             "HOME": str(tmp_path)})
    assert proc.returncode == 2
    assert proc.stdout == ""
    assert "needs a TPU" in proc.stderr


def test_epoch_sound_and_traced():
    # the CPU has no device plane: the readers find nothing to read
    result = rehearse(epoch_cell(), traced=True)
    assert result["correct"] is True, result["checks"]
    assert list(result)[-1] == "checks"
    assert result["metrics"] == {}
    assert result["device"]["busy_s"] == 0
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}


def test_epoch_control_is_refused():
    result = rehearse(epoch_cell(), control="float32_sweep")
    assert result["correct"] is False
    assert result["checks"]["balance_mismatches"]["value"] > 0


def _broken_step(monkeypatch, breakage):
    from consensus_specs_tpu import parallel

    real = parallel.make_sharded_epoch_step

    def make(mesh, params):
        step = real(mesh, params)
        return lambda reg, *rest: breakage(reg, *step(reg, *rest))

    monkeypatch.setattr(parallel, "make_sharded_epoch_step", make)


def test_epoch_state_unchanged_is_refused(monkeypatch):
    _broken_step(monkeypatch, lambda reg, bal, eff, br, rr:
                 (reg.balance, reg.effective_balance, br, rr))
    result = rehearse(epoch_cell())
    assert result["correct"] is False
    assert result["checks"]["balance_mismatches"]["value"] > 0


def test_epoch_altered_root_is_refused(monkeypatch):
    _broken_step(monkeypatch, lambda reg, bal, eff, br, rr:
                 (bal, eff, br, rr.at[3].add(1)))
    result = rehearse(epoch_cell())
    assert result["correct"] is False
    assert result["checks"]["root_mismatches"]["value"] > 0


def test_verify_sound_and_traced(host_kernel):
    # long enough for the host kernel to answer a batch and the loop to
    # refill inside the window
    result = rehearse(verify_cell(), traced=True, seconds=4.0)
    assert result["correct"] is True, result["checks"]
    assert result["attempted"] >= 8 + 4
    assert result["metrics"]["submit_ms"]["value"] > 0
    assert result["metrics"]["statements_per_batch"]["value"] > 0


def test_verify_unit_coefficients_control_is_refused(host_kernel):
    # the swapped signatures pass a batch check whose coefficients are 1
    result = rehearse(verify_cell(), control="unit_coefficients")
    assert result["correct"] is False
    assert result["checks"]["verdict_errors"]["value"] == 2
    assert result["checks"]["unanswered"]["value"] == 0


def test_verify_deadline_shed_control_is_refused(host_kernel, monkeypatch):
    from benchmark.systems import aggregate_verify

    # at this size nothing waits seconds: shed at once
    monkeypatch.setattr(aggregate_verify, "SHED_DEADLINE_MS", 1e-6)
    result = rehearse(verify_cell(), control="deadline_shed")
    assert result["correct"] is False
    assert result["checks"]["unanswered"]["value"] > 0


def test_verify_altered_answer_is_refused(host_kernel, monkeypatch):
    from consensus_specs_tpu.serve import executor
    from consensus_specs_tpu.serve.futures import DeviceFuture

    class Altered(DeviceFuture):
        settled_count = 0

        def set_result(self, value):
            Altered.settled_count += 1
            if Altered.settled_count == 512 + 5:  # past the warm-up batch
                value = not value
            super().set_result(value)

    monkeypatch.setattr(executor, "DeviceFuture", Altered)
    result = rehearse(verify_cell())
    assert result["correct"] is False
    assert result["checks"]["verdict_errors"]["value"] == 1


def test_verify_half_batch_left_out_is_refused(host_kernel, monkeypatch):
    from benchmark.systems import aggregate_verify
    from consensus_specs_tpu.serve.executor import ServeExecutor

    real = ServeExecutor._dispatch_one
    real_window = aggregate_verify.System.window

    def half(self, kind, reqs, attempt=1):
        return real(self, kind, reqs[:max(1, len(reqs) // 2)], attempt)

    def window(self, tracer):
        # past the warm-up: the window's batches lose half their requests
        monkeypatch.setattr(ServeExecutor, "_dispatch_one", half)
        return real_window(self, tracer)

    monkeypatch.setattr(aggregate_verify.System, "window", window)
    result = rehearse(verify_cell())
    assert result["correct"] is False
    assert result["checks"]["unanswered"]["value"] > 0


def test_verify_kernel_leaving_half_out_is_refused(host_kernel, monkeypatch):
    """A kernel that leaves the second half of each batch out of its
    product and answers for the whole batch: the window's valid batches
    still pass, the probes tampered in the second half do too."""
    from consensus_specs_tpu.ops import bls_batch

    monkeypatch.setattr(bls_batch, "batch_verify_async",
                        lambda tasks, **kw: host_kernel(
                            tasks[:len(tasks) // 2], **kw))
    result = rehearse(verify_cell())
    assert result["correct"] is False
    assert result["checks"]["verdict_errors"]["value"] == 2


def test_verify_kernel_accepting_unseen_is_refused(monkeypatch):
    """A kernel that answers true without looking."""
    from consensus_specs_tpu.ops import bls_batch
    from consensus_specs_tpu.serve.futures import DeviceFuture

    monkeypatch.setattr(bls_batch, "batch_verify_async",
                        lambda tasks, **kw: DeviceFuture.settled(True))
    result = rehearse(verify_cell())
    assert result["correct"] is False
    assert result["checks"]["verdict_errors"]["value"] == 4
    assert result["checks"]["unanswered"]["value"] == 0
