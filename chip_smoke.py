"""Bring-up smoke of the main path on one TPU chip.

Two phases, each through the repo's own entry points and each checked
against independent code:

- `epoch`: the flagship (BASELINE #4 state size) — a mainnet-preset
  registry of 2**20 validators, resident on the device, through
  `parallel.epoch_sweep` + `balances_list_root` + `validator_registry_root`
  in one jitted step.  Both roots must equal a host merkleization of the
  device's own output arrays (`ops.sha256_np`), and a real 1024-validator
  mainnet genesis state with attestations must come out of the device
  sweep bit for bit as the pure-Python spec leaves it.
- `serve`: a `serve.ServeExecutor` with its default policies answering
  128 FastAggregateVerify requests from 64-member committees (BASELINE #2's
  batch) and tampered requests; every answer must equal the pure-Python
  oracle's, and the executor must report no fallback, retry or failure.

`python chip_smoke.py` insists on a TPU and the full sizes.  Lines before
the last are bring-up observations, not benchmark results.  The last line
is `{"ok": true, "device": {...}}`, printed only when every phase passed;
any failure exits non-zero without it.
"""

from __future__ import annotations

import functools
import hashlib
import json
import sys
import time

import numpy as np

EPOCH_VALIDATORS = 1 << 20
GENESIS_VALIDATORS = 1024
EPOCH_STEPS = 3
SERVE_ATTESTATIONS = 128
SERVE_COMMITTEE = 64
SERVE_TAMPERED = 2
SERVE_STEADY_ROUNDS = 2

# SSZ limits: List[uint64, 2**40] packs 4 per chunk; List[Validator, 2**40]
BALANCES_CHUNK_LIMIT = 1 << 38
REGISTRY_LIMIT = 1 << 40


def setup_jax():
    """x64 on and the persistent compile cache."""
    import jax

    jax.config.update("jax_enable_x64", True)
    from consensus_specs_tpu.utils.jaxtools import enable_compile_cache

    enable_compile_cache()
    return jax


def observe(phase: str, **fields) -> None:
    print("bring-up observation (not a benchmark): "
          + json.dumps({"phase": phase, **fields}), flush=True)


# --- host references (numpy / hashlib, independent of the device code) -----


def _mix_in_length(root: bytes, length: int) -> bytes:
    return hashlib.sha256(root + length.to_bytes(32, "little")).digest()


def _u64_leaf_chunks(values: np.ndarray) -> np.ndarray:
    """(N,) uint64 -> (N, 32) uint8 SSZ uint64 leaf chunks."""
    out = np.zeros((values.shape[0], 32), np.uint8)
    out[:, :8] = values.astype("<u8").view(np.uint8).reshape(-1, 8)
    return out


def host_balances_root(balances: np.ndarray) -> bytes:
    from consensus_specs_tpu.ops.sha256_np import merkleize_chunks_bytes

    data = balances.astype("<u8").tobytes()
    data += b"\x00" * (-len(data) % 32)
    return _mix_in_length(
        merkleize_chunks_bytes(data, BALANCES_CHUNK_LIMIT), len(balances))


def host_registry_root(pubkey_root, credentials, effective_balance,
                       slashed, activation_eligibility_epoch,
                       activation_epoch, exit_epoch,
                       withdrawable_epoch) -> bytes:
    """hash_tree_root of the List[Validator] whose records carry these
    fields (the two static leaves as (N, 8) big-endian words)."""
    from consensus_specs_tpu.ops.sha256_np import (
        merkleize_chunks_bytes, words_to_chunks)

    n = effective_balance.shape[0]
    leaves = memoryview(np.stack(
        [words_to_chunks(pubkey_root), words_to_chunks(credentials)]
        + [_u64_leaf_chunks(np.asarray(f, np.uint64)) for f in (
            effective_balance, slashed, activation_eligibility_epoch,
            activation_epoch, exit_epoch, withdrawable_epoch)],
        axis=1).tobytes())
    # each record's 8 leaves -> its root, by hashlib (a per-record loop
    # runs ~10x faster than the batched numpy SHA-256 at this depth)
    sha = hashlib.sha256
    records = bytearray(32 * n)
    for i in range(n):
        b = 256 * i
        left = sha(sha(leaves[b:b + 64]).digest()
                   + sha(leaves[b + 64:b + 128]).digest()).digest()
        right = sha(sha(leaves[b + 128:b + 192]).digest()
                    + sha(leaves[b + 192:b + 256]).digest()).digest()
        records[32 * i:32 * i + 32] = sha(left + right).digest()
    return _mix_in_length(
        merkleize_chunks_bytes(bytes(records), REGISTRY_LIMIT), n)


def _root_bytes(words) -> bytes:
    return np.asarray(words).astype(">u4").tobytes()


# --- phase: epoch -----------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _epoch_step(params):
    import jax

    from consensus_specs_tpu.parallel import (
        ValidatorLeaves, balances_list_root, epoch_sweep,
        validator_records_root, validator_registry_root)

    @jax.jit
    def step(reg, sc, length, pubkey_root, credentials):
        new_bal, new_eff = epoch_sweep(reg, sc, params, axis_name=None)
        bal_root = balances_list_root(new_bal, length)
        records = validator_records_root(
            ValidatorLeaves(pubkey_root, credentials), new_eff, reg.slashed,
            reg.activation_eligibility_epoch, reg.activation_epoch,
            reg.exit_epoch, reg.withdrawable_epoch)
        return (new_bal, new_eff, bal_root,
                validator_registry_root(records, length))

    return step


def check_sweep_against_spec(n_validators: int) -> None:
    """A real mainnet genesis state with attestations (plus slashed
    validators) through the state bridge and the device sweep must leave
    balances and effective balances bit for bit where the pure-Python
    spec leaves them."""
    from consensus_specs_tpu.models.builder import build_spec
    from consensus_specs_tpu.parallel import (
        EpochParams, RegistryArrays, make_epoch_step, pad_pow2,
        registry_arrays_from_state)
    from consensus_specs_tpu.testlib.context import (
        default_activation_threshold)
    from consensus_specs_tpu.testlib.helpers.attestations import (
        prepare_state_with_attestations)
    from consensus_specs_tpu.testlib.helpers.genesis import (
        create_genesis_state)
    from consensus_specs_tpu.utils.ssz.ssz_impl import hash_tree_root

    spec = build_spec("phase0", "mainnet")
    state = create_genesis_state(
        spec, [spec.MAX_EFFECTIVE_BALANCE] * n_validators,
        default_activation_threshold(spec))
    prepare_state_with_attestations(spec, state)
    for i in (1, n_validators // 3, n_validators - 2):
        v = state.validators[i]
        v.slashed = True
        v.withdrawable_epoch = spec.Epoch(
            int(spec.get_current_epoch(state))
            + int(spec.EPOCHS_PER_SLASHINGS_VECTOR) // 2)
        state.slashings[0] += v.effective_balance
    spec.process_justification_and_finalization(state)

    reg, sc = registry_arrays_from_state(spec, state)
    reg = RegistryArrays(*(pad_pow2(np.asarray(a)) for a in reg))
    step = make_epoch_step(EpochParams.from_spec(spec))
    new_bal, new_eff, root = step(reg, sc, np.uint64(n_validators))

    spec.process_rewards_and_penalties(state)
    spec.process_slashings(state)
    spec.process_effective_balance_updates(state)
    want_bal = np.array([int(b) for b in state.balances], np.uint64)
    want_eff = np.array([int(v.effective_balance) for v in state.validators],
                        np.uint64)
    got_bal = np.asarray(new_bal)[:n_validators]
    got_eff = np.asarray(new_eff)[:n_validators]
    if not np.array_equal(got_bal, want_bal):
        raise AssertionError(
            f"device balances differ from the spec at "
            f"{int(np.count_nonzero(got_bal != want_bal))} validators")
    if not np.array_equal(got_eff, want_eff):
        raise AssertionError(
            f"device effective balances differ from the spec at "
            f"{int(np.count_nonzero(got_eff != want_eff))} validators")
    if _root_bytes(root) != bytes(hash_tree_root(state.balances)):
        raise AssertionError("device balances root differs from the SSZ "
                             "engine's hash_tree_root")


def epoch_phase(n: int = EPOCH_VALIDATORS, steps: int = EPOCH_STEPS,
                genesis_validators: int = GENESIS_VALIDATORS,
                seed: int = 0) -> dict:
    """Run the fused epoch step on an n-validator synthetic registry and
    check it; returns the phase's observations."""
    import jax

    from __graft_entry__ import _mainnet_params, _synthetic_registry
    from consensus_specs_tpu.parallel import EpochScalars

    assert n & (n - 1) == 0 and steps >= 1, (n, steps)
    reg = _synthetic_registry(n, seed)
    rng = np.random.RandomState(seed + 1)
    pubkey_root = rng.randint(0, 2**32, (n, 8), dtype=np.uint64).astype(
        np.uint32)
    credentials = rng.randint(0, 2**32, (n, 8), dtype=np.uint64).astype(
        np.uint32)
    sc = EpochScalars(current_epoch=np.uint64(100_000),
                      finality_delay=np.uint64(2),
                      slashings_sum=np.uint64(32_000_000_000))
    args = jax.device_put((reg, sc, np.uint64(n), pubkey_root, credentials))
    step = _epoch_step(_mainnet_params())

    t0 = time.perf_counter()
    out = jax.block_until_ready(step(*args))
    compile_first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(steps):
        out = jax.block_until_ready(step(*args))
    steady_s = (time.perf_counter() - t0) / steps

    new_bal, new_eff, bal_root, reg_root = (np.asarray(o) for o in out)
    if new_bal.shape != (n,) or new_eff.shape != (n,):
        raise AssertionError(f"sweep output shapes {new_bal.shape}, "
                             f"{new_eff.shape} != ({n},)")
    if _root_bytes(bal_root) != host_balances_root(new_bal):
        raise AssertionError("device balances root differs from the host "
                             "merkleization of its own balances")
    if _root_bytes(reg_root) != host_registry_root(
            pubkey_root, credentials, new_eff, reg.slashed,
            reg.activation_eligibility_epoch, reg.activation_epoch,
            reg.exit_epoch, reg.withdrawable_epoch):
        raise AssertionError("device registry root differs from the host "
                             "merkleization of its own effective balances")
    check_sweep_against_spec(genesis_validators)
    return {"validators": n, "steps": steps,
            "compile_first_s": compile_first_s,
            "steady_s_per_step": steady_s,
            "spec_parity_validators": genesis_validators}


# --- phase: serve -----------------------------------------------------------


def _build_statements(n: int, keys_per_statement: int, seed_base: int):
    """n valid FastAggregateVerify statements as wire bytes (pubkey,
    message, signature), from the serve loadgen's statement pool."""
    from consensus_specs_tpu.ops.bls import ciphersuite as cs
    from consensus_specs_tpu.serve.loadgen import build_statement_pool

    return [(cs.g1_to_bytes(pk), msg, cs.g2_to_bytes(sig))
            for pk, msg, sig in build_statement_pool(
                n, keys_per_statement, seed_base=seed_base)]


def _tamper(raw, i: int):
    """Request i with the next statement's (valid, wrong) signature."""
    pk, msg, _ = raw[i]
    return pk, msg, raw[(i + 1) % len(raw)][2]


def serve_phase(n_attestations: int = SERVE_ATTESTATIONS,
                committee: int = SERVE_COMMITTEE,
                n_tampered: int = SERVE_TAMPERED,
                steady_rounds: int = SERVE_STEADY_ROUNDS) -> dict:
    """Serve one cold and `steady_rounds` warm batches of
    `n_attestations` valid aggregate attestations, then `n_tampered`
    single tampered requests; every answer must equal the pure-Python
    oracle's (the `ops.bls` py backend's ciphersuite)."""
    from consensus_specs_tpu.ops.bls import ciphersuite
    from consensus_specs_tpu.serve import ServeExecutor

    oracle = ciphersuite.FastAggregateVerify
    assert n_attestations >= 2 and n_tampered >= 1, (n_attestations,
                                                     n_tampered)
    raw = _build_statements(n_attestations, committee, seed_base=1000)
    tampered = [_tamper(raw, i) for i in range(n_tampered)]
    want_valid = [oracle([pk], msg, sig) for pk, msg, sig in raw]
    want_tampered = [oracle([pk], msg, sig) for pk, msg, sig in tampered]

    ex = ServeExecutor()
    if ex.retry is not None or ex.breakers is not None:
        raise AssertionError("default ServeExecutor arms retry/breakers")

    def serve(requests):
        futs = [ex.submit_fast_aggregate_verify([pk], msg, sig)
                for pk, msg, sig in requests]
        t0 = time.perf_counter()
        ex.drain()
        got = [f.result() for f in futs]
        return got, time.perf_counter() - t0

    got, compile_first_s = serve(raw)
    answers = [(got, want_valid)]
    steady = []
    for _ in range(steady_rounds):
        got, dt = serve(raw)
        answers.append((got, want_valid))
        steady.append(dt)
    for req, want in zip(tampered, want_tampered):
        got, _ = serve([req])
        answers.append((got, [want]))
    for got, want in answers:
        if got != want:
            bad = [i for i, (g, w) in enumerate(zip(got, want)) if g != w]
            raise AssertionError(f"served answers differ from the oracle "
                                 f"at requests {bad[:8]}")
    if not all(want_valid) or any(want_tampered):
        raise AssertionError("oracle and server agree, but not with how "
                             "the requests were built (valid, tampered)")

    stats = ex.stats()
    n_requests = n_attestations * (1 + steady_rounds) + n_tampered
    for key in ("failed", "retries", "fallbacks", "shed"):
        if stats[key]:
            raise AssertionError(f"executor counted {key}={stats[key]}")
    if stats["settled"] != n_requests or stats["submitted"] != n_requests:
        raise AssertionError(f"executor settled {stats['settled']} of "
                             f"{stats['submitted']} submitted, want "
                             f"{n_requests}")
    if stats["batches"] != 1 + steady_rounds + n_tampered:
        raise AssertionError(f"{stats['batches']} device batches, want "
                             f"{1 + steady_rounds + n_tampered}")
    return {"attestations": n_attestations, "committee": committee,
            "tampered": n_tampered,
            "compile_first_s": compile_first_s,
            "steady_s_per_batch": (sum(steady) / len(steady)
                                   if steady else None),
            "executor": {k: stats[k] for k in (
                "submitted", "settled", "batches", "rechecks", "failed",
                "retries", "fallbacks", "shed")}}


# --- main -------------------------------------------------------------------


def main() -> int:
    jax = setup_jax()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found platform "
              f"{dev.platform!r}", file=sys.stderr, flush=True)
        return 1
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    observe("epoch", device_kind=dev.device_kind, **epoch_phase())
    observe("serve", device_kind=dev.device_kind, **serve_phase())
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
