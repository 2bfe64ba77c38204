"""The plain references against the pure-Python spec and ciphersuite."""

import json

import numpy as np
import pytest

from benchmark.harness import BENCH_DIR
from benchmark.reference import bls, epoch, statements

CONFIG = json.loads((BENCH_DIR / "configs" / "epoch-mainnet-1m.json")
                    .read_text())


@pytest.fixture(scope="module")
def spec_state():
    """A mainnet genesis state of 1024 validators with a previous epoch of
    attestations and three slashed validators, whose penalties fall due in
    the transition, as `chip_smoke.py` builds it."""
    from consensus_specs_tpu.models.builder import build_spec
    from consensus_specs_tpu.testlib.context import (
        default_activation_threshold)
    from consensus_specs_tpu.testlib.helpers.attestations import (
        prepare_state_with_attestations)
    from consensus_specs_tpu.testlib.helpers.genesis import (
        create_genesis_state)

    spec = build_spec("phase0", "mainnet")
    n = 1024
    state = create_genesis_state(spec, [spec.MAX_EFFECTIVE_BALANCE] * n,
                                 default_activation_threshold(spec))
    prepare_state_with_attestations(spec, state)
    for i in (1, n // 3, n - 2):
        v = state.validators[i]
        v.slashed = True
        v.withdrawable_epoch = spec.Epoch(
            int(spec.get_current_epoch(state))
            + int(spec.EPOCHS_PER_SLASHINGS_VECTOR) // 2)
        state.slashings[0] += v.effective_balance
    spec.process_justification_and_finalization(state)
    return spec, state


def test_preset_matches_the_spec(spec_state):
    spec, _ = spec_state
    for key, value in CONFIG["preset"].items():
        assert int(getattr(spec, key)) == value, key


def test_sweep_and_roots_match_the_spec(spec_state):
    from consensus_specs_tpu.parallel import (
        registry_arrays_from_state, validator_static_leaf_words)
    from consensus_specs_tpu.utils.ssz.ssz_impl import hash_tree_root

    spec, state = spec_state
    state = state.copy()
    arrays, sc = registry_arrays_from_state(spec, state)
    reg = epoch.Registry(*(np.asarray(a) for a in arrays))
    pubkey_root, credentials = validator_static_leaf_words(spec, state)
    bal, eff = epoch.sweep(reg, int(sc.current_epoch),
                           int(sc.finality_delay), int(sc.slashings_sum),
                           CONFIG["preset"])

    spec.process_rewards_and_penalties(state)
    spec.process_slashings(state)
    spec.process_effective_balance_updates(state)
    assert bal.tolist() == [int(b) for b in state.balances]
    assert eff.tolist() == [int(v.effective_balance)
                            for v in state.validators]
    assert epoch.balances_root(bal) == bytes(hash_tree_root(state.balances))
    roots = epoch.RegistryRoots(np.asarray(pubkey_root),
                                np.asarray(credentials), reg)
    assert roots.root(eff) == bytes(hash_tree_root(state.validators))


def test_float32_control_differs_from_the_reference():
    reg, _, _, slashings = epoch.make_registry(
        4096, 7, CONFIG["preset"], CONFIG["registry"])
    args = (CONFIG["registry"]["start_epoch"], 2, slashings, CONFIG["preset"])
    bal, _ = epoch.sweep(reg, *args)
    bal32, _ = epoch.sweep_float32(reg, *args)
    assert np.count_nonzero(bal32 != bal) > 4096 // 2


def test_registry_roots_rehash_only_what_moved():
    reg, pk, cred, _ = epoch.make_registry(
        256, 3, CONFIG["preset"], CONFIG["registry"])
    eff = reg.effective_balance.copy()
    roots = epoch.RegistryRoots(pk, cred, reg)
    first = roots.root(eff)
    eff[17] -= np.uint64(10**9)
    moved = roots.root(eff)
    assert moved != first
    assert moved == epoch.RegistryRoots(pk, cred, reg).root(eff)


def test_statements_are_distinct_and_valid():
    made = statements.make_statements(seed=2**31 + 11, n_messages=2,
                                      per_message=3)
    assert len(set(made)) == 6
    assert len({m for _, m, _ in made}) == 2
    for pk, msg, sig in made:
        assert bls.FastAggregateVerify([pk], msg, sig)


def test_statements_are_drawn_from_the_seed():
    a = statements.make_statements(seed=5, n_messages=4, per_message=2,
                                   workers=2)
    b = statements.make_statements(seed=5, n_messages=4, per_message=2)
    c = statements.make_statements(seed=6, n_messages=1, per_message=2)
    assert a == b
    assert not set(c) & set(a)


def tamper(statement, other):
    """The statement with another statement's (valid, wrong) signature."""
    return statement[0], statement[1], other[2]


@pytest.mark.parametrize("how", statements.TAMPERINGS)
@pytest.mark.parametrize("half", [0, 1])
def test_tampered_batch_is_refused_in_its_half(how, half):
    import random

    made = statements.make_statements(seed=9, n_messages=2, per_message=4)
    batch, where = statements.tamper(made, how, half, random.Random(3))
    assert [i for i in range(8) if batch[i] != made[i]] == where
    assert all((i >= 4) == bool(half) for i in where)
    verdicts, _ = statements.verify_all([batch[i] for i in where])
    assert not any(verdicts)


def test_verify_all_over_processes_keeps_the_order():
    made = statements.make_statements(seed=2, n_messages=2, per_message=2)
    stmts = [made[0], tamper(made[0], made[1]), made[2], made[3]]
    verdicts, extra = statements.verify_all(stmts, workers=2,
                                            during=lambda: "meanwhile")
    assert verdicts == [True, False, True, True]
    assert extra == "meanwhile"


def test_reference_agrees_with_the_program_ciphersuite():
    """The copy reads as the oracle it was copied from."""
    from consensus_specs_tpu.ops.bls import ciphersuite

    made = statements.make_statements(seed=4, n_messages=1, per_message=2)
    bad = tamper(made[0], made[1])
    for pk, msg, sig in made + [bad]:
        assert (bls.FastAggregateVerify([pk], msg, sig)
                == ciphersuite.FastAggregateVerify([pk], msg, sig))
