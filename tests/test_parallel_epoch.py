"""Parity: the TPU epoch-sweep kernel vs the executable spec.

The kernel (`parallel.epoch.epoch_sweep`) must reproduce the spec's
rewards/penalties + slashings + effective-balance pipeline bit-for-bit
(arrays extracted AFTER `process_justification_and_finalization`, which is
where the sweep's finality/justification inputs are read in `process_epoch`).
"""

import numpy as np
import pytest

from consensus_specs_tpu.models.builder import build_spec
from consensus_specs_tpu.parallel import (
    EpochParams,
    balances_list_root,
    make_epoch_step,
    pad_pow2,
    registry_arrays_from_state,
    RegistryArrays,
)
from consensus_specs_tpu.testlib.context import (
    default_activation_threshold,
    default_balances,
)
from consensus_specs_tpu.testlib.helpers.attestations import (
    prepare_state_with_attestations,
)
from consensus_specs_tpu.testlib.helpers.genesis import create_genesis_state
from consensus_specs_tpu.testlib.helpers.state import next_epoch, next_slot
from consensus_specs_tpu.utils.ssz.ssz_impl import hash_tree_root


@pytest.fixture(scope="module")
def spec():
    return build_spec("phase0", "minimal")


def _fresh_state(spec, extra_slashed=(), leak_epochs=0):
    state = create_genesis_state(
        spec, default_balances(spec), default_activation_threshold(spec))
    for _ in range(leak_epochs):
        next_epoch(spec, state)
    if not leak_epochs:
        prepare_state_with_attestations(spec, state)
    for i in extra_slashed:
        state.validators[i].slashed = True
        state.validators[i].withdrawable_epoch = spec.Epoch(
            int(spec.get_current_epoch(state))
            + int(spec.EPOCHS_PER_SLASHINGS_VECTOR) // 2)
        state.slashings[0] += state.validators[i].effective_balance
    return state


def _run_both(spec, state):
    """Run spec process_epoch tail vs kernel on the same pre-state."""
    spec_state = state.copy()
    spec.process_justification_and_finalization(spec_state)

    reg, sc = registry_arrays_from_state(spec, spec_state)
    n = len(state.validators)
    reg = RegistryArrays(*(pad_pow2(np.asarray(a)) for a in reg))

    step = make_epoch_step(EpochParams.from_spec(spec))
    new_bal, new_eff, root = step(reg, sc, np.uint64(n))

    spec.process_rewards_and_penalties(spec_state)
    spec.process_slashings(spec_state)
    spec.process_effective_balance_updates(spec_state)

    want_bal = np.array([int(b) for b in spec_state.balances], dtype=np.uint64)
    want_eff = np.array([int(v.effective_balance)
                         for v in spec_state.validators], dtype=np.uint64)
    return (np.asarray(new_bal)[:n], np.asarray(new_eff)[:n], root,
            want_bal, want_eff, spec_state)


def test_sweep_matches_spec_with_full_participation(spec):
    state = _fresh_state(spec)
    got_bal, got_eff, root, want_bal, want_eff, spec_state = _run_both(
        spec, state)
    np.testing.assert_array_equal(got_bal, want_bal)
    np.testing.assert_array_equal(got_eff, want_eff)


def test_sweep_matches_spec_with_slashed_validators(spec):
    state = _fresh_state(spec, extra_slashed=(1, 5, 9))
    got_bal, got_eff, _, want_bal, want_eff, _ = _run_both(spec, state)
    np.testing.assert_array_equal(got_bal, want_bal)
    np.testing.assert_array_equal(got_eff, want_eff)


def test_sweep_matches_spec_in_inactivity_leak(spec):
    # advance far past finality with zero attestations -> leak active
    state = _fresh_state(
        spec, leak_epochs=int(spec.MIN_EPOCHS_TO_INACTIVITY_PENALTY) + 3)
    assert spec.is_in_inactivity_leak(state)
    got_bal, got_eff, _, want_bal, want_eff, _ = _run_both(spec, state)
    np.testing.assert_array_equal(got_bal, want_bal)
    np.testing.assert_array_equal(got_eff, want_eff)


def test_balances_root_matches_ssz(spec):
    state = _fresh_state(spec)
    got_bal, _, root, _, _, spec_state = _run_both(spec, state)
    want = hash_tree_root(spec_state.balances)
    got = np.asarray(root).astype(">u4").tobytes()
    assert got == bytes(want)


def test_registry_root_matches_ssz_non_pow2(spec):
    """Padded registries (any non-power-of-two count) must merkleize like
    SSZ: pad rows are zero *chunks*, not zero-Validator record roots."""
    from consensus_specs_tpu.parallel import (
        ValidatorLeaves,
        validator_records_root,
        validator_registry_root,
        validator_static_leaf_words,
    )

    state = create_genesis_state(
        spec, [spec.MAX_EFFECTIVE_BALANCE] * 48,
        default_activation_threshold(spec))
    n = len(state.validators)
    assert n & (n - 1) != 0  # genuinely exercises the padding path

    pk_root, cred = validator_static_leaf_words(spec, state)
    arrs = {
        "effective_balance": [int(v.effective_balance)
                              for v in state.validators],
        "slashed": [bool(v.slashed) for v in state.validators],
        "activation_eligibility_epoch": [
            int(v.activation_eligibility_epoch) for v in state.validators],
        "activation_epoch": [int(v.activation_epoch)
                             for v in state.validators],
        "exit_epoch": [int(v.exit_epoch) for v in state.validators],
        "withdrawable_epoch": [int(v.withdrawable_epoch)
                               for v in state.validators],
    }
    pad = {k: pad_pow2(np.asarray(v, dtype=np.uint64))
           for k, v in arrs.items()}
    rec = validator_records_root(
        ValidatorLeaves(pad_pow2(np.asarray(pk_root)),
                        pad_pow2(np.asarray(cred))),
        pad["effective_balance"], pad["slashed"],
        pad["activation_eligibility_epoch"], pad["activation_epoch"],
        pad["exit_epoch"], pad["withdrawable_epoch"])
    root = validator_registry_root(rec, np.uint64(n))
    got = np.asarray(root).astype(">u4").tobytes()
    want = bytes(hash_tree_root(state.validators))
    assert got == want


# --- edge states: the sweep and the benchmark's reference vs the spec --------
#
# Each case is a state mutation applied to a base state in one of two reward
# regimes: "normal" (finality delay 2) and "leak" (finality delay 5, past
# MIN_EPOCHS_TO_INACTIVITY_PENALTY = 4).  A `pre` mutation runs on the genesis
# state, before any epoch is advanced or attested; a `post` one on the state
# whose previous epoch is fully attested, before the epoch transition.

GWEI = 10**9
_REFERENCE_KEYS = (
    "BASE_REWARD_FACTOR", "BASE_REWARDS_PER_EPOCH", "PROPOSER_REWARD_QUOTIENT",
    "INACTIVITY_PENALTY_QUOTIENT", "MIN_EPOCHS_TO_INACTIVITY_PENALTY",
    "EFFECTIVE_BALANCE_INCREMENT", "MAX_EFFECTIVE_BALANCE",
    "HYSTERESIS_QUOTIENT", "HYSTERESIS_DOWNWARD_MULTIPLIER",
    "HYSTERESIS_UPWARD_MULTIPLIER", "EPOCHS_PER_SLASHINGS_VECTOR",
    "PROPORTIONAL_SLASHING_MULTIPLIER")
_EMPTY_EPOCHS = {"normal": 2, "leak": 5}


def _reissue_by_bucket(spec, state, bucket_of):
    """Re-issue each previous-epoch attestation once per participation
    bucket of its attesters: "head" keeps its data, "target" votes for a
    wrong head, "source" for a wrong target as well, "none" drops out."""
    out = []
    for a in state.previous_epoch_attestations:
        committee = spec.get_beacon_committee(state, a.data.slot, a.data.index)
        for kind in ("head", "target", "source"):
            bits = [bool(a.aggregation_bits[j]) and bucket_of(int(v)) == kind
                    for j, v in enumerate(committee)]
            if not any(bits):
                continue
            data = a.data.copy()
            if kind != "head":
                data.beacon_block_root = spec.Root(b"\x5a" * 32)
            if kind == "source":
                data.target.root = spec.Root(b"\xa5" * 32)
            out.append(spec.PendingAttestation(
                aggregation_bits=type(a.aggregation_bits)(*bits), data=data,
                inclusion_delay=a.inclusion_delay,
                proposer_index=a.proposer_index))
    state.previous_epoch_attestations = out


def _deltas(spec, state):
    """The spec's attestation rewards and penalties for `state`, as the
    transition will compute them (they read effective balances, never
    balances)."""
    probe = state.copy()
    spec.process_justification_and_finalization(probe)
    rewards, penalties = spec.get_attestation_deltas(probe)
    return [int(r) for r in rewards], [int(p) for p in penalties]


def _set_post_balances(spec, state, targets):
    """Balances such that, after this epoch's rewards and penalties, each
    validator i in `targets` holds exactly `targets[i]`."""
    rewards, penalties = _deltas(spec, state)
    for i, want in targets.items():
        pre = want - rewards[i] + penalties[i]
        assert pre >= 0, (i, want, rewards[i], penalties[i])
        state.balances[i] = spec.Gwei(pre)


def _slash(spec, state, indices, withdrawable_offset=0):
    """Slash `indices` with their correlated penalty due
    `withdrawable_offset` epochs after this transition's (0: now)."""
    due = (int(spec.get_current_epoch(state))
           + int(spec.EPOCHS_PER_SLASHINGS_VECTOR) // 2 + withdrawable_offset)
    for i in indices:
        v = state.validators[i]
        v.slashed = True
        v.withdrawable_epoch = spec.Epoch(due)
        state.slashings[0] += v.effective_balance


def _nested_participation(spec, state):
    # the epoch cell's 99/98/95 % source/target/head, at least one
    # validator in each of the four buckets
    n = len(state.validators)
    order = np.random.default_rng(11).permutation(n)
    none, source, target = (max(1, round(f * n)) for f in (0.01, 0.01, 0.03))
    bucket = {}
    for rank, i in enumerate(order):
        bucket[int(i)] = ("none" if rank < none else
                          "source" if rank < none + source else
                          "target" if rank < none + source + target else
                          "head")
    _reissue_by_bucket(spec, state, bucket.__getitem__)


def _late_inclusion(spec, state):
    # every attestation also included later by another proposer, some
    # copies listed first, and one tie of equal delays: the earliest
    # inclusion wins, the first listed among equals
    atts = list(state.previous_epoch_attestations)
    n = len(state.validators)
    out = []
    for k, a in enumerate(atts):
        late = a.copy()
        late.inclusion_delay = spec.uint64(2 + k % 3)
        late.proposer_index = spec.ValidatorIndex((k * 7 + 3) % n)
        early = a.copy()
        early.inclusion_delay = spec.uint64(1 + k % 2)
        out += [late, early] if k % 2 else [early, late]
    tie = atts[0].copy()
    tie.proposer_index = spec.ValidatorIndex(n - 1)
    tie.inclusion_delay = out[0].inclusion_delay
    state.previous_epoch_attestations = out[:1] + [tie] + out[1:]


def _no_participation(spec, state):
    state.previous_epoch_attestations = []


def _varied_effective_balances(spec, state):
    for i, v in enumerate(state.validators):
        eff = (32, 31, 24, 17)[i % 4] * GWEI
        v.effective_balance = spec.Gwei(eff)
        state.balances[i] = spec.Gwei(eff + (i * 37 % 11) * GWEI // 10)


def _slashed_at_midpoint(spec, state):
    for i, eth in ((5, 31), (9, 24), (22, 17)):
        state.validators[i].effective_balance = spec.Gwei(eth * GWEI)
    _slash(spec, state, (1, 5, 9, 22, 40))


def _slashed_off_midpoint(spec, state):
    _slash(spec, state, (2, 11), withdrawable_offset=-1)
    _slash(spec, state, (30, 41), withdrawable_offset=1)


def _mass_slashing(spec, state):
    # the slashings vector holds the whole active balance: the
    # proportional penalty takes a slashed validator's effective balance,
    # and its balance clamps at 0
    _slash(spec, state, range(0, len(state.validators), 3))
    state.slashings[1] += spec.get_total_active_balance(state)


def _hysteresis(spec, multiplier: str) -> int:
    return (int(spec.EFFECTIVE_BALANCE_INCREMENT)
            // int(spec.HYSTERESIS_QUOTIENT) * int(getattr(spec, multiplier)))


def _hysteresis_down_edge(spec, state):
    down = _hysteresis(spec, "HYSTERESIS_DOWNWARD_MULTIPLIER")
    targets = {}
    for i, v in enumerate(state.validators):
        edge = int(v.effective_balance) - down
        targets[i] = edge + (-1, 0, 1)[i % 3]
    _set_post_balances(spec, state, targets)


def _hysteresis_up_edge(spec, state):
    up = _hysteresis(spec, "HYSTERESIS_UPWARD_MULTIPLIER")
    for v in state.validators:
        v.effective_balance = spec.Gwei(30 * GWEI)
    targets = {i: 30 * GWEI + up + (-1, 0, 1)[i % 3]
               for i in range(len(state.validators))}
    _set_post_balances(spec, state, targets)


def _balance_below_penalty(spec, state):
    # eight validators that did not attest, left one gwei short of, at,
    # and one gwei past their penalty net of any proposer reward
    absent = set(range(3, 64, 8))
    _reissue_by_bucket(spec, state,
                       lambda i: "none" if i in absent else "head")
    rewards, penalties = _deltas(spec, state)
    for k, i in enumerate(sorted(absent)):
        net = penalties[i] - rewards[i]
        state.balances[i] = spec.Gwei(max(0, net + (-1, 0, 1, -net)[k % 4]))


def _exiting_this_epoch(spec, state):
    # active in the previous epoch, not in the current one: eligible for
    # rewards and penalties, outside the total active balance
    cur = spec.get_current_epoch(state)
    for i in (4, 13, 50):
        state.validators[i].exit_epoch = cur
        state.validators[i].withdrawable_epoch = spec.Epoch(int(cur) + 256)


def _slashed_exited_not_withdrawable(spec, state):
    # exited and slashed, withdrawable only after the next epoch: still
    # eligible for the penalties of an absent attester
    cur = int(spec.get_current_epoch(state))
    for i in (6, 27):
        v = state.validators[i]
        v.slashed = True
        v.exit_epoch = spec.Epoch(cur)
        v.withdrawable_epoch = spec.Epoch(cur + 1)
        state.slashings[1] += v.effective_balance


def _exited_before_previous_epoch(spec, state):
    # `pre`: gone before the attested epoch, withdrawable much later
    for i in (7, 19, 35):
        state.validators[i].exit_epoch = spec.Epoch(1)
        state.validators[i].withdrawable_epoch = spec.Epoch(300)


EDGE_CASES = {
    "nested_participation": (None, _nested_participation, 64),
    "late_inclusion": (None, _late_inclusion, 64),
    "no_participation": (None, _no_participation, 64),
    "varied_effective_balances": (None, _varied_effective_balances, 64),
    "slashed_at_midpoint": (None, _slashed_at_midpoint, 64),
    "slashed_off_midpoint": (None, _slashed_off_midpoint, 64),
    "mass_slashing": (None, _mass_slashing, 64),
    "hysteresis_down_edge": (None, _hysteresis_down_edge, 64),
    "hysteresis_up_edge": (None, _hysteresis_up_edge, 64),
    "balance_below_penalty": (None, _balance_below_penalty, 64),
    "exiting_this_epoch": (None, _exiting_this_epoch, 64),
    "slashed_exited_not_withdrawable": (
        None, _slashed_exited_not_withdrawable, 64),
    "exited_before_previous_epoch": (_exited_before_previous_epoch, None, 64),
    "registry_of_40": (None, None, 40),
    "registry_of_100": (None, None, 100),
}
EDGE_IDS = [f"{mode}-{name}" for mode in ("normal", "leak")
            for name in EDGE_CASES] + ["genesis_epoch"]


def _edge_state(spec, case_id):
    """The pre-transition state of one case: `process_justification_and_
    finalization` already applied, as `_run_both` does."""
    if case_id == "genesis_epoch":
        # one slot in, so that the previous epoch has a block root: the
        # spec pays no rewards in the genesis epoch
        state = create_genesis_state(
            spec, default_balances(spec), default_activation_threshold(spec))
        next_slot(spec, state)
        assert int(spec.get_current_epoch(state)) == 0
        return state
    mode, name = case_id.split("-", 1)
    pre, post, n = EDGE_CASES[name]
    state = create_genesis_state(spec, [spec.MAX_EFFECTIVE_BALANCE] * n,
                                 default_activation_threshold(spec))
    if pre is not None:
        pre(spec, state)
    for _ in range(_EMPTY_EPOCHS[mode]):
        next_epoch(spec, state)
    prepare_state_with_attestations(spec, state)
    if post is not None:
        post(spec, state)
    spec.process_justification_and_finalization(state)
    assert spec.is_in_inactivity_leak(state) == (mode == "leak")
    return state


def _spec_after(spec, state):
    out = state.copy()
    spec.process_rewards_and_penalties(out)
    spec.process_slashings(out)
    spec.process_effective_balance_updates(out)
    return (np.array([int(b) for b in out.balances], dtype=np.uint64),
            np.array([int(v.effective_balance) for v in out.validators],
                     dtype=np.uint64), out)


@pytest.fixture(scope="module")
def epoch_step(spec):
    return make_epoch_step(EpochParams.from_spec(spec))


@pytest.mark.parametrize("case_id", EDGE_IDS)
def test_sweep_matches_spec_on_edge_states(spec, epoch_step, case_id):
    state = _edge_state(spec, case_id)
    want_bal, want_eff, spec_state = _spec_after(spec, state)
    reg, sc = registry_arrays_from_state(spec, state)
    n = len(state.validators)
    padded = RegistryArrays(*(pad_pow2(np.asarray(a)) for a in reg))
    new_bal, new_eff, root = epoch_step(padded, sc, np.uint64(n))
    np.testing.assert_array_equal(np.asarray(new_bal)[:n], want_bal)
    np.testing.assert_array_equal(np.asarray(new_eff)[:n], want_eff)
    # the zero rows that pad the registry to a power of two stay zero
    assert not np.asarray(new_bal)[n:].any()
    assert not np.asarray(new_eff)[n:].any()
    got_root = np.asarray(root).astype(">u4").tobytes()
    assert got_root == bytes(hash_tree_root(spec_state.balances))


@pytest.mark.parametrize("case_id", EDGE_IDS)
def test_benchmark_reference_sweep_matches_spec_on_edge_states(spec, case_id):
    """`benchmark/reference/epoch.py` decides the epoch cell's `correct`;
    it must agree with the spec wherever the program is held to it."""
    from benchmark.reference import epoch as ref

    state = _edge_state(spec, case_id)
    want_bal, want_eff, spec_state = _spec_after(spec, state)
    reg, sc = registry_arrays_from_state(spec, state)
    preset = {k: int(getattr(spec, k)) for k in _REFERENCE_KEYS}
    bal, eff = ref.sweep(ref.Registry(*(np.asarray(a) for a in reg)),
                         int(sc.current_epoch), int(sc.finality_delay),
                         int(sc.slashings_sum), preset)
    np.testing.assert_array_equal(bal, want_bal)
    np.testing.assert_array_equal(eff, want_eff)
    assert ref.balances_root(bal) == bytes(
        hash_tree_root(spec_state.balances))


# --- the names the benchmark reads ------------------------------------------


@pytest.fixture(scope="module")
def benchmark_epoch_program(spec):
    """The step the epoch cell runs (`make_sharded_epoch_step` over a
    one-device mesh), lowered at 256 validators: its text with the
    location of each op, which the compiled program carries into the
    op names the profiler's trace shows."""
    from consensus_specs_tpu import parallel

    n = 256
    step = parallel.make_sharded_epoch_step(
        parallel.make_mesh(1), EpochParams.from_spec(spec))
    u64 = np.zeros(n, np.uint64)
    flag = np.zeros(n, bool)
    reg = RegistryArrays(u64, u64, flag, u64, u64, u64, u64, flag, flag,
                         flag, np.ones(n, np.uint64), np.zeros(n, np.int32))
    sc = parallel.EpochScalars(*(np.uint64(0),) * 3)
    leaves = np.zeros((n, 8), np.uint32)
    return step.lower(reg, sc, np.uint64(n), leaves,
                      leaves).as_text(debug_info=True)


def _benchmark_scopes():
    from benchmark.systems.epoch_transition import SCOPES

    return sorted({s for names in SCOPES.values() for s in names})


@pytest.mark.parametrize("scope", _benchmark_scopes())
def test_benchmark_reads_scope_in_the_epoch_step(benchmark_epoch_program,
                                                  scope):
    """`sweep_ms` and `merkle_ms` add up the device ops whose metadata
    names these scopes: the step has to keep naming them."""
    assert f"/{scope}/" in benchmark_epoch_program, scope
