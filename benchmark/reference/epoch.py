"""Plain phase0 epoch transition over a struct-of-arrays registry.

numpy and hashlib only; it imports nothing of the program.  `sweep` follows
`specs/phase0/beacon-chain.md` (`process_rewards_and_penalties`,
`process_slashings`, `process_effective_balance_updates`) in exact uint64
integer arithmetic, and the roots are the SSZ `hash_tree_root` of
`List[Gwei, 2**40]` and `List[Validator, 2**40]`, merkleized by hashlib.
"""

from __future__ import annotations

import hashlib
import math
from typing import NamedTuple

import numpy as np

U64 = np.uint64
FAR_FUTURE_EPOCH = 2**64 - 1
BALANCES_LIMIT_DEPTH = 38           # 2**40 uint64 values, 4 to a chunk
REGISTRY_LIMIT_DEPTH = 40           # 2**40 Validator records

_sha = hashlib.sha256


class Registry(NamedTuple):
    """The validator fields the transition reads, one (N,) array each, and
    the previous epoch's participation expanded per validator."""

    balance: np.ndarray                 # uint64 Gwei
    effective_balance: np.ndarray       # uint64 Gwei
    slashed: np.ndarray                 # bool
    activation_eligibility_epoch: np.ndarray
    activation_epoch: np.ndarray
    exit_epoch: np.ndarray
    withdrawable_epoch: np.ndarray
    is_source: np.ndarray               # bool: matching source attestation
    is_target: np.ndarray               # bool: matching target attestation
    is_head: np.ndarray                 # bool: matching head attestation
    inclusion_delay: np.ndarray         # uint64, of the earliest inclusion
    proposer_index: np.ndarray          # int32, its proposer


def make_registry(n: int, seed: int, preset: dict, registry: dict):
    """A registry of n validators from `seed`: balances around 32 ETH,
    nested participation at the configured rates, a slashed share whose
    correlated penalties fall due inside the first `slashing_spread`
    epochs, and the two static leaves of every record (pubkey root and
    withdrawal credentials) as (N, 8) big-endian uint32 words.

    Returns (Registry, pubkey_root_words, credential_words, slashings_sum).
    """
    rng = np.random.default_rng(seed)
    start = int(registry["start_epoch"])
    balance = rng.integers(registry["balance_min_gwei"],
                           registry["balance_max_gwei"], n, dtype=U64)
    slashed = rng.random(n) < registry["slashed_fraction"]
    withdrawable = np.full(n, FAR_FUTURE_EPOCH, U64)
    due = (start + preset["EPOCHS_PER_SLASHINGS_VECTOR"] // 2
           + rng.integers(0, registry["slashing_spread"], n, dtype=U64))
    withdrawable[slashed] = due[slashed]
    # a validator that matched the head also matched target and source
    u = rng.random(n)
    reg = Registry(
        balance=balance,
        effective_balance=np.full(n, preset["MAX_EFFECTIVE_BALANCE"], U64),
        slashed=slashed,
        activation_eligibility_epoch=np.zeros(n, U64),
        activation_epoch=np.zeros(n, U64),
        exit_epoch=np.full(n, FAR_FUTURE_EPOCH, U64),
        withdrawable_epoch=withdrawable,
        is_source=u < registry["source_rate"],
        is_target=u < registry["target_rate"],
        is_head=u < registry["head_rate"],
        inclusion_delay=rng.integers(1, registry["max_inclusion_delay"] + 1,
                                     n, dtype=U64),
        proposer_index=rng.integers(0, n, n, dtype=np.int32),
    )
    pubkey_root = rng.integers(0, 2**32, (n, 8), dtype=np.uint32)
    credentials = rng.integers(0, 2**32, (n, 8), dtype=np.uint32)
    slashings_sum = int(reg.effective_balance[slashed].sum(dtype=U64))
    return reg, pubkey_root, credentials, slashings_sum


def sweep(reg: Registry, current_epoch: int, finality_delay: int,
          slashings_sum: int, preset: dict):
    """One epoch's rewards and penalties, slashings and effective-balance
    updates.  Returns (balance, effective_balance), new arrays."""
    p = preset
    incr = p["EFFECTIVE_BALANCE_INCREMENT"]
    prev_epoch = max(current_epoch, 1) - 1
    eff = reg.effective_balance
    active_cur = ((reg.activation_epoch <= U64(current_epoch))
                  & (U64(current_epoch) < reg.exit_epoch))
    active_prev = ((reg.activation_epoch <= U64(prev_epoch))
                   & (U64(prev_epoch) < reg.exit_epoch))
    eligible = active_prev | (reg.slashed
                              & (U64(prev_epoch + 1) < reg.withdrawable_epoch))
    total = max(incr, int(eff[active_cur].sum(dtype=U64)))

    # get_base_reward
    base_reward = (eff * U64(p["BASE_REWARD_FACTOR"]) // U64(math.isqrt(total))
                   // U64(p["BASE_REWARDS_PER_EPOCH"]))
    proposer_reward = base_reward // U64(p["PROPOSER_REWARD_QUOTIENT"])
    in_leak = finality_delay > p["MIN_EPOCHS_TO_INACTIVITY_PENALTY"]
    unslashed = ~reg.slashed
    rewards = np.zeros_like(reg.balance)
    penalties = np.zeros_like(reg.balance)

    # get_source_deltas, get_target_deltas, get_head_deltas
    for matched in (reg.is_source, reg.is_target, reg.is_head):
        flag = matched & unslashed
        attesting = max(incr, int(eff[flag].sum(dtype=U64)))
        if in_leak:
            reward = base_reward
        else:
            reward = (base_reward * U64(attesting // incr)
                      // U64(total // incr))
        rewards[eligible & flag] += reward[eligible & flag]
        penalties[eligible & ~flag] += base_reward[eligible & ~flag]

    # get_inclusion_delay_deltas
    src = reg.is_source & unslashed
    rewards[src] += ((base_reward - proposer_reward)[src]
                     // reg.inclusion_delay[src])
    np.add.at(rewards, reg.proposer_index[src], proposer_reward[src])

    # get_inactivity_penalty_deltas
    if in_leak:
        penalties[eligible] += (U64(p["BASE_REWARDS_PER_EPOCH"]) * base_reward
                                - proposer_reward)[eligible]
        late = eligible & ~(reg.is_target & unslashed)
        penalties[late] += (eff * U64(finality_delay)
                            // U64(p["INACTIVITY_PENALTY_QUOTIENT"]))[late]

    # process_rewards_and_penalties: increase, then saturating decrease
    bal = reg.balance.copy()
    if current_epoch != 0:
        bal += rewards
        bal = np.where(penalties > bal, U64(0), bal - penalties)

    # process_slashings
    adjusted = min(slashings_sum * p["PROPORTIONAL_SLASHING_MULTIPLIER"], total)
    hit = reg.slashed & (reg.withdrawable_epoch == U64(
        current_epoch + p["EPOCHS_PER_SLASHINGS_VECTOR"] // 2))
    if hit.any():
        penalty = np.zeros_like(bal)
        penalty[hit] = np.array(
            [int(e) // incr * adjusted // total * incr for e in eff[hit]], U64)
        bal = np.where(penalty > bal, U64(0), bal - penalty)

    # process_effective_balance_updates
    hyst = incr // p["HYSTERESIS_QUOTIENT"]
    down = U64(hyst * p["HYSTERESIS_DOWNWARD_MULTIPLIER"])
    up = U64(hyst * p["HYSTERESIS_UPWARD_MULTIPLIER"])
    move = (bal + down < eff) | (eff + up < bal)
    new_eff = np.where(
        move, np.minimum(bal - bal % U64(incr), U64(p["MAX_EFFECTIVE_BALANCE"])),
        eff)
    return bal, new_eff


def sweep_float32(reg: Registry, current_epoch: int, finality_delay: int,
                  slashings_sum: int, preset: dict, xp=np):
    """The same rewards, penalties and effective-balance updates computed
    in float32 lanes, as a port that moved the sweep onto 32-bit floating
    point would: the control that the comparison has to refuse.  `xp` is
    numpy or jax.numpy; returns uint64 (balance, effective_balance)."""
    p = preset
    f = xp.float32
    incr = f(p["EFFECTIVE_BALANCE_INCREMENT"])
    eff = xp.asarray(reg.effective_balance).astype(f)
    bal = xp.asarray(reg.balance).astype(f)
    unslashed = ~xp.asarray(reg.slashed)
    total = xp.maximum(incr, eff.sum(dtype=f))
    base = xp.floor(xp.floor(eff * f(p["BASE_REWARD_FACTOR"]) / xp.sqrt(total))
                    / f(p["BASE_REWARDS_PER_EPOCH"]))
    prop = xp.floor(base / f(p["PROPOSER_REWARD_QUOTIENT"]))
    rewards = xp.zeros_like(bal)
    penalties = xp.zeros_like(bal)
    for matched in (reg.is_source, reg.is_target, reg.is_head):
        flag = xp.asarray(matched) & unslashed
        attesting = xp.maximum(incr, xp.where(flag, eff, f(0)).sum(dtype=f))
        reward = xp.floor(base * xp.floor(attesting / incr)
                          / xp.floor(total / incr))
        rewards = rewards + xp.where(flag, reward, f(0))
        penalties = penalties + xp.where(flag, f(0), base)
    src = xp.asarray(reg.is_source) & unslashed
    delay = xp.asarray(reg.inclusion_delay).astype(f)
    rewards = rewards + xp.where(src, xp.floor((base - prop) / delay), f(0))
    contrib = xp.where(src, prop, f(0))
    idx = xp.asarray(reg.proposer_index)
    if xp is np:
        np.add.at(rewards, idx, contrib)
    else:
        rewards = rewards.at[idx].add(contrib)
    bal = xp.maximum(bal + rewards - penalties, f(0))
    hyst = incr / f(p["HYSTERESIS_QUOTIENT"])
    move = ((bal + hyst * f(p["HYSTERESIS_DOWNWARD_MULTIPLIER"]) < eff)
            | (eff + hyst * f(p["HYSTERESIS_UPWARD_MULTIPLIER"]) < bal))
    new_eff = xp.where(move, xp.minimum(bal - xp.mod(bal, incr),
                                        f(p["MAX_EFFECTIVE_BALANCE"])), eff)
    return bal.astype(xp.uint64), new_eff.astype(xp.uint64)


# --- SSZ roots by hashlib ---------------------------------------------------


def _zero_hashes(depth: int) -> list[bytes]:
    z = [b"\x00" * 32]
    for _ in range(depth):
        z.append(_sha(z[-1] + z[-1]).digest())
    return z


_ZERO = _zero_hashes(REGISTRY_LIMIT_DEPTH)


def _hash_level(level: bytes) -> bytes:
    """Hash each 64-byte pair of a level of 32-byte nodes."""
    mv = memoryview(level)
    return b"".join([_sha(mv[i:i + 64]).digest()
                     for i in range(0, len(level), 64)])


def merkleize(chunks: bytes, limit_depth: int) -> bytes:
    """Root of a tree of 2**limit_depth leaves whose first leaves are the
    32-byte `chunks` and the rest zero chunks."""
    level = chunks or _ZERO[0]
    depth = 0
    while len(level) > 32:
        if len(level) % 64:
            level += _ZERO[depth]
        level = _hash_level(level)
        depth += 1
    for d in range(depth, limit_depth):
        level = _sha(level + _ZERO[d]).digest()
    return level


def mix_in_length(root: bytes, length: int) -> bytes:
    return _sha(root + length.to_bytes(32, "little")).digest()


def balances_root(balance: np.ndarray) -> bytes:
    data = balance.astype("<u8").tobytes()
    data += b"\x00" * (-len(data) % 32)
    return mix_in_length(merkleize(data, BALANCES_LIMIT_DEPTH), len(balance))


def _u64_leaves(values: np.ndarray) -> np.ndarray:
    out = np.zeros((values.shape[0], 32), np.uint8)
    out[:, :8] = values.astype("<u8").view(np.uint8).reshape(-1, 8)
    return out


def record_roots(pubkey_root, credentials, reg: Registry,
                 effective_balance, index=None) -> np.ndarray:
    """(K, 32) uint8 hash_tree_root of each Validator record (all of them,
    or those at `index`): its 8 field leaves hashed to depth 3."""
    def pick(a):
        return a if index is None else a[index]
    leaves = np.stack(
        [pick(pubkey_root).astype(">u4").view(np.uint8),
         pick(credentials).astype(">u4").view(np.uint8)]
        + [_u64_leaves(pick(a).astype(U64)) for a in (
            effective_balance, reg.slashed, reg.activation_eligibility_epoch,
            reg.activation_epoch, reg.exit_epoch, reg.withdrawable_epoch)],
        axis=1)
    level = leaves.tobytes()
    for _ in range(3):
        level = _hash_level(level)
    return np.frombuffer(level, np.uint8).reshape(-1, 32)


class RegistryRoots:
    """hash_tree_root of the registry, re-hashing only the records whose
    effective balance moved since the last call (the other fields of a
    record do not change across these transitions)."""

    def __init__(self, pubkey_root, credentials, reg: Registry):
        self.pubkey_root, self.credentials, self.reg = (
            pubkey_root, credentials, reg)
        self.eff = None
        self.records = None

    def root(self, effective_balance: np.ndarray) -> bytes:
        if self.records is None:
            self.records = record_roots(self.pubkey_root, self.credentials,
                                        self.reg, effective_balance).copy()
        else:
            moved = np.flatnonzero(effective_balance != self.eff)
            if moved.size:
                self.records[moved] = record_roots(
                    self.pubkey_root, self.credentials, self.reg,
                    effective_balance, moved)
        self.eff = effective_balance.copy()
        return mix_in_length(
            merkleize(self.records.tobytes(), REGISTRY_LIMIT_DEPTH),
            len(effective_balance))
