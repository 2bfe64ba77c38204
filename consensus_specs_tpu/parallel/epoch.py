"""TPU-native epoch processing: the per-validator sweep as one fused XLA
program over a struct-of-arrays registry, sharded across a device mesh.

This is the TPU redesign of the reference's epoch pipeline
(`specs/phase0/beacon-chain.md:1410-1850`: `get_attestation_deltas`,
`process_rewards_and_penalties`, `process_slashings`,
`process_effective_balance_updates`).  The reference walks Python lists of
`Validator` objects per epoch; here the registry lives as flat uint64/bool
arrays, the whole sweep is elementwise + a handful of reductions, and under a
`jax.sharding.Mesh` the reductions become `psum` over the `data` axis so the
1M-validator sweep scales across chips.

Exactness contract: all arithmetic is uint64 (requires jax x64) and matches
the spec's integer semantics bit-for-bit — verified by
`tests/test_parallel_epoch.py` against the executable spec.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from .. import telemetry

# x64 (uint64 spec arithmetic) is enabled once, in parallel/__init__ — this
# module is only reachable through that package import.

U64 = jnp.uint64


class EpochParams(NamedTuple):
    """Preset/config constants the sweep needs (python ints; closed over as
    compile-time constants — they never change within a preset)."""

    base_reward_factor: int
    base_rewards_per_epoch: int
    proposer_reward_quotient: int
    inactivity_penalty_quotient: int
    min_epochs_to_inactivity_penalty: int
    effective_balance_increment: int
    max_effective_balance: int
    hysteresis_quotient: int
    hysteresis_downward_multiplier: int
    hysteresis_upward_multiplier: int
    epochs_per_slashings_vector: int
    proportional_slashing_multiplier: int

    @classmethod
    def from_spec(cls, spec) -> "EpochParams":
        return cls(
            base_reward_factor=int(spec.BASE_REWARD_FACTOR),
            base_rewards_per_epoch=int(spec.BASE_REWARDS_PER_EPOCH),
            proposer_reward_quotient=int(spec.PROPOSER_REWARD_QUOTIENT),
            inactivity_penalty_quotient=int(spec.INACTIVITY_PENALTY_QUOTIENT),
            min_epochs_to_inactivity_penalty=int(
                spec.MIN_EPOCHS_TO_INACTIVITY_PENALTY),
            effective_balance_increment=int(spec.EFFECTIVE_BALANCE_INCREMENT),
            max_effective_balance=int(spec.MAX_EFFECTIVE_BALANCE),
            hysteresis_quotient=int(spec.HYSTERESIS_QUOTIENT),
            hysteresis_downward_multiplier=int(
                spec.HYSTERESIS_DOWNWARD_MULTIPLIER),
            hysteresis_upward_multiplier=int(
                spec.HYSTERESIS_UPWARD_MULTIPLIER),
            epochs_per_slashings_vector=int(spec.EPOCHS_PER_SLASHINGS_VECTOR),
            proportional_slashing_multiplier=int(
                spec.PROPORTIONAL_SLASHING_MULTIPLIER),
        )


class RegistryArrays(NamedTuple):
    """Struct-of-arrays view of the validator registry + participation for
    one epoch transition.  All shapes (N,); shardable on the leading axis."""

    balance: jnp.ndarray             # uint64 Gwei
    effective_balance: jnp.ndarray   # uint64 Gwei
    slashed: jnp.ndarray             # bool
    activation_eligibility_epoch: jnp.ndarray  # uint64
    activation_epoch: jnp.ndarray    # uint64
    exit_epoch: jnp.ndarray          # uint64
    withdrawable_epoch: jnp.ndarray  # uint64
    # previous-epoch participation (already committee-expanded on host from
    # PendingAttestations / participation flags)
    is_source: jnp.ndarray           # bool — attested with matching source
    is_target: jnp.ndarray           # bool — …and matching target
    is_head: jnp.ndarray             # bool — …and matching head
    inclusion_delay: jnp.ndarray     # uint64 — min delay (1 if none)
    proposer_index: jnp.ndarray      # int32 — proposer of min-delay att (0 if none)


class EpochScalars(NamedTuple):
    """Per-epoch scalar inputs (traced; uint64 0-d arrays)."""

    current_epoch: jnp.ndarray
    finality_delay: jnp.ndarray      # previous_epoch - finalized.epoch
    slashings_sum: jnp.ndarray       # sum(state.slashings)


def _isqrt_u64(n):
    """Exact integer sqrt for n < 2**63 (float64 seed + correction)."""
    x = jnp.floor(jnp.sqrt(n.astype(jnp.float64))).astype(U64)
    # one Newton step guards seeds that overshoot, then exact ±1 correction
    x = jnp.where(x > 0, jnp.minimum(x, (x + n // jnp.maximum(x, 1)) // 2), x)
    x = jnp.where(x * x > n, x - 1, x)
    x = jnp.where((x + 1) * (x + 1) <= n, x + 1, x)
    return x


def _u64_collective(x, reduce):
    """`reduce` (a psum-like collective) of uint64 `x`, carried on uint32
    lanes: the TPU's all-reduce refuses uint64 ("Supported lowering only
    of Sum all reduce").  Each value splits into four 16-bit limbs on a
    new leading axis, the limb sums stay exact in uint32 for up to 2**16
    devices, and they recombine in uint64, wrapping mod 2**64 exactly as
    a uint64 sum would."""
    limbs = jnp.stack([((x >> U64(16 * k)) & U64(0xFFFF)).astype(jnp.uint32)
                       for k in range(4)])
    sums = reduce(limbs).astype(U64)
    return (sums[0] + (sums[1] << U64(16)) + (sums[2] << U64(32))
            + (sums[3] << U64(48)))


def _total(x, axis_name: str | None):
    """Global sum of a (N,) shard — psum across the mesh axis if sharded.

    `axis_name` is annotated static: the branch below is a host-side
    sharding decision, not data-dependent control flow (the analyzer's
    recompile-traced-branch rule keys off the annotation)."""
    s = jnp.sum(x)
    if axis_name is not None:
        s = _u64_collective(s, lambda v: lax.psum(v, axis_name))
    return s


def epoch_sweep(reg: RegistryArrays, sc: EpochScalars, params: EpochParams,
                axis_name: str | None = None):
    """One epoch's rewards/penalties + slashings + effective-balance sweep.

    Returns (new_balance, new_effective_balance), both (N,) uint64.
    Pure function of its inputs; jit/shard_map it at the call site.

    The body runs under `jax.named_scope` (the sweep shows up as one
    block in XLA device profiles) and a telemetry span — under jit the
    span fires per TRACE, so its wall time is the Python tracing cost,
    not the device step."""
    with telemetry.span("parallel.epoch_sweep.trace",
                        n=int(reg.balance.shape[0])), \
            jax.named_scope("cst.epoch_sweep"):
        return _epoch_sweep_impl(reg, sc, params, axis_name)


def _epoch_sweep_impl(reg: RegistryArrays, sc: EpochScalars,
                      params: EpochParams, axis_name: str | None = None):
    p = params
    one = jnp.uint64(1)
    prev_epoch = jnp.maximum(sc.current_epoch, one) - one

    active_cur = ((reg.activation_epoch <= sc.current_epoch)
                  & (sc.current_epoch < reg.exit_epoch))
    active_prev = ((reg.activation_epoch <= prev_epoch)
                   & (prev_epoch < reg.exit_epoch))
    eligible = active_prev | (reg.slashed
                              & (prev_epoch + one < reg.withdrawable_epoch))

    incr = jnp.uint64(p.effective_balance_increment)
    total_active = jnp.maximum(
        incr, _total(jnp.where(active_cur, reg.effective_balance, 0), axis_name))
    sqrt_total = _isqrt_u64(total_active)

    # get_base_reward (beacon-chain.md): eff * BRF // isqrt(total) // BRPE
    base_reward = (reg.effective_balance * jnp.uint64(p.base_reward_factor)
                   // sqrt_total // jnp.uint64(p.base_rewards_per_epoch))
    proposer_reward = base_reward // jnp.uint64(p.proposer_reward_quotient)

    in_leak = sc.finality_delay > jnp.uint64(p.min_epochs_to_inactivity_penalty)

    unslashed = ~reg.slashed
    rewards = jnp.zeros_like(reg.balance)
    penalties = jnp.zeros_like(reg.balance)

    # -- source/target/head component deltas (get_attestation_component_deltas)
    for flag in (reg.is_source & unslashed,
                 reg.is_target & unslashed,
                 reg.is_head & unslashed):
        attesting_balance = jnp.maximum(
            incr, _total(jnp.where(flag, reg.effective_balance, 0), axis_name))
        participation_reward = (base_reward * (attesting_balance // incr)
                                // (total_active // incr))
        comp_reward = jnp.where(in_leak, base_reward, participation_reward)
        rewards += jnp.where(eligible & flag, comp_reward, 0)
        penalties += jnp.where(eligible & ~flag, base_reward, 0)

    # -- inclusion-delay micro rewards (get_inclusion_delay_deltas)
    src = reg.is_source & unslashed
    max_attester_reward = base_reward - proposer_reward
    rewards += jnp.where(
        src, max_attester_reward // jnp.maximum(reg.inclusion_delay, one), 0)
    # proposer micro-reward: scatter-add to the proposer of each attester's
    # earliest-included attestation.  Under sharding the proposer may live on
    # another shard: scatter into a global-length accumulator and psum it.
    prop_contrib = jnp.where(src, proposer_reward, 0)
    if axis_name is None:
        rewards = rewards.at[reg.proposer_index].add(
            prop_contrib, mode="drop")
    else:
        n_local = reg.balance.shape[0]
        n_dev = lax.psum(1, axis_name)
        global_acc = jnp.zeros((n_local * n_dev,), dtype=U64)
        global_acc = global_acc.at[reg.proposer_index].add(
            prop_contrib, mode="drop")
        # reduce-scatter: each shard receives exactly its own reduced slice
        # (no full-array broadcast back as psum would do)
        rewards += _u64_collective(global_acc, lambda v: lax.psum_scatter(
            v, axis_name, scatter_dimension=1, tiled=True))

    # -- inactivity-leak penalties (get_inactivity_penalty_deltas)
    leak_base = (jnp.uint64(p.base_rewards_per_epoch) * base_reward
                 - proposer_reward)
    leak_extra = (reg.effective_balance * sc.finality_delay
                  // jnp.uint64(p.inactivity_penalty_quotient))
    tgt = reg.is_target & unslashed
    penalties += jnp.where(in_leak & eligible, leak_base, 0)
    penalties += jnp.where(in_leak & eligible & ~tgt, leak_extra, 0)

    # -- apply deltas (process_rewards_and_penalties; saturating decrease)
    is_genesis = sc.current_epoch == 0
    bal = reg.balance + jnp.where(is_genesis, 0, rewards)
    pen = jnp.where(is_genesis, 0, penalties)
    bal = jnp.where(pen > bal, 0, bal - pen)

    # -- process_slashings (correlated slashing penalty sweep)
    adj_slashing = jnp.minimum(
        sc.slashings_sum * jnp.uint64(p.proportional_slashing_multiplier),
        total_active)
    hits = reg.slashed & (
        sc.current_epoch + jnp.uint64(p.epochs_per_slashings_vector // 2)
        == reg.withdrawable_epoch)
    slash_pen = ((reg.effective_balance // incr) * adj_slashing
                 // total_active * incr)
    slash_pen = jnp.where(hits, slash_pen, 0)
    bal = jnp.where(slash_pen > bal, 0, bal - slash_pen)

    # -- process_effective_balance_updates (hysteresis)
    hyst_incr = incr // jnp.uint64(p.hysteresis_quotient)
    down = hyst_incr * jnp.uint64(p.hysteresis_downward_multiplier)
    up = hyst_incr * jnp.uint64(p.hysteresis_upward_multiplier)
    candidate = jnp.minimum(bal - bal % incr,
                            jnp.uint64(p.max_effective_balance))
    move = ((bal + down < reg.effective_balance)
            | (reg.effective_balance + up < bal))
    new_eff = jnp.where(move, candidate, reg.effective_balance)

    return bal, new_eff
