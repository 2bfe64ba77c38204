"""jax.sharding mesh layouts + the sharded epoch step.

The scale axes of this domain (SURVEY.md §5.7) are validator count and
attestation count; both shard on one `data` mesh axis.  Which array
rides that axis is decided ONCE, by the partition-rule registry
(`parallel.partition`: regex path -> PartitionSpec over the epoch state
pytree, the `match_partition_rules` pattern).  `sharded_epoch_step` is
the "full training step" of this framework: the per-validator epoch
sweep (rewards, slashings, effective balances) fused with the balances-
and registry-list merkleization, `shard_map`ped over the mesh with
psum / all_gather collectives over ICI — its in_specs come from the
rule table, and `partition.partitioned_epoch_step` re-buckets the same
step onto a `device_ids` subset for the mesh-resilience ladder.
"""

from __future__ import annotations

import jax

from ..utils.jaxtools import watch_builds
from .bridge import (  # noqa: F401
    pad_pow2,
    participation_from_pending,
    registry_arrays_from_state,
    validator_static_leaf_words,
)
from .epoch import EpochParams, EpochScalars, RegistryArrays, epoch_sweep  # noqa: F401
from .incremental import (  # noqa: F401
    MerkleForest,
    ShardedMerkleForest,
    SSZProof,
    balances_forest,
    dirty_balance_leaves,
    dirty_chunks_from_validators,
    emit_proofs,
    emit_proofs_async,
    merkleize_dirty,
    merkleize_dirty_async,
    pad_dirty_idx,
    registry_forest,
    sharded_balances_forest,
    verify_proof,
)
from .merkle import (  # noqa: F401
    ValidatorLeaves,
    balances_list_root,
    pack_u64_chunks,
    u64_leaf_planes,
    validator_records_root,
    validator_registry_root,
)
from .partition import (  # noqa: F401
    DATA_AXIS,
    EPOCH_STATE_RULES,
    available_devices,
    build_mesh,
    epoch_state_rules,
    epoch_step_dispatcher,
    epoch_step_specs,
    gather_tree,
    match_partition_rules,
    mesh_rung,
    named_tree_leaves,
    partitioned_epoch_step,
    shard_tree,
    sharded_epoch_step,
)


def require_x64() -> None:
    """The sweep/merkle kernels carry Gwei balances and epochs as uint64;
    without `jax_enable_x64` JAX silently downcasts them to uint32.  The
    flag is process-wide, so it is set by *entry points* (bench.py,
    __graft_entry__, tests/conftest.py) — flipping it at import time here
    would retroactively change dtypes under any host application."""
    if not jax.config.jax_enable_x64:
        raise RuntimeError(
            "consensus_specs_tpu.parallel needs uint64: enable x64 first "
            '(jax.config.update("jax_enable_x64", True) at process start, '
            "or JAX_ENABLE_X64=1)")

__all__ = [
    "EpochParams", "EpochScalars", "RegistryArrays", "ValidatorLeaves",
    "epoch_sweep", "balances_list_root", "validator_records_root",
    "validator_registry_root", "make_mesh", "shard_registry",
    "make_epoch_step", "make_sharded_epoch_step",
    "registry_arrays_from_state", "validator_static_leaf_words",
    "participation_from_pending", "pad_pow2",
    "MerkleForest", "SSZProof", "balances_forest", "registry_forest",
    "merkleize_dirty", "merkleize_dirty_async", "emit_proofs",
    "emit_proofs_async", "dirty_balance_leaves",
    "dirty_chunks_from_validators", "pad_dirty_idx", "verify_proof",
    # partition-rule registry (parallel.partition)
    "DATA_AXIS", "EPOCH_STATE_RULES", "available_devices", "build_mesh",
    "epoch_state_rules", "epoch_step_dispatcher", "epoch_step_specs",
    "gather_tree", "match_partition_rules", "mesh_rung",
    "named_tree_leaves", "partitioned_epoch_step", "shard_tree",
    "sharded_epoch_step", "ShardedMerkleForest",
    "sharded_balances_forest",
]


def make_mesh(n_devices: int | None = None, axis: str = DATA_AXIS):
    """1-axis device mesh (delegates to `partition.build_mesh`, the one
    mesh builder).  Power-of-two width enforced: the sharded merkle
    reduction needs it (quantize with `mesh_rung`)."""
    return build_mesh(n_devices=n_devices, axis=axis, require_pow2=True)


def shard_registry(mesh, reg: RegistryArrays, axis: str = DATA_AXIS):
    """Place each (N,) registry array sharded on the mesh's data axis —
    the placements come from the partition-rule registry, not per-field
    code."""
    return shard_tree(mesh, reg, epoch_state_rules(axis))


def make_epoch_step(params: EpochParams):
    """Single-device jitted epoch step: sweep + balances root.

    Returns f(reg: RegistryArrays, sc: EpochScalars, length)
         -> (new_bal, new_eff, balances_root_words).
    Registry arrays must be pre-padded to a power-of-two length; `length`
    is the true validator count (for the SSZ length mix-in).
    """
    require_x64()
    watch_builds()

    @jax.jit
    def step(reg: RegistryArrays, sc: EpochScalars, length):
        new_bal, new_eff = epoch_sweep(reg, sc, params, axis_name=None)
        root = balances_list_root(new_bal, length, axis_name=None)
        return new_bal, new_eff, root

    return step


def make_sharded_epoch_step(mesh, params: EpochParams,
                            axis: str = DATA_AXIS):
    """Mesh-sharded full step (facade over
    `partition.sharded_epoch_step`; the shard_map specs come from the
    partition-rule registry).

    Inputs are sharded (N,) arrays (N divisible by mesh size, power of
    two); `pubkey_root`/`credentials` are the (N, 8) static leaf words.
    Outputs: (new_bal, new_eff, balances_root, registry_root) with the
    roots replicated.  The build ledger (`utils.jaxtools.builds`) watches
    from here on.
    """
    watch_builds()
    return sharded_epoch_step(mesh, params, axis=axis)
