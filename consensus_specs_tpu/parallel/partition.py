"""Partition-rule registry — one declarative table shards the flagship.

The mesh story before this module was piecemeal: `batch_verify_sharded`
built its own `Mesh` inside the kernel factory, `parallel.make_mesh`
built another for the epoch step, `resilience.mesh` plumbed raw
`device_ids` tuples between them, and every new sharded surface
re-decided by hand which arrays ride the `data` axis.  This module
centralizes both decisions behind the `match_partition_rules` pattern
(SNIPPETS.md [2], the fmengine/EasyLM regex-path registry):

- `match_partition_rules(rules, tree)` maps every path-named leaf of a
  pytree to a `jax.sharding.PartitionSpec`: scalar leaves are never
  partitioned, the FIRST matching `(regex, spec)` rule wins, and an
  unmatched non-scalar path is a HARD error — a new epoch-state array
  cannot silently land replicated and eat n_devices times its memory.
- `EPOCH_STATE_RULES` is the default table for the flagship epoch
  state: every validator-indexed array (balances, registry fields,
  participation flags, sweep masks, per-validator leaf words) shards
  over the mesh's `data` axis; small per-epoch scalars replicate.
- `build_mesh` is THE mesh builder (n_devices prefix, or an explicit
  `device_ids` subset — the resilience layer's surviving-device form),
  shared by the epoch step, the sharded MerkleForest, and
  `ops.bls_batch`'s sharded RLC/MSM kernels.
- `shard_tree`/`gather_tree` place/fetch a pytree according to the
  matched specs (device_put with `NamedSharding`, one host fetch).
- `sharded_epoch_step` / `partitioned_epoch_step` wire the registry
  into `shard_map`: the step's `in_specs` are DERIVED from the rule
  table (via `epoch_step_specs`), not hand-written per call site, and
  `partitioned_epoch_step` accepts a `device_ids` subset so the
  flagship step composes with `resilience.mesh.MeshVerifier`'s
  recovery ladder (a lost chip re-buckets the SAME epoch state over
  the surviving power-of-two subset — `mesh_rung`).

`mesh_rung(n)` is the mesh-width ladder: the largest power of two <= n.
The sharded merkle reduction and the registry-tree fold both need a
power-of-two device axis, and quantizing device counts through one
sanctioned function also bounds executable churn — the analyzer's
recompile-hazard rule treats device-count reads like raw `len()` dims
and accepts `mesh_rung` as the laundering seam (like `_bucket` for
batch shapes).
"""

from __future__ import annotations

import functools
import re

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import telemetry
from .epoch import EpochParams, EpochScalars, RegistryArrays, epoch_sweep

DATA_AXIS = "data"


# --- rule matching -----------------------------------------------------------


def _is_namedtuple(node) -> bool:
    return isinstance(node, tuple) and hasattr(node, "_fields")


def named_tree_leaves(tree, sep: str = "/") -> list[tuple[str, object]]:
    """[(path, leaf)] pairs with human-readable path names: NamedTuple
    fields and dict keys by name, list/tuple positions by index.  The
    manual walk (instead of `jax.tree_util` key-paths) keeps the names
    stable across jax versions and containers."""
    out: list[tuple[str, object]] = []

    def walk(prefix, node):
        if _is_namedtuple(node):
            for name, sub in zip(node._fields, node):
                walk(prefix + [name], sub)
        elif isinstance(node, dict):
            for key in node:
                walk(prefix + [str(key)], node[key])
        elif isinstance(node, (list, tuple)):
            for i, sub in enumerate(node):
                walk(prefix + [str(i)], sub)
        else:
            out.append((sep.join(prefix), node))

    walk([], tree)
    return out


def _leaf_is_scalar(leaf) -> bool:
    shape = getattr(leaf, "shape", ())
    return len(shape) == 0 or int(np.prod(shape)) == 1


def match_partition_rules(rules, tree, sep: str = "/"):
    """Pytree of `PartitionSpec`s for `tree` under `rules`.

    `rules` is an ordered sequence of `(regex, PartitionSpec)` pairs;
    the FIRST rule whose regex `re.search`-matches a leaf's `/`-joined
    path wins (put specific rules above catch-alls).  Scalar leaves
    (0-d or single-element) are never partitioned, whatever the rules
    say.  A non-scalar leaf that no rule matches raises `ValueError`
    naming the path — sharding decisions are explicit, never a silent
    replicate-by-default."""

    def spec_for(name: str, leaf):
        if _leaf_is_scalar(leaf):
            return P()
        for rule, spec in rules:
            if re.search(rule, name) is not None:
                return spec
        raise ValueError(
            f"no partition rule matches leaf {name!r} "
            f"(shape {getattr(leaf, 'shape', None)}) — add a row to the "
            f"rule table (see README 'Mesh sharding')")

    def walk(prefix, node):
        if _is_namedtuple(node):
            return type(node)(*(walk(prefix + [f], s)
                                for f, s in zip(node._fields, node)))
        if isinstance(node, dict):
            return {k: walk(prefix + [str(k)], v) for k, v in node.items()}
        if isinstance(node, (list, tuple)):
            vals = [walk(prefix + [str(i)], s) for i, s in enumerate(node)]
            return vals if isinstance(node, list) else tuple(vals)
        return spec_for(sep.join(prefix), node)

    return walk([], tree)


def epoch_state_rules(axis: str = DATA_AXIS):
    """The default rule table for the flagship epoch state pytree.

    Every validator-indexed array shards on the mesh's data axis; the
    per-epoch scalars replicate (they are 0-d, so the scalar skip
    already covers them — the explicit row documents intent and keeps
    a (1,)-shaped scalar from hitting the unmatched-path error)."""
    return (
        # RegistryArrays: the struct-of-arrays validator registry
        (r"(^|/)(balance|effective_balance|slashed"
         r"|activation_eligibility_epoch|activation_epoch|exit_epoch"
         r"|withdrawable_epoch|is_source|is_target|is_head"
         r"|inclusion_delay|proposer_index)$", P(axis)),
        # per-validator static leaf words + merkle leaf arrays
        (r"(^|/)(pubkey_root|credentials|record_roots|leaf_words"
         r"|balances)$", P(axis)),
        # sweep masks / dirty-set arrays ride with the validators
        (r"(^|/)(mask|sweep_mask|dirty_mask|dirty_idx|chunk_idx)$",
         P(axis)),
        # per-epoch scalars are replicated
        (r"(^|/)(current_epoch|finality_delay|slashings_sum|length)$",
         P()),
    )


EPOCH_STATE_RULES = epoch_state_rules()


# --- mesh building (the ONE builder) -----------------------------------------


def mesh_rung(n: int) -> int:
    """Largest power of two <= n — the mesh-width ladder.  The sharded
    merkle reductions need a power-of-two axis, and quantizing device
    counts here bounds per-topology executable churn (the analyzer
    accepts this as the device-count laundering seam)."""
    assert n >= 1, n
    return 1 << (int(n).bit_length() - 1)


def available_devices() -> int:
    """Device-pool size (the one `jax.devices()` probe the sharded
    surfaces and `resilience.mesh` share)."""
    return len(jax.devices())


def build_mesh(n_devices: int | None = None, axis: str = DATA_AXIS,
               device_ids=None, require_pow2: bool = False) -> Mesh:
    """The shared 1-axis mesh builder.

    `device_ids` (a tuple of `jax.devices()` indices) builds the mesh
    from exactly those devices — the resilience layer's surviving-set
    form after a `device_loss`; otherwise the first `n_devices` (all,
    when None).  `require_pow2` asserts the width is a power of two
    (the sharded merkle reductions need it; quantize with
    `mesh_rung`)."""
    devs = jax.devices()
    if device_ids is not None:
        device_ids = tuple(int(i) for i in device_ids)
        assert device_ids and max(device_ids) < len(devs), device_ids
        devs = [devs[i] for i in device_ids]
    elif n_devices is not None:
        assert 1 <= n_devices <= len(devs), (n_devices, len(devs))
        devs = devs[:n_devices]
    n = len(devs)
    if require_pow2:
        assert n & (n - 1) == 0, (
            f"mesh must be a power of two for the sharded merkle "
            f"reduction, got {n} devices (quantize with mesh_rung)")
    return Mesh(np.array(devs), (axis,))


# --- shard / gather helpers --------------------------------------------------


def shard_tree(mesh: Mesh, tree, rules=EPOCH_STATE_RULES):
    """device_put every leaf of `tree` with the `NamedSharding` its
    matched rule names (replicated for scalars).  Returns the same
    container type with device arrays."""
    specs = match_partition_rules(rules, tree)
    return jax.tree_util.tree_map(
        lambda leaf, spec: jax.device_put(leaf, NamedSharding(mesh, spec)),
        tree, specs)


def gather_tree(tree):
    """Fetch every leaf back to host numpy (the one blocking transfer
    of a shard/compute/gather round)."""
    return jax.tree_util.tree_map(lambda leaf: np.asarray(leaf), tree)


# --- the registry-driven sharded epoch step ----------------------------------


def epoch_step_specs(axis: str = DATA_AXIS):
    """`shard_map` in/out specs for the flagship epoch step, DERIVED
    from the rule table (a template tree per argument) instead of
    hand-written per call site.

    Returns (in_specs, out_specs) for
    f(reg: RegistryArrays, sc: EpochScalars, length, pubkey_root,
      credentials) -> (new_bal, new_eff, balances_root, registry_root).
    """
    rules = epoch_state_rules(axis)
    dummy = np.zeros((2,), np.uint64)
    reg_specs = match_partition_rules(
        rules, RegistryArrays(*([dummy] * len(RegistryArrays._fields))))
    sc_specs = match_partition_rules(
        rules, EpochScalars(*([np.uint64(0)] * len(EpochScalars._fields))))
    leaf_specs = match_partition_rules(
        rules, {"pubkey_root": np.zeros((2, 8), np.uint32),
                "credentials": np.zeros((2, 8), np.uint32)})
    in_specs = (reg_specs, sc_specs, P(), leaf_specs["pubkey_root"],
                leaf_specs["credentials"])
    out_specs = (P(axis), P(axis), P(), P())
    return in_specs, out_specs


def sharded_epoch_step(mesh: Mesh, params: EpochParams,
                       axis: str = DATA_AXIS):
    """Mesh-sharded full flagship step: sweep with psum totals +
    cross-shard proposer-reward scatter + sharded balances/registry
    merkle roots, with the shard_map specs coming from the partition
    registry.  Inputs are sharded (N,) arrays (N divisible by the mesh
    size, power of two); outputs (new_bal, new_eff, balances_root,
    registry_root) with the roots replicated."""
    from . import require_x64
    from .merkle import (ValidatorLeaves, balances_list_root,
                         validator_records_root, validator_registry_root)

    require_x64()

    def _step(reg: RegistryArrays, sc: EpochScalars, length,
              pubkey_root, credentials):
        new_bal, new_eff = epoch_sweep(reg, sc, params, axis_name=axis)
        bal_root = balances_list_root(new_bal, length, axis_name=axis)
        rec_roots = validator_records_root(
            ValidatorLeaves(pubkey_root, credentials), new_eff,
            reg.slashed, reg.activation_eligibility_epoch,
            reg.activation_epoch, reg.exit_epoch, reg.withdrawable_epoch)
        reg_root = validator_registry_root(rec_roots, length,
                                           axis_name=axis)
        return new_bal, new_eff, bal_root, reg_root

    in_specs, out_specs = epoch_step_specs(axis)
    # replication is not checked: the roots are replicated by explicit
    # all_gathers
    sharded = jax.shard_map(_step, mesh=mesh, in_specs=in_specs,
                            out_specs=out_specs, check_vma=False)
    return jax.jit(sharded)


@functools.lru_cache(maxsize=16)
def _partitioned_epoch_step_cached(params: EpochParams,
                                   n_devices: int | None,
                                   device_ids: tuple | None,
                                   axis: str):
    mesh = build_mesh(n_devices=n_devices, device_ids=device_ids,
                      axis=axis, require_pow2=True)
    return sharded_epoch_step(mesh, params, axis=axis)


def partitioned_epoch_step(params: EpochParams,
                           n_devices: int | None = None,
                           device_ids: tuple | None = None,
                           axis: str = DATA_AXIS):
    """`sharded_epoch_step` keyed by mesh topology: the first
    `n_devices` (all, when None), or an explicit `device_ids` subset —
    the resilience layer's surviving-set form, so the flagship step
    re-buckets onto a shrunken mesh exactly like the sharded RLC batch.
    One executable per (params, topology) — the positional-normalizing
    facade keeps keyword/default spellings on ONE lru cache key; device
    counts are quantized through `mesh_rung` by the callers that derive
    them from a pool probe."""
    from ..telemetry import costmodel

    telemetry.count("parallel.partition.step_topologies")
    # cost seam presence for the per-topology executable: the step's
    # own kernels record through their spans; the watermark sample
    # keeps the topology build visible to CST_COSTMODEL rounds
    costmodel.sample_watermark("parallel.partition.step")
    if device_ids is not None:
        device_ids = tuple(int(i) for i in device_ids)
    return _partitioned_epoch_step_cached(params, n_devices,
                                          device_ids, axis)


def epoch_step_dispatcher(params: EpochParams, axis: str = DATA_AXIS):
    """A `resilience.mesh.MeshVerifier`-shaped dispatch function for
    the flagship epoch step: `dispatch(payload, rng, device_ids)`
    re-shards the SAME epoch state over the given device subset
    (trimmed to the `mesh_rung` power of two) and returns a
    `DeviceFuture` settling to the host (new_bal, new_eff,
    balances_root, registry_root) tuple.  Pair it with
    `MeshVerifier(dispatch_fn=..., result_cast=None)` — see
    `resilience.mesh.sharded_epoch_verifier` — and the `device_ids`-
    subset fallback covers the epoch step, not just the RLC batch."""
    from ..serve.futures import value_future

    def dispatch(payload, rng, device_ids):
        del rng                      # epoch steps draw no randomness
        reg, sc, length, pubkey_root, credentials = payload
        ids = tuple(int(i) for i in device_ids)
        ids = ids[:mesh_rung(len(ids))]
        with telemetry.span("parallel.partition.epoch_dispatch",
                            devices=len(ids)):
            step = partitioned_epoch_step(params, device_ids=ids,
                                          axis=axis)
            mesh = build_mesh(device_ids=ids, axis=axis,
                              require_pow2=True)
            rules = epoch_state_rules(axis)
            reg_s = shard_tree(mesh, reg, rules)
            leaves = shard_tree(mesh, {"pubkey_root": pubkey_root,
                                       "credentials": credentials}, rules)
            out = step(reg_s, sc, length, leaves["pubkey_root"],
                       leaves["credentials"])
        return value_future(out)

    return dispatch
