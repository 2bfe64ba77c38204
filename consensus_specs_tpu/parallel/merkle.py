"""Device-side SSZ merkleization of the registry-scale lists.

The reference amortizes `hash_tree_root(state)` with remerkleable's cached
pointer-tree (`eth2spec/utils/ssz/ssz_impl.py:25`).  The TPU redesign keeps
the big lists (balances, validators) as flat arrays and re-hashes them as a
batched tree reduction on device — at 1M validators the whole balances tree
is ~19 SHA-256 levels of perfectly regular batches of 64-byte blocks, held
as word planes (word j of every node in row j) for `ops.sha256_jax`'s
kernel.

Sharded form: each device reduces its local contiguous sub-tree, the (tiny)
per-device roots are `all_gather`ed over the mesh axis and folded on every
device (replicated), then the zero-subtree ladder up to the SSZ limit depth
and the length mix-in finish the root.  Collectives ride the ICI: one
all_gather of n_dev×32 bytes per list.

Parity oracle: `utils.ssz.ssz_impl.hash_tree_root` on the spec containers
(`tests/test_parallel_merkle.py`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from .. import telemetry
from ..ops.sha256_jax import reduce_planes, sha256_64B_planes, zero_ladder

# uint64 packing needs x64; entry points enable it (see parallel.require_x64)


def _bswap32(x):
    x = x.astype(jnp.uint32)
    return ((x & jnp.uint32(0xFF)) << 24) | ((x & jnp.uint32(0xFF00)) << 8) \
        | ((x >> 8) & jnp.uint32(0xFF00)) | (x >> 24)


def pack_u64_chunks(values):
    """(N,) uint64 -> (ceil(N/4), 8) big-endian uint32 chunk words with SSZ
    little-endian byte layout (4 uint64 per 32-byte chunk)."""
    n = values.shape[0]
    pad = (-n) % 4
    if pad:
        values = jnp.concatenate([values, jnp.zeros((pad,), dtype=jnp.uint64)])
    v = values.reshape(-1, 4)
    lo = _bswap32(v & jnp.uint64(0xFFFFFFFF))
    hi = _bswap32(v >> jnp.uint64(32))
    return jnp.stack([lo[:, 0], hi[:, 0], lo[:, 1], hi[:, 1],
                      lo[:, 2], hi[:, 2], lo[:, 3], hi[:, 3]], axis=-1)


def u64_leaf_planes(values):
    """(N,) uint64 -> (8, N) word planes of SSZ uint64 field leaves: each
    value alone in a 32-byte chunk, so two planes (lo, hi) and six zero."""
    lo = _bswap32(values & jnp.uint64(0xFFFFFFFF))
    hi = _bswap32(values >> jnp.uint64(32))
    z = jnp.zeros_like(lo)
    return jnp.stack([lo, hi, z, z, z, z, z, z])


def subtree_root(words, depth: int):
    """Root of the 2**depth-leaf subtree containing `words` (N, 8), with the
    tail padded by zero-subtree hashes.  N must be a power of two <= 2**depth
    (pad on host); levels above the data fold against the zero ladder."""
    n = words.shape[0]
    assert n & (n - 1) == 0 and n >= 1
    data_depth = n.bit_length() - 1
    root = reduce_planes(words.T, data_depth)[:, 0]
    return zero_ladder(root, data_depth, depth)


def mix_in_length(root_words, length):
    """H(root || le64(length) || zeros) — SSZ list length mix-in."""
    lo = _bswap32(length.astype(jnp.uint64) & jnp.uint64(0xFFFFFFFF))
    hi = _bswap32(length.astype(jnp.uint64) >> jnp.uint64(32))
    z = jnp.zeros((), dtype=jnp.uint32)
    tail = jnp.stack([lo, hi, z, z, z, z, z, z])
    blk = jnp.concatenate([root_words, tail])
    return sha256_64B_planes(blk[:, None])[:, 0]


def balances_list_root(balances, length, limit_depth: int = 38,
                       axis_name: str | None = None):
    """hash_tree_root of `List[uint64, 2**40]` (SSZ packed, limit 2**40
    values -> 2**38 chunks).  `balances` is the (padded, pow2) local shard;
    `length` the true global element count."""
    if axis_name is not None:
        # shard boundaries must be 32-byte-chunk-aligned, or pack_u64_chunks
        # would zero-pad mid-stream and silently corrupt the root
        assert balances.shape[0] % 4 == 0, (
            f"sharded balances_list_root needs a chunk-aligned shard "
            f"(multiple of 4 uint64), got {balances.shape[0]}")
    with telemetry.span("parallel.balances_list_root.trace",
                        n=int(balances.shape[0])), \
            jax.named_scope("cst.balances_list_root"):
        chunks = pack_u64_chunks(balances)
        if axis_name is None:
            root = subtree_root(chunks, limit_depth)
        else:
            root = _sharded_list_root(chunks, limit_depth, axis_name)
        return mix_in_length(root, length)


def _sharded_list_root(local_chunks, limit_depth: int, axis_name: str):
    """Each shard holds a contiguous power-of-two run of data chunks: reduce
    it to its local root, all_gather the shard roots, finish the data tree,
    THEN fold the zero-subtree ladder (padding sits above the whole data
    tree, not inside each shard)."""
    n_local = local_chunks.shape[0]
    assert n_local & (n_local - 1) == 0
    local_depth = n_local.bit_length() - 1
    local = subtree_root(local_chunks, local_depth)
    roots = lax.all_gather(local, axis_name)  # (n_dev, 8) on every device
    n_dev = roots.shape[0]
    assert n_dev & (n_dev - 1) == 0, (
        f"sharded list root needs a power-of-two mesh, got {n_dev} devices")
    shard_depth = (n_dev - 1).bit_length()
    root = reduce_planes(roots.T, shard_depth)[:, 0]
    return zero_ladder(root, local_depth + shard_depth, limit_depth)


class ValidatorLeaves:
    """Precomputed per-validator leaf words for the registry tree.

    A `Validator` container has 8 field leaves
    (`specs/phase0/beacon-chain.md` `Validator`): [pubkey_root,
    withdrawal_credentials, effective_balance, slashed, act_eligibility,
    activation, exit, withdrawable].  pubkey_root and credentials are static
    per validator (change only on deposit) and are precomputed host-side;
    the dynamic uint64/bool fields come straight from the sweep arrays.
    """

    def __init__(self, pubkey_root_words, credentials_words):
        self.pubkey_root = jnp.asarray(pubkey_root_words)    # (N, 8) uint32
        self.credentials = jnp.asarray(credentials_words)    # (N, 8) uint32


def validator_records_root(leaves: ValidatorLeaves, effective_balance,
                           slashed, activation_eligibility_epoch,
                           activation_epoch, exit_epoch, withdrawable_epoch):
    """(N,) arrays -> (N, 8) root words of each Validator container (a full
    depth-3 reduction over the 8 field leaves, batched over validators).
    Each leaf is 8 word planes, so a record's siblings pair by stacking
    planes: three kernel calls of 4N, 2N and N hashes."""
    n = int(effective_balance.shape[0])
    with telemetry.span("parallel.validator_records_root.trace", n=n), \
            jax.named_scope("cst.validator_records_root"):
        f = [leaves.pubkey_root.T,
             leaves.credentials.T,
             u64_leaf_planes(effective_balance),
             u64_leaf_planes(slashed.astype(jnp.uint64)),
             u64_leaf_planes(activation_eligibility_epoch),
             u64_leaf_planes(activation_epoch),
             u64_leaf_planes(exit_epoch),
             u64_leaf_planes(withdrawable_epoch)]
        level = jnp.stack(f, axis=1)        # (8 words, 8 leaves, N)
        for half in (4, 2, 1):
            # leaf 2p + side of each record -> block p: (16, half * N)
            blocks = level.reshape(8, half, 2, n).transpose(2, 0, 1, 3)
            level = sha256_64B_planes(blocks.reshape(16, half * n))
            level = level.reshape(8, half, n)
        return level[:, 0, :].T


def validator_registry_root(record_roots, length, limit_depth: int = 40,
                            axis_name: str | None = None):
    """hash_tree_root of `List[Validator, 2**40]` given the (padded, pow2)
    local shard of per-record roots.

    Pad rows (global index >= `length`) are masked to zero chunks here:
    SSZ pads the List's leaf level with 32-byte zero chunks, NOT with the
    record root of an all-zero Validator."""
    n_local = record_roots.shape[0]
    with telemetry.span("parallel.validator_registry_root.trace",
                        n=n_local), \
            jax.named_scope("cst.validator_registry_root"):
        idx = jnp.arange(n_local, dtype=jnp.uint64)
        if axis_name is not None:
            idx = idx + (lax.axis_index(axis_name).astype(jnp.uint64)
                         * jnp.uint64(n_local))
        in_range = idx < jnp.asarray(length, dtype=jnp.uint64)
        record_roots = jnp.where(in_range[:, None], record_roots,
                                 jnp.zeros_like(record_roots))
        if axis_name is None:
            root = subtree_root(record_roots, limit_depth)
        else:
            root = _sharded_list_root(record_roots, limit_depth,
                                      axis_name)
        return mix_in_length(root, length)
