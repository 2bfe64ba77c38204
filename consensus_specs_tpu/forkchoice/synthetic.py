"""Synthetic fork-choice workloads — one generator for the serve
loadgen and the device-vs-oracle parity tests.

Both drive the same shape of traffic (a seeded random block tree, an
all-active 32 ETH validator set, and an attestation stream whose target
epochs climb one per batch so latest-message updates keep being
accepted at sustained load); keeping a single implementation means a
change to the store's constructor or the fold's accept semantics can
never skew one workload silently while the other is fixed.
"""

from __future__ import annotations

import numpy as np

from .store import ProtoArrayStore


def synthetic_store(n_blocks: int, n_validators: int, seed: int = 29,
                    slots_per_epoch: int = 32,
                    preset: str = "mainnet"):
    """(store, roots): a seeded random tree (every non-anchor block's
    parent drawn uniformly among its predecessors, child slot =
    parent slot + 1) over an all-active 32 ETH validator set, with the
    clock one epoch past the newest block."""
    rng = np.random.RandomState(seed)
    anchor = b"\x41" + b"\x00" * 31
    store = ProtoArrayStore(anchor, 0, slots_per_epoch=slots_per_epoch,
                            preset=preset)
    roots = [anchor]
    for i in range(1, n_blocks):
        parent = roots[rng.randint(0, i)]
        slot = store.slots[store.root_index[parent]] + 1
        root = bytes(rng.randint(0, 256, 32, dtype=np.uint8))
        store.add_block(root, parent, slot, 0, 0)
        roots.append(root)
    store.set_validators(np.full(n_validators, 32 * 10 ** 9,
                                 dtype=np.int64))
    store.set_current_epoch(max(store.slots) // slots_per_epoch + 1)
    return store, roots


def attestation_stream(roots, n_validators: int, batch: int,
                       seed: int = 29):
    """Infinite (validator_indices, target_epochs, block_roots) batch
    stream: uniform validators and vote blocks, epochs climbing one
    per batch (so the strictly-greater rule keeps accepting)."""
    rng = np.random.RandomState(seed + 1)
    epoch = 1
    while True:
        idx = rng.randint(0, n_validators, batch)
        blk = [roots[rng.randint(0, len(roots))] for _ in range(batch)]
        yield (idx.tolist(), [epoch] * batch, blk)
        epoch += 1
