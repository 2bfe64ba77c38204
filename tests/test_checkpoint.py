"""MerkleForest checkpoint/restore (`resilience/checkpoint.py`):
snapshot + leaf-delta journal round-trips, SSZ-oracle root parity,
corrupted-checksum rejection with full-rebuild fallback,
restore-under-concurrent-update safety, and the `checkpoint` block
schema.

Small forests (64–256 chunks) keep every test on depths tier-1 already
compiles; the 2^20-chunk speedup measurement lives in the chaos
checkpoint segment (`resilience.chaos`), not here.
"""

from __future__ import annotations

import json
import threading

import numpy as np
import pytest

from consensus_specs_tpu.parallel.incremental import MerkleForest
from consensus_specs_tpu.resilience import faults, healing
from consensus_specs_tpu.resilience.checkpoint import (
    CheckpointCorrupt,
    CheckpointManager,
    env_every,
    manager_from_env,
)
from consensus_specs_tpu.telemetry import validate_checkpoint_block


@pytest.fixture(autouse=True)
def _no_leaked_plan():
    faults.clear()
    yield
    faults.clear()


def _forest(n=64, seed=17, limit_depth=8):
    rng = np.random.RandomState(seed)
    words = rng.randint(0, 2**32, (n, 8),
                        dtype=np.uint64).astype(np.uint32)
    return MerkleForest(words, limit_depth, n), words, rng


def _leaves(rng, m):
    return rng.randint(0, 2**32, (m, 8),
                       dtype=np.uint64).astype(np.uint32)


# --- snapshot / restore / journal replay -------------------------------------


def test_snapshot_restore_root_parity(tmp_path):
    forest, _, rng = _forest()
    mgr = CheckpointManager(tmp_path, name="t")
    forest.checkpoint = mgr
    mgr.snapshot(forest)
    want = forest.root_bytes()
    restored = mgr.restore()
    assert restored.root_bytes() == want
    assert restored.restored_journal_entries == 0
    assert restored.n_chunks == forest.n_chunks
    assert restored.limit_depth == forest.limit_depth
    # the restored stack serves proofs that verify against its root
    from consensus_specs_tpu.parallel import incremental

    proofs = restored.emit_proofs([0, 5, 63])
    assert all(incremental.verify_proof(p, want) for p in proofs)


def test_journal_replay_reproduces_live_root(tmp_path):
    forest, _, rng = _forest()
    mgr = CheckpointManager(tmp_path, name="t")
    forest.checkpoint = mgr
    mgr.snapshot(forest)
    for idx in ([1, 5, 9], [5], [0, 63]):
        forest.update(np.asarray(idx, dtype=np.uint32),
                      _leaves(rng, len(idx)))
    assert mgr.journal_entries == 3
    restored = mgr.restore()
    assert restored.restored_journal_entries == 3
    assert restored.root_bytes() == forest.root_bytes()


def test_restore_parity_vs_ssz_oracle(tmp_path):
    """The satellite contract verbatim: restore+replay reproduces the
    pure-Python SSZ oracle's `hash_tree_root` of the same
    `List[uint64, N]` value."""
    import jax.numpy as jnp

    from consensus_specs_tpu.parallel import incremental
    from consensus_specs_tpu.utils.ssz.ssz_impl import hash_tree_root
    from consensus_specs_tpu.utils.ssz.ssz_typing import List, uint64

    rng = np.random.RandomState(29)
    bal = rng.randint(0, 2**63, 100, dtype=np.uint64)
    forest = incremental.balances_forest(bal, 100, limit_depth=8)
    mgr = CheckpointManager(tmp_path, name="bal")
    forest.checkpoint = mgr
    mgr.snapshot(forest)
    dirty = np.asarray([2, 41, 97], dtype=np.uint32)
    bal[dirty] = rng.randint(0, 2**63, 3, dtype=np.uint64)
    chunks = incremental.dirty_chunks_from_validators(dirty)
    leaves = incremental.dirty_balance_leaves(jnp.asarray(bal), chunks)
    forest.update(chunks, leaves)
    oracle = bytes(hash_tree_root(List[uint64, 1024](
        *(int(b) for b in bal))))
    restored = mgr.restore()
    assert restored.root_bytes() == oracle == forest.root_bytes()


def test_snapshot_truncates_journal_and_bumps_seq(tmp_path):
    forest, _, rng = _forest()
    mgr = CheckpointManager(tmp_path, name="t")
    forest.checkpoint = mgr
    mgr.snapshot(forest)
    forest.update([3], _leaves(rng, 1))
    assert mgr.journal_path.read_text().strip()
    mgr.snapshot(forest)
    assert mgr.journal_path.read_text() == ""
    manifest = json.loads(mgr.manifest_path.read_text())
    assert manifest["seq"] == 2
    # a stale line from seq 1 left behind would be skipped on restore
    restored = mgr.restore()
    assert restored.root_bytes() == forest.root_bytes()


def test_stale_seq_journal_lines_are_skipped(tmp_path):
    forest, _, rng = _forest()
    mgr = CheckpointManager(tmp_path, name="t")
    forest.checkpoint = mgr
    mgr.snapshot(forest)
    forest.update([7], _leaves(rng, 1))
    stale = mgr.journal_path.read_text()
    mgr.snapshot(forest)                 # seq 2, journal truncated
    # resurrect the seq-1 line alongside a fresh seq-2 delta
    forest.update([9], _leaves(rng, 1))
    mgr.journal_path.write_text(stale + mgr.journal_path.read_text())
    restored = mgr.restore()
    assert restored.restored_journal_entries == 1   # only the seq-2 line
    assert restored.root_bytes() == forest.root_bytes()


def test_auto_snapshot_every(tmp_path):
    forest, _, rng = _forest()
    mgr = CheckpointManager(tmp_path, name="t", every=2)
    forest.checkpoint = mgr
    mgr.snapshot(forest)                 # seq 1
    for i in range(5):
        forest.update([i], _leaves(rng, 1))
    # updates 1,2 journal on seq 1; update 3 trips the auto-snapshot
    # (seq 2) then journals; updates 4,5 -> snapshot (seq 3) + journal
    manifest = json.loads(mgr.manifest_path.read_text())
    assert manifest["seq"] == 3
    restored = mgr.restore()
    assert restored.root_bytes() == forest.root_bytes()


def test_sentinel_padded_rows_are_not_journaled(tmp_path):
    """The flagship pre-pads dirty sets with the out-of-range sentinel;
    journal entries must carry only the live rows."""
    from consensus_specs_tpu.parallel.incremental import pad_dirty_idx

    forest, _, rng = _forest()
    mgr = CheckpointManager(tmp_path, name="t")
    forest.checkpoint = mgr
    mgr.snapshot(forest)
    idx = pad_dirty_idx(np.asarray([4, 8], dtype=np.uint32),
                        forest.capacity)
    leaves = np.zeros((idx.shape[0], 8), dtype=np.uint32)
    leaves[:2] = _leaves(rng, 2)
    forest.update(idx, leaves)
    entry = json.loads(mgr.journal_path.read_text().strip())
    assert entry["n"] == 2
    assert mgr.journal_chunks == 2
    restored = mgr.restore()
    assert restored.root_bytes() == forest.root_bytes()


# --- corruption rejection + fallback -----------------------------------------


def test_corrupted_snapshot_checksum_rejected(tmp_path):
    forest, _, rng = _forest()
    mgr = CheckpointManager(tmp_path, name="t")
    mgr.snapshot(forest)
    data = bytearray(mgr.layers_path.read_bytes())
    data[len(data) // 2] ^= 0xFF
    mgr.layers_path.write_bytes(bytes(data))
    with pytest.raises(CheckpointCorrupt):
        mgr.restore()
    assert mgr.restore_or_none() is None
    assert isinstance(mgr.last_error, CheckpointCorrupt)


def test_corrupted_journal_line_rejected(tmp_path):
    forest, _, rng = _forest()
    mgr = CheckpointManager(tmp_path, name="t")
    forest.checkpoint = mgr
    mgr.snapshot(forest)
    forest.update([3], _leaves(rng, 1))
    entry = json.loads(mgr.journal_path.read_text().strip())
    entry["length"] = entry["length"] + 1      # checksum no longer holds
    mgr.journal_path.write_text(json.dumps(entry) + "\n")
    with pytest.raises(CheckpointCorrupt):
        mgr.restore()
    # truncated / non-JSON journal is corrupt too, not a crash
    mgr.journal_path.write_text("{not json")
    assert mgr.restore_or_none() is None


def test_bad_manifest_format_rejected(tmp_path):
    forest, _, _ = _forest()
    mgr = CheckpointManager(tmp_path, name="t")
    mgr.snapshot(forest)
    manifest = json.loads(mgr.manifest_path.read_text())
    manifest["format"] = 99
    mgr.manifest_path.write_text(json.dumps(manifest))
    with pytest.raises(CheckpointCorrupt):
        mgr.restore()
    # a missing checkpoint is FileNotFoundError, mapped by _or_none
    empty = CheckpointManager(tmp_path / "nowhere", name="x")
    with pytest.raises(FileNotFoundError):
        empty.restore()
    assert empty.restore_or_none() is None


def test_corrupt_checkpoint_falls_back_to_full_rebuild(tmp_path):
    """The heal routing satellite: a diverged forest with a CORRUPT
    snapshot must recover through the rebuild floor — and record that
    path."""
    forest, _, rng = _forest()
    mgr = CheckpointManager(tmp_path, name="t")
    forest.checkpoint = mgr
    mgr.snapshot(forest)
    data = bytearray(mgr.layers_path.read_bytes())
    data[len(data) // 2] ^= 0xFF
    mgr.layers_path.write_bytes(bytes(data))
    faults.install("merkle_update:corrupt:count=1")
    forest.update([5], _leaves(rng, 1))
    faults.clear()
    assert healing.forest_diverged(forest)
    report = healing.heal_forest(forest)
    assert report.diverged and report.path == "rebuild"
    assert not healing.forest_diverged(forest)


def test_heal_routes_through_valid_checkpoint(tmp_path):
    forest, _, rng = _forest()
    mgr = CheckpointManager(tmp_path, name="t")
    forest.checkpoint = mgr
    mgr.snapshot(forest)
    faults.install("merkle_update:corrupt:count=1")
    forest.update([5], _leaves(rng, 1))
    faults.clear()
    report = healing.heal_forest(forest)
    assert report.diverged and report.path == "checkpoint"
    assert forest.root_bytes() == report.root
    assert forest.root_bytes() == healing._reference_root_bytes(forest)
    # clean forests stay path "none"
    assert healing.heal_forest(forest).path == "none"


def test_heal_with_authoritative_leaves_bypasses_checkpoint(tmp_path):
    """Authoritative `leaf_words` assert the persisted state — snapshot
    included — is suspect; recovery must NOT trust the checkpoint."""
    forest, words, rng = _forest()
    mgr = CheckpointManager(tmp_path, name="t")
    forest.checkpoint = mgr
    mgr.snapshot(forest)
    forest.update([3], np.full((1, 8), 0xBEEF, dtype=np.uint32))
    report = healing.heal_forest(forest, leaf_words=words)
    assert report.diverged and report.path == "rebuild"


# --- restore under concurrent updates ----------------------------------------


def test_restore_under_concurrent_update_is_safe(tmp_path):
    """Updates racing a restore never corrupt the files: the restore
    reads a consistent journal prefix, and a post-quiesce restore
    catches up to the final root."""
    forest, _, rng = _forest(n=128, limit_depth=9)
    mgr = CheckpointManager(tmp_path, name="t")
    forest.checkpoint = mgr
    mgr.snapshot(forest)
    stop = threading.Event()
    errors: list[BaseException] = []

    def writer():
        i = 0
        local = np.random.RandomState(99)
        try:
            while not stop.is_set() and i < 200:
                forest.update([i % 128], local.randint(
                    0, 2**32, (1, 8), dtype=np.uint64).astype(np.uint32))
                i += 1
        except BaseException as exc:    # pragma: no cover - fail signal
            errors.append(exc)

    th = threading.Thread(target=writer)
    th.start()
    try:
        for _ in range(5):
            restored = mgr.restore()    # consistent prefix, no raise
            assert restored.n_chunks == 128
    finally:
        stop.set()
        th.join(30)
    assert not errors, errors
    final = mgr.restore()
    assert final.root_bytes() == forest.root_bytes()


# --- knobs / env arming ------------------------------------------------------


def test_manager_from_env(tmp_path, monkeypatch):
    monkeypatch.delenv("CST_CHECKPOINT_DIR", raising=False)
    assert manager_from_env() is None
    monkeypatch.setenv("CST_CHECKPOINT_DIR", str(tmp_path))
    monkeypatch.setenv("CST_CHECKPOINT_EVERY", "7")
    mgr = manager_from_env(name="f")
    assert mgr is not None and mgr.every == 7
    assert mgr.dir == tmp_path
    monkeypatch.setenv("CST_CHECKPOINT_EVERY", "not-a-number")
    assert env_every() == 64


def test_existing_seq_resumes(tmp_path):
    forest, _, _ = _forest()
    mgr = CheckpointManager(tmp_path, name="t")
    mgr.snapshot(forest)
    mgr.snapshot(forest)
    fresh = CheckpointManager(tmp_path, name="t")
    assert fresh._existing_seq() == 2
    fresh.snapshot(forest)
    assert json.loads(fresh.manifest_path.read_text())["seq"] == 3


# --- the checkpoint block schema --------------------------------------------


def test_checkpoint_block_validation_and_records():
    block = {"n_chunks": 1 << 17, "journal_entries": 2,
             "journal_replayed": 2, "journal_frac": 0.0039,
             "snapshot_bytes": 8_500_000, "restore_s": 0.12,
             "rebuild_s": 1.3, "speedup": 10.8, "parity": True}
    assert validate_checkpoint_block(block) == []
    assert validate_checkpoint_block(None) == []
    assert validate_checkpoint_block({"parity": "yes"})
