"""MerkleForest checkpoint/restore — snapshots + a leaf-delta journal.

A process restart loses every device-resident `MerkleForest` layer
stack, forcing an O(N) re-merkleize of the million-validator trees the
flagship depends on.  This module makes the forest durable the way
training stacks make optimizer state durable for elastic restart:

    snapshot    `CheckpointManager.snapshot(forest)` persists EVERY
                interior layer host-side — versioned (`FORMAT`,
                monotone `seq`) and checksummed (one sha256 over the
                concatenated layer bytes, stored in the manifest).
                Writes are tmp-file + `os.replace`, so a crash
                mid-snapshot leaves the previous checkpoint intact.
    journal     a leaf-delta journal appended at the `update_dirty`
                seam (`MerkleForest.update` calls `on_update` when a
                manager is attached): one JSON line per update —
                live dirty indices + leaf chunk words (base64), the
                list length, and a per-line sha256.  Snapshots
                truncate it (baked-in deltas).
    restore     load snapshot (checksum-verified) -> rebuild the layer
                stack with ZERO hashing (`MerkleForest.from_layers` is
                device puts only) -> replay the journal's dirty
                updates (O(journal · log N) hash lanes).  At <=1%
                journal depth this beats the full O(N) re-merkleize
                >=5x, measured by the chaos checkpoint segment.

Corruption policy: any checksum / format / truncation problem raises
the typed `CheckpointCorrupt`; `restore_or_none` maps it (and I/O
errors) to None so callers — `healing.heal_forest` above all — FALL
BACK TO A FULL REBUILD instead of serving from a damaged checkpoint.

Concurrency contract: journal appends and snapshots serialize on one
re-entrant lock; `restore()` reads a consistent journal prefix under
that lock and replays it outside — an update arriving mid-restore is
safe (never corrupts the files) and lands in the journal for the NEXT
restore.  Pinned by tests/test_checkpoint.py.

Knobs: `CST_CHECKPOINT_DIR` (arming: a directory makes
`manager_from_env` return a live manager), `CST_CHECKPOINT_EVERY`
(auto-snapshot after that many journaled updates; 0 disables
auto-snapshots).  See README "Mesh resilience & checkpointing" and
tests/formats/README.md for the file format.

numpy loads lazily inside the methods (importing the resilience
package must stay stdlib-only); jax enters only through
`MerkleForest.from_layers` at restore time.
"""

from __future__ import annotations

import base64
import hashlib
import json
import os
import threading
import time
import zipfile
from pathlib import Path

from .. import telemetry
from ..telemetry import flightrec

FORMAT = 1


class CheckpointCorrupt(RuntimeError):
    """A snapshot or journal line failed its checksum/format check.
    Typed so restore callers can fall back to a full rebuild instead of
    serving from damaged state."""


def env_dir() -> str | None:
    """The CST_CHECKPOINT_DIR knob (None == checkpointing disarmed)."""
    return os.environ.get("CST_CHECKPOINT_DIR") or None


def env_every(default: int = 64) -> int:
    """The CST_CHECKPOINT_EVERY knob: auto-snapshot cadence in journaled
    updates (0 disables auto-snapshots)."""
    try:
        return int(os.environ.get("CST_CHECKPOINT_EVERY", default))
    except ValueError:
        return default


def manager_from_env(name: str = "forest") -> "CheckpointManager | None":
    """A live manager when CST_CHECKPOINT_DIR is set, else None — the
    one arming read call sites guard with."""
    d = env_dir()
    if not d:
        return None
    return CheckpointManager(d, name=name, every=env_every())


def _line_digest(idx_bytes: bytes, leaf_bytes: bytes, length: int) -> str:
    h = hashlib.sha256()
    h.update(idx_bytes)
    h.update(leaf_bytes)
    h.update(str(int(length)).encode())
    return h.hexdigest()


class CheckpointManager:
    """One forest's checkpoint state under `directory` (see module
    docstring).  `every=None/0` disables auto-snapshots; `name` keys
    the three files so several forests can share a directory."""

    def __init__(self, directory, name: str = "forest",
                 every: int | None = None):
        self.dir = Path(directory)
        self.name = name
        self.every = int(every) if every else 0
        self._lock = threading.RLock()
        self.journal_entries = 0
        self.journal_chunks = 0
        self.snapshot_bytes = 0
        self.last_error: BaseException | None = None
        self._updates_since_snapshot = 0
        self._seq = self._existing_seq()

    # --- paths ---------------------------------------------------------------

    @property
    def manifest_path(self) -> Path:
        return self.dir / f"{self.name}.manifest.json"

    @property
    def layers_path(self) -> Path:
        return self.dir / f"{self.name}.layers.npz"

    @property
    def journal_path(self) -> Path:
        return self.dir / f"{self.name}.journal.jsonl"

    def _existing_seq(self) -> int:
        try:
            manifest = json.loads(self.manifest_path.read_text())
            return int(manifest.get("seq", 0))
        except (OSError, json.JSONDecodeError, ValueError, TypeError):
            return 0

    # --- snapshot ------------------------------------------------------------

    def snapshot(self, forest) -> Path:
        """Persist the forest's full layer stack (versioned, checksummed,
        atomic) and truncate the journal.  Returns the manifest path."""
        import numpy as np

        with self._lock, telemetry.span("resilience.checkpoint.snapshot",
                                        chunks=forest.n_chunks):
            self.dir.mkdir(parents=True, exist_ok=True)
            host_layers = [np.asarray(lay, dtype=np.uint32)
                           for lay in forest.layers]
            digest = hashlib.sha256()
            for lay in host_layers:
                digest.update(lay.tobytes())
            tmp = self.layers_path.with_name(self.layers_path.name + ".tmp")
            with open(tmp, "wb") as f:
                np.savez(f, **{f"layer_{i}": lay
                               for i, lay in enumerate(host_layers)})
            os.replace(tmp, self.layers_path)
            seq = self._seq + 1
            manifest = {
                "format": FORMAT,
                "seq": seq,
                "n_chunks": int(forest.n_chunks),
                "data_depth": int(forest.data_depth),
                "limit_depth": int(forest.limit_depth),
                "length": int(forest.length),
                "sha256": digest.hexdigest(),
                "layers_file": self.layers_path.name,
                "created_at": round(time.time(), 3),
            }
            mtmp = self.manifest_path.with_name(
                self.manifest_path.name + ".tmp")
            mtmp.write_text(json.dumps(manifest, sort_keys=True))
            os.replace(mtmp, self.manifest_path)
            # journal entries predate this snapshot: baked in, truncate
            # — and the counters mean PENDING (replayable) depth, so
            # they reset with the file (journal_depth_frac must report
            # what a restore would replay, not lifetime totals)
            with open(self.journal_path, "w"):
                pass
            self.journal_entries = 0
            self.journal_chunks = 0
            self._seq = seq
            self._updates_since_snapshot = 0
            self.snapshot_bytes = self.layers_path.stat().st_size
            telemetry.count("checkpoint.snapshots")
            flightrec.record("checkpoint_snapshot", seq=seq,
                             n_chunks=int(forest.n_chunks),
                             bytes=int(self.snapshot_bytes))
        return self.manifest_path

    # --- journal (the update_dirty seam's hook) ------------------------------

    def on_update(self, forest, dirty_idx, new_leaf_words) -> None:
        """Journal one dirty-set update (live rows only — sentinel-pad
        rows beyond the forest's capacity are dropped).  Called by
        `MerkleForest.update` while a manager is attached; materializes
        the leaf words host-side (the one sync checkpointing costs —
        opt-in by construction)."""
        import numpy as np

        idx = np.asarray(dirty_idx, dtype=np.uint32)
        leaves = np.asarray(new_leaf_words, dtype=np.uint32)
        m = min(idx.shape[0], leaves.shape[0])
        idx, leaves = idx[:m], leaves[:m]
        live = idx < forest.capacity
        idx, leaves = idx[live], leaves[live]
        if idx.shape[0] == 0:
            return
        with self._lock:
            if self.every and self._updates_since_snapshot >= self.every:
                # pre-update snapshot, so this delta lands in the fresh
                # journal and replay stays exact
                self.snapshot(forest)
            idx_b, leaf_b = idx.tobytes(), leaves.tobytes()
            entry = {
                "seq": self._seq,
                "n": int(idx.shape[0]),
                "idx": base64.b64encode(idx_b).decode(),
                "leaves": base64.b64encode(leaf_b).decode(),
                "length": int(forest.length),
                "sha256": _line_digest(idx_b, leaf_b, forest.length),
            }
            self.dir.mkdir(parents=True, exist_ok=True)
            with open(self.journal_path, "a") as f:
                f.write(json.dumps(entry, sort_keys=True) + "\n")
            self.journal_entries += 1
            self.journal_chunks += int(idx.shape[0])
            self._updates_since_snapshot += 1
            telemetry.count("checkpoint.journal_appends")

    def journal_depth_frac(self, n_chunks: int) -> float:
        """Journaled chunk rows as a fraction of the tree width — the
        <=1% regime the restore-speedup threshold is stated at."""
        return self.journal_chunks / max(1, int(n_chunks))

    # --- restore -------------------------------------------------------------

    def _read_journal_lines(self) -> list[str]:
        with self._lock:
            try:
                return self.journal_path.read_text().splitlines()
            except OSError:
                return []

    def restore(self):
        """Snapshot + journal replay -> a fresh `MerkleForest` (no full
        re-merkleize: layer puts + O(journal · log N) dirty re-hash).
        Raises `CheckpointCorrupt` on any checksum/format problem and
        `FileNotFoundError` when no snapshot exists."""
        import numpy as np

        from ..parallel.incremental import MerkleForest

        with telemetry.span("resilience.checkpoint.restore"):
            # manifest + layers + journal are read as ONE locked unit:
            # a concurrent snapshot() (same lock) rewrites all three,
            # and unsynchronized reads could checksum seq-N+1 layer
            # bytes against the seq-N manifest — a spurious corrupt
            # verdict that would force an unnecessary O(N) rebuild.
            # The replay itself (device work) runs outside the lock.
            with self._lock:
                try:
                    manifest = json.loads(self.manifest_path.read_text())
                except json.JSONDecodeError as exc:
                    raise CheckpointCorrupt(
                        f"unreadable manifest: {exc}") from exc
                if not isinstance(manifest, dict) \
                        or manifest.get("format") != FORMAT:
                    raise CheckpointCorrupt(
                        f"manifest format {manifest.get('format')!r} != "
                        f"{FORMAT}")
                depth = int(manifest["data_depth"])
                digest = hashlib.sha256()
                try:
                    with np.load(self.layers_path) as z:
                        layers = [np.asarray(z[f"layer_{i}"],
                                             dtype=np.uint32)
                                  for i in range(depth + 1)]
                except (OSError, KeyError, ValueError, EOFError,
                        zipfile.BadZipFile) as exc:
                    # a damaged npz surfaces as BadZipFile/EOFError
                    # before the sha256 even runs — same corrupt verdict
                    raise CheckpointCorrupt(
                        f"unreadable layer archive: {exc}") from exc
                for lay in layers:
                    digest.update(lay.tobytes())
                if digest.hexdigest() != manifest.get("sha256"):
                    raise CheckpointCorrupt(
                        "layer-stack checksum mismatch — snapshot is "
                        "corrupt, fall back to a full rebuild")
                lines = self._read_journal_lines()
            forest = MerkleForest.from_layers(
                layers, manifest["limit_depth"], manifest["length"],
                manifest["n_chunks"])
            replayed = self._replay(forest, lines, int(manifest["seq"]))
            telemetry.count("checkpoint.restores")
            flightrec.record("checkpoint_restore",
                             seq=int(manifest["seq"]),
                             replayed_entries=int(replayed))
            forest.restored_journal_entries = replayed
            return forest

    def _replay(self, forest, lines: list[str], seq: int) -> int:
        import numpy as np

        replayed = 0
        for i, line in enumerate(lines, 1):
            line = line.strip()
            if not line:
                continue
            try:
                entry = json.loads(line)
            except json.JSONDecodeError as exc:
                raise CheckpointCorrupt(
                    f"journal line {i} is not JSON") from exc
            if not isinstance(entry, dict):
                raise CheckpointCorrupt(f"journal line {i}: not a dict")
            if entry.get("seq") != seq:
                continue            # stale: predates the loaded snapshot
            try:
                idx_b = base64.b64decode(entry["idx"])
                leaf_b = base64.b64decode(entry["leaves"])
                length = int(entry["length"])
            except (KeyError, ValueError, TypeError) as exc:
                raise CheckpointCorrupt(
                    f"journal line {i}: malformed fields") from exc
            if _line_digest(idx_b, leaf_b, length) != entry.get("sha256"):
                raise CheckpointCorrupt(
                    f"journal line {i}: checksum mismatch")
            idx = np.frombuffer(idx_b, dtype=np.uint32)
            leaves = np.frombuffer(leaf_b,
                                   dtype=np.uint32).reshape(-1, 8)
            if leaves.shape[0] != idx.shape[0]:
                raise CheckpointCorrupt(
                    f"journal line {i}: {idx.shape[0]} indices vs "
                    f"{leaves.shape[0]} leaf rows")
            forest.length = length
            forest.update(idx, leaves)
            replayed += 1
        return replayed

    def restore_or_none(self):
        """`restore()`, with the fallback contract folded in: a missing,
        corrupt, or unreadable checkpoint returns None (and records the
        reason in `last_error`) so the caller rebuilds instead."""
        try:
            return self.restore()
        except (CheckpointCorrupt, OSError, ValueError, KeyError,
                TypeError) as exc:
            self.last_error = exc
            telemetry.count("checkpoint.restore_rejected")
            return None

    # --- accounting ----------------------------------------------------------

    def describe(self) -> dict:
        """Compact JSON-able summary (rides the resilience block)."""
        return {
            "dir": str(self.dir),
            "seq": self._seq,
            "journal_entries": self.journal_entries,
            "journal_chunks": self.journal_chunks,
            "snapshot_bytes": self.snapshot_bytes,
        }
