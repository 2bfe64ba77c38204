"""Resilience layer — deterministic fault injection, recovery policies,
self-healing Merkle state.

The serving stack (PR 6) poisons the handles of a failed batch and the
incremental flagship (PR 7) asserts Merkle parity — but nothing HEALS:
one failed compile, a poisoned-batch storm, or a silently diverged
`MerkleForest` has no recovery story.  Production committee-consensus
measurement work (arXiv:2302.00418) and censorship-resilient
million-scale aggregation (Wonderboom, arXiv:2602.06655) both treat
verification as a service that must keep answering *correctly* under
partial failure; this package gives the repo that property and the
machinery to prove it:

    faults.py    deterministic, seeded, schema-validated fault injection
                 at the four sanctioned seams (`ops.bls_batch._dispatch`,
                 `serve.futures.DeviceFuture` settle,
                 `ServeExecutor._dispatch_one`, `incremental.update_dirty`)
                 — dispatch exceptions, injected latency, compile failure
                 on first call, corrupted device output (bit-flip/NaN),
                 mesh-device loss.  OFF by default; the disabled path is
                 one module-global read (no-op bound pinned by
                 tests/test_resilience.py, the telemetry pattern).
    policies.py  per-kernel retry with capped exponential backoff, a
                 per-(kernel, rung) circuit breaker that trips to the
                 pure-Python oracle fallback (correct-but-slow degraded
                 mode, half-open probes to re-close), and typed
                 `DeadlineExceeded` request shedding.
    healing.py   divergence detector + quarantine/recovery for a
                 `parallel.incremental.MerkleForest` (recovery latency
                 recorded); recovery routes through checkpoint restore
                 when a valid snapshot exists, else a full rebuild —
                 the taken path rides the `heal` block.
    mesh.py      per-shard recovery for `batch_verify_sharded`: a lost
                 mesh device (`MeshDeviceLost` or a real
                 XlaRuntimeError) re-buckets the SAME statements over
                 the surviving devices (degraded n-1 mode), with a
                 half-open re-admission probe once the device answers
                 again — zero wrong or dropped statements.
    checkpoint.py versioned, checksummed host-side snapshots of
                 `MerkleForest` layer stacks plus a leaf-delta journal
                 appended at the `update_dirty` seam; restore = load
                 snapshot + replay journal instead of the O(N)
                 re-merkleize (`CST_CHECKPOINT_DIR` /
                 `CST_CHECKPOINT_EVERY`).
    chaos.py     the chaos-round harness (`CST_SERVE_CHAOS=1`): mainnet
                 arrival mix under an active fault plan, requiring the
                 service to return to steady state — returns the
                 `resilience` block.

Import discipline: `faults`, `policies`, `mesh` and `checkpoint` are
stdlib-only at import (+ telemetry, itself stdlib-only) so the hot-path
seams can import them eagerly without touching numpy/jax; `healing` and
`chaos` import the heavy modules lazily, at call time.
"""

from . import checkpoint, faults, mesh
from .checkpoint import CheckpointCorrupt, CheckpointManager
from .faults import FaultInjected, FaultPlan, MeshDeviceLost
from .mesh import MeshState, MeshVerifier
from .policies import (
    BreakerRegistry,
    CircuitBreaker,
    DeadlineExceeded,
    RetryPolicy,
)

__all__ = [
    "BreakerRegistry", "CheckpointCorrupt", "CheckpointManager",
    "CircuitBreaker", "DeadlineExceeded", "FaultInjected", "FaultPlan",
    "MeshDeviceLost", "MeshState", "MeshVerifier", "RetryPolicy",
    "checkpoint", "faults", "mesh",
]
