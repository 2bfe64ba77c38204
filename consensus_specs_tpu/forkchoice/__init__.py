"""Fork-choice subsystem — device-batched LMD-GHOST on a proto-array
store.

The fourth heavy consensus workload on the device path (after state
transition, KZG/blob verification and PeerDAS cells): per-attestation
latest-message folding and head selection as flat-array segment
reductions.

    kernels   the `fc_rung` shape ladder + the three jitted kernels
              (latest-message/weight fold, full weight refresh,
              pointer-jumping head selection)
    store     `ProtoArrayStore` — device arrays + bit-equivalent host
              mirror, async facades through `serve.futures`
    oracle    the phase0 executable-spec referee (`spec_get_head` over
              a synthesized Store) — parity target and the serve
              executor's degraded-mode fallback
    bridge    executable-spec Store -> proto store projection (the
              fork-choice vector generator's seam)

Serving: `ServeExecutor.submit_attestation_batch` (queued batches fold
into ONE device dispatch per pump) and `submit_head_request` (the
`head` request kind); loadgen drives them at `CST_FC_ATTS_PER_SLOT`.
"""

from .kernels import (
    FC_BATCH_STEPS,
    FC_BLOCK_STEPS,
    FC_VALIDATOR_STEPS,
    fc_rung,
)
from .store import ProtoArrayStore

__all__ = [
    "FC_BATCH_STEPS",
    "FC_BLOCK_STEPS",
    "FC_VALIDATOR_STEPS",
    "ProtoArrayStore",
    "fc_rung",
]
