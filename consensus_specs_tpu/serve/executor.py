"""Batching serve executor — queue → topological device batches → settle.

`ServeExecutor` is the serving counterpart of the block executor's
`DeferredBatch`: requests (`submit_*`) enqueue and immediately return a
`DeviceFuture`; `pump()` drains the queue into device batches on the
`_bucket` shape ladder (so sustained traffic reuses the same AOT-warmed
executables instead of compiling per batch size) and settles futures in
arrival order WITHIN each request kind (batches themselves dispatch in
fixed `KINDS` order per pump, so cross-kind ordering is not preserved).

Pipelining contract (the "double-buffered host→device transfer"): the
executor keeps up to `depth` dispatched batches in flight and settles
the oldest only once newer work has been dispatched — so the host-side
prep of batch N+1 (point→limb conversion, RLC coefficient draws,
transfers) overlaps the device execution of batch N, and a `result()`
on any handle finds the answer already materializing instead of
stalling a cold pipeline.  `drain()` settles everything.

Request kinds and their device paths:

    verify     FastAggregateVerify-style statements, BATCHED: up to
               `max_batch` statements per RLC dispatch
               (`bls_batch.batch_verify_async`).  A batch verdict of
               True settles every statement True; False triggers a
               per-statement recheck (`pairing_check_device`) so each
               handle gets its own verdict — all-or-nothing is a block
               semantics, not a serving one.
    committee  FastAggregateVerify of a committee aggregate whose keys
               the node holds: the executor's `registry`
               (`bls_batch.registry.PubkeyRegistry`) names the members,
               the aggregation bits select them.  Batched like verify;
               each batch dispatches the committee aggregation program,
               then the RLC program on its output.  A false batch reads
               its aggregate keys back for the per-statement recheck.
    pairing    one pairing-product check (`pairing_check_device_async`)
    msm        one G1 MSM (`g1_multi_exp_device_async`)
    sha256     one Merkle-root reduction (`merkleize_words_jax_async`)
    fr         one barycentric evaluation (`barycentric_eval_async`)
    proof      one batched SSZ single-proof emission from a persistent
               `parallel.incremental.MerkleForest`
               (`incremental.emit_proofs_async`) — the stateless-client
               proof-serving workload riding the same futures pipeline
    das        data-column sampling checks, CROSS-SAMPLE BATCHED: every
               sample queued at pump time folds into ONE RLC pairing
               equation (`das.sampling.verify_sample_group_async` —
               host inclusion walks per sample, then all the samples'
               cell statements as a single device batch; a failed batch
               verdict rechecks per sample, so each request keeps its
               own answer)
    fc_atts    fork-choice attestation batches (`forkchoice
               .ProtoArrayStore.apply_attestations_async`): every batch
               queued at pump time for the same store folds into ONE
               latest-message/weight-delta dispatch; each request
               settles to ITS OWN accepted count (the device accept
               mask is split per request).  Idempotent under retry —
               the strictly-greater epoch rule makes re-applying a
               batch a no-op.
    head       one LMD-GHOST head poll (`ProtoArrayStore
               .get_head_async`); settles to the head's 32-byte root.
               The breaker's degraded mode answers on the actual phase0
               spec oracle (`get_head_host`), and degraded-mode
               `fc_atts` applies land on the store's host mirror, from
               which the device arrays rebuild when the breaker
               re-closes.

Failure semantics are LAYERED (PR 8, the resilience layer):

- Base contract (always on): a device batch that RAISES settles the
  exception into every pending handle of that batch — and ONLY that
  batch — and the executor keeps serving.  One poisoned batch must not
  take the service down.
- `retry=RetryPolicy(...)`: a failed batch re-dispatches with capped
  exponential backoff before the failure is final.
- `breakers=BreakerRegistry(...)`: consecutive failures per
  (kind, rung) trip a circuit breaker; while OPEN, matching batches
  route to the PURE-PYTHON ORACLE fallback (`_oracle_compute` —
  bit-identical results, orders of magnitude slower: the degraded mode
  that keeps answers correct while the device path is sick), and
  half-open probes re-close the breaker once the device recovers.
  Kinds without an oracle (`proof`) keep trying the device.
- `deadline_ms` (default `CST_SERVE_DEADLINE_MS`): queued requests
  older than the deadline are shed at the next pump with a typed
  `resilience.DeadlineExceeded` — oldest first, so overload degrades
  into explicit failures instead of unbounded queue growth.
- `mesh=MeshVerifier(...)` (PR 9): verify batches dispatch sharded
  over the device mesh with per-shard loss recovery — a dead device
  re-buckets the batch over the survivors inside the mesh layer, so
  the retry/breaker ladder here only sees failures the mesh could not
  absorb (`resilience.mesh`; its counters ride `stats()["mesh"]`).

Fault injection (`resilience.faults`, OFF by default): the
`serve_pump` seam fires inside `_dispatch_one`'s try block, so an
injected fault has exactly a real host-prep failure's blast radius.

Telemetry (env-gated like everything else): `serve.queue_depth` and
`serve.inflight_batches` gauges (exported as Chrome-trace counter
tracks next to the device-memory ones), spans per pump/settle, and
submitted/settled/failed/recheck/retry/fallback/shed counters.
Queue-depth and latency accounting for the bench contract is kept
independently in plain members (`stats()`, `latencies_s`) so the serve
block never depends on CST_TELEMETRY.
"""

from __future__ import annotations

import json
import os
import sys
import time
from collections import deque

from .. import telemetry
from ..resilience import faults
from ..resilience.policies import DeadlineExceeded
from ..telemetry import flightrec, occupancy, reqtrace
from .futures import DeviceFuture, FutureTimeout

KINDS = ("verify", "pairing", "msm", "sha256", "fr", "proof", "das",
         "recover", "fc_atts", "head", "committee")
# kinds whose requests share one device batch, up to max_batch each
_BATCHED_KINDS = ("verify", "das", "committee")

# batched-kind dispatchers resolve lazily: importing the executor must
# not pull jax/numpy-heavy ops modules until the first dispatch


def _ops_bls_batch():
    from ..ops import bls_batch
    return bls_batch


class _Request:
    __slots__ = ("kind", "payload", "future", "t_enqueue", "ctx")

    def __init__(self, kind, payload, future, ctx=None):
        self.kind = kind
        self.payload = payload
        self.future = future
        self.ctx = ctx          # reqtrace.RequestContext (None when off)
        self.t_enqueue = time.perf_counter()


class _Batch:
    __slots__ = ("kind", "future", "reqs", "t_dispatch", "attempt",
                 "occ", "keys")

    def __init__(self, kind, future, reqs, attempt=1, occ=None, keys=None):
        self.kind = kind
        self.future = future
        self.reqs = reqs
        self.t_dispatch = time.perf_counter()
        self.attempt = attempt
        self.occ = occ          # occupancy.BatchSpan (None when off)
        self.keys = keys        # registry.CommitteeKeys (committee kind)


def _depth_bucket(n: int) -> str:
    """Histogram label: 0 or the next power of two (1, 2, 4, 8, ...)."""
    return "0" if n <= 0 else str(1 << (n - 1).bit_length())


def _breaker_key(kind: str, n: int) -> str:
    """Per-(kind, rung) breaker key: a verify batch of 100 and one of
    128 share executables (the `_bucket` ladder) and share health."""
    return f"{kind}@{_depth_bucket(n)}"


# --- pure-Python oracle fallback (degraded mode) -----------------------------
#
# One oracle per kind that has one; results are BIT-IDENTICAL to the
# device path (pinned by tests/test_resilience.py), just slow.  The
# verify oracle memoizes on the statement's canonical serialization:
# sustained traffic cycles a finite statement pool, so a tripped
# breaker costs one pure-Python pairing check per DISTINCT statement,
# not per request.

_ORACLE_VERIFY_CACHE: dict = {}
_ORACLE_VERIFY_CACHE_MAX = 4096


def _oracle_verify(task) -> bool:
    from ..ops.bls.ciphersuite import _pairing_check, fast_aggregate_pairs
    from ..ops.bls.curve import g1_to_bytes, g2_to_bytes

    pk, msg, sig = task
    try:
        key = (g1_to_bytes(pk), bytes(msg), g2_to_bytes(sig))
    except (TypeError, ValueError):
        key = None      # unserializable point: verify uncached
    if key is not None and key in _ORACLE_VERIFY_CACHE:
        telemetry.count("resilience.fallback.verify_cache_hit")
        return _ORACLE_VERIFY_CACHE[key]
    ok = _pairing_check(fast_aggregate_pairs(task))
    if key is not None:
        if len(_ORACLE_VERIFY_CACHE) >= _ORACLE_VERIFY_CACHE_MAX:
            _ORACLE_VERIFY_CACHE.clear()
        _ORACLE_VERIFY_CACHE[key] = ok
    return ok


def _oracle_committee(payload) -> bool:
    """The committee statement on the oracle: the pure-Python sum of the
    registry's host mirror, then FastAggregateVerify of the aggregate."""
    from ..ops.bls.ciphersuite import _pairing_check, fast_aggregate_pairs
    from ..ops.bls.curve import g1

    registry, committee_id, bits, msg, sig = payload
    agg = registry.host_aggregate(committee_id, bits)
    if g1.is_inf(agg):
        return False            # KeyValidate of the aggregate
    return _pairing_check(fast_aggregate_pairs((agg, msg, sig)))


def _oracle_barycentric(poly_ints, roots_brp_ints, z_int) -> int:
    """The closed-form host evaluation `fr_batch` mirrors: f(z) =
    (z^W - 1)/W * sum_i f_i * w_i / (z - w_i) mod r, with the in-domain
    short-circuit."""
    from ..ops.fr_batch import R_MODULUS as r

    width = len(poly_ints)
    z = int(z_int) % r
    roots = [int(w) % r for w in roots_brp_ints]
    poly = [int(f) % r for f in poly_ints]
    for f, w in zip(poly, roots):
        if (z - w) % r == 0:
            return f
    total = 0
    for f, w in zip(poly, roots):
        total = (total + f * w % r * pow((z - w) % r, r - 2, r)) % r
    factor = (pow(z, width, r) - 1) % r
    inv_width = pow(width, r - 2, r)
    return total * factor % r * inv_width % r


def _oracle_compute(kind: str, payload):
    """Dispatch one request on the pure-Python oracle.  Raises KeyError
    for kinds without an oracle (`proof`)."""
    if kind == "verify":
        return _oracle_verify(payload)
    if kind == "committee":
        return _oracle_committee(payload)
    if kind == "pairing":
        from ..ops.bls.ciphersuite import _pairing_check

        return _pairing_check(payload)
    if kind == "sha256":
        import numpy as np

        from ..ops.sha256_np import merkleize_words

        words, limit_depth = payload
        return merkleize_words(np.asarray(words, dtype=np.uint32),
                               limit_depth)
    if kind == "fr":
        return _oracle_barycentric(*payload)
    if kind == "msm":
        from ..ops.bls import curve as pycurve

        points, scalars = payload
        acc = pycurve.g1.infinity()
        for p, s in zip(points, scalars):
            acc = pycurve.g1.add(acc, pycurve.g1.mul(p, int(s)
                                                     % pycurve.R))
        return acc
    if kind == "das":
        from ..das.sampling import verify_sample_host

        return verify_sample_host(payload)
    if kind == "recover":
        from ..das.recover import recover_cells_and_kzg_proofs_host

        return recover_cells_and_kzg_proofs_host(*payload)
    if kind == "fc_atts":
        # host-mirror fold (the exact kernel rule); the store rebuilds
        # its device arrays from the mirror when the breaker re-closes
        store, idx, epochs, roots = payload
        return store.apply_attestations_host(idx, epochs, roots)
    if kind == "head":
        # the actual phase0 spec oracle's get_head over the mirror
        return payload.get_head_host()
    raise KeyError(f"no oracle fallback for request kind {kind!r}")


ORACLE_KINDS = frozenset({"verify", "pairing", "msm", "sha256", "fr",
                          "das", "recover", "fc_atts", "head", "committee"})


class ServeExecutor:
    """See the module docstring.  `max_batch` caps statements per RLC
    dispatch (a `_bucket` ladder rung keeps executables shared);
    `depth` is the number of in-flight batches the pipeline holds
    before settling the oldest.  `retry`/`breakers`/`deadline_ms` arm
    the resilience policies (all off by default; `deadline_ms` falls
    back to the CST_SERVE_DEADLINE_MS knob).  `registry` (a
    `bls_batch.registry.PubkeyRegistry`) is the key cache committee
    submits name their keys in."""

    def __init__(self, max_batch: int = 512, depth: int = 2,
                 retry=None, breakers=None,
                 deadline_ms: float | None = None, mesh=None,
                 registry=None):
        assert max_batch >= 1 and depth >= 1
        self.max_batch = max_batch
        self.depth = depth
        self.registry = registry
        self.retry = retry
        self.breakers = breakers
        # a resilience.mesh.MeshVerifier: verify batches dispatch over
        # the device mesh with the per-shard recovery ladder (a lost
        # device re-buckets the batch over the survivors before the
        # retry/breaker ladder here ever sees a failure)
        self.mesh = mesh
        if deadline_ms is None:
            try:
                deadline_ms = float(
                    os.environ.get("CST_SERVE_DEADLINE_MS", "0")) or None
            except ValueError:
                deadline_ms = None
        self.deadline_s = deadline_ms / 1e3 if deadline_ms else None
        self._queue: deque[_Request] = deque()
        self._inflight: deque[_Batch] = deque()
        self.latencies_s: list[float] = []
        self._submitted = 0
        self._settled = 0
        self._failed = 0
        self._rechecks = 0
        self._dispatched_batches = 0
        self._retries = 0
        self._fallbacks = 0
        self._shed = 0
        self._keys_aggregated = 0
        self._poisoned_batches = 0
        self._poison_dumped = False
        self._queue_hist: dict[str, int] = {}
        self._queue_max = 0
        self._inflight_max = 0
        self._t_start = time.perf_counter()
        # live ops snapshot: CST_SERVE_STATUS_EVERY seconds > 0 dumps
        # status() as one JSON line on stderr from inside pump(), so a
        # sustained round is observable while it runs (on-demand reads
        # call status() directly)
        try:
            self._status_every = float(
                os.environ.get("CST_SERVE_STATUS_EVERY", "0") or 0)
        except ValueError:
            self._status_every = 0.0
        self._status_last = time.perf_counter()

    # --- submission ---------------------------------------------------------

    def _submit(self, kind: str, payload) -> DeviceFuture:
        assert kind in KINDS, kind
        ctx = reqtrace.mint(kind)
        fut = DeviceFuture(waiter=self._settle_until)
        if ctx is not None:
            fut.ctx = ctx       # the context rides the handle too
            ctx.mark_enqueue()
        self._queue.append(_Request(kind, payload, fut, ctx))
        self._submitted += 1
        telemetry.count("serve.submitted")
        self._note_queue_depth()
        return fut

    def submit_verify_task(self, task) -> DeviceFuture:
        """One pre-parsed FastAggregateVerify statement
        (g1_pubkey_jacobian, message_bytes, g2_sig_jacobian) — the
        `batch_verify` task shape.  Returns a bool handle."""
        return self._submit("verify", task)

    def submit_fast_aggregate_verify(self, pubkeys, message,
                                     signature) -> DeviceFuture:
        """Wire-format FastAggregateVerify: inputs validate eagerly
        (same boundary as `DeferredBatch.record`), the pairing defers.
        Invalid inputs settle False immediately."""
        from ..ops.bls.ciphersuite import parse_fast_aggregate_task

        with telemetry.span("serve.parse"):
            task = parse_fast_aggregate_task(pubkeys, message, signature)
        if task is None:
            telemetry.count("serve.rejected_eager")
            return DeviceFuture.settled(False)
        return self.submit_verify_task(task)

    def submit_committee_aggregate_verify(self, slot: int,
                                          committee_index: int,
                                          aggregation_bits, message,
                                          signature) -> DeviceFuture:
        """FastAggregateVerify of one committee aggregate against the
        executor's registry: `aggregation_bits` (SSZ `Bitlist` bytes)
        select the members of committee `committee_index` at `slot`.
        Only the signature is parsed (decompression and the G2 subgroup
        check); the keys are the registry's.  An unknown committee, bits
        of another length than the committee's, no bit set, or a bad
        signature settle False at once."""
        from ..ops.bls.ciphersuite import _sig_to_point
        from ..ops.bls.curve import g2
        from ..ops.bls_batch.registry import decode_bitlist

        if self.registry is None:
            raise ValueError("committee submits need a registry")
        with telemetry.span("serve.parse"):
            committee_id = self.registry.committee_id(slot, committee_index)
            bits = decode_bitlist(aggregation_bits, self.registry.size)
            try:
                sig = _sig_to_point(bytes(signature))
            except ValueError:
                sig = None
        if committee_id is None or bits is None or not bits.any() \
                or sig is None or g2.is_inf(sig):
            telemetry.count("serve.rejected_eager")
            return DeviceFuture.settled(False)
        return self._submit("committee", (self.registry, committee_id, bits,
                                          bytes(message), sig))

    def submit_pairing(self, pairs) -> DeviceFuture:
        """One product-of-pairings check (sync-aggregate shape)."""
        return self._submit("pairing", pairs)

    def submit_msm(self, points, scalars) -> DeviceFuture:
        """One G1 multiscalar multiplication; settles to an oracle
        Jacobian point."""
        return self._submit("msm", (points, scalars))

    def submit_sha256_root(self, words, limit_depth: int) -> DeviceFuture:
        """One Merkle-root reduction; settles to (8,) uint32 words."""
        return self._submit("sha256", (words, limit_depth))

    def submit_barycentric(self, poly_ints, roots_brp_ints,
                           z_int) -> DeviceFuture:
        """One evaluation-form polynomial evaluation; settles to int."""
        return self._submit("fr", (poly_ints, roots_brp_ints, z_int))

    def submit_proof_request(self, forest, indices) -> DeviceFuture:
        """Batched SSZ single-proof emission from a persistent
        `parallel.incremental.MerkleForest` (the stateless-client
        serving workload): one bucketed sibling-path gather rides the
        pipeline; settles to `list[SSZProof]`.  Out-of-range indices
        fail eagerly at dispatch and poison only their own handle."""
        return self._submit("proof", (forest, list(indices)))

    def submit_das_sample(self, sample) -> DeviceFuture:
        """One data-column sampling check (`das.sampling.DasSample`):
        host inclusion walk, then the cell proofs ride the pump's
        cross-sample RLC batch (every das sample queued at pump time
        folds into ONE device dispatch).  Settles to bool; a
        structurally broken or inclusion-failing sample settles False
        without touching the device."""
        return self._submit("das", sample)

    def submit_recover_request(self, cell_indices, cells) -> DeviceFuture:
        """One damaged-blob reconstruction (the super-node lane): >= 64
        surviving cells in, ALL 128 cells + FK20 proofs out — the
        device coset decode + re-prove (`das.recover`).  Settles to
        (cells, proofs); malformed input (too few cells, duplicates,
        bad sizes) fails at dispatch and poisons only its own handle.
        The breaker's degraded route is the pure-Python spec oracle."""
        return self._submit("recover", (list(cell_indices),
                                        [bytes(c) for c in cells]))

    def submit_attestation_batch(self, store, validator_indices,
                                 target_epochs,
                                 block_roots) -> DeviceFuture:
        """One fork-choice attestation batch against a
        `forkchoice.ProtoArrayStore` (validator index, target epoch,
        vote-block root per message — the post-verification facts the
        fork choice consumes; signature checking is the `verify`
        lane's job).  Batches queued for the same store fold into ONE
        device dispatch per pump; settles to this request's accepted
        latest-message count."""
        n = len(validator_indices)
        assert n == len(target_epochs) == len(block_roots)
        return self._submit("fc_atts", (store, list(validator_indices),
                                        list(target_epochs),
                                        list(block_roots)))

    def submit_head_request(self, store) -> DeviceFuture:
        """One LMD-GHOST head poll against a
        `forkchoice.ProtoArrayStore`; settles to the head's 32-byte
        root."""
        return self._submit("head", store)

    # --- pipeline -----------------------------------------------------------

    def pump(self, settle_all: bool = False) -> None:
        """Shed aged-out requests, dispatch everything queued, then
        settle in-flight batches down to the pipeline depth (all of
        them with `settle_all`)."""
        with telemetry.span("serve.pump", queued=len(self._queue),
                            inflight=len(self._inflight)):
            self._shed_expired()
            self._dispatch_queued()
            self._settle_ready(settle_all)
        self._maybe_dump_status()

    def drain(self) -> None:
        """Dispatch and settle everything; the queue and pipeline are
        empty afterwards."""
        self.pump(settle_all=True)

    def outstanding(self) -> int:
        """Requests submitted but not yet settled."""
        return len(self._queue) + sum(len(b.reqs) for b in self._inflight)

    # --- internals ----------------------------------------------------------

    def _note_queue_depth(self) -> None:
        n = len(self._queue)
        self._queue_hist[_depth_bucket(n)] = \
            self._queue_hist.get(_depth_bucket(n), 0) + 1
        if n > self._queue_max:
            self._queue_max = n
        telemetry.gauge("serve.queue_depth", n)

    def _note_inflight(self) -> None:
        n = len(self._inflight)
        if n > self._inflight_max:
            self._inflight_max = n
        telemetry.gauge("serve.inflight_batches", n)

    def _shed_expired(self) -> None:
        """The deadline policy: fail queued requests older than the
        per-request deadline with a typed `DeadlineExceeded`, OLDEST
        first (the queue is FIFO, so the head is always the oldest) —
        an overloaded service sheds explicitly instead of letting the
        queue grow without bound."""
        if self.deadline_s is None or not self._queue:
            return
        now = time.perf_counter()
        while self._queue:
            age = now - self._queue[0].t_enqueue
            if age <= self.deadline_s:
                break
            req = self._queue.popleft()
            trace_id = req.ctx.trace_id if req.ctx is not None else None
            req.future.set_exception(
                DeadlineExceeded(req.kind, age, self.deadline_s,
                                 trace_id=trace_id))
            if req.ctx is not None:
                # the whole shed lifetime is queue wait — there was no
                # dispatch, no settle
                req.ctx.complete("shed", final_component="queue_wait")
            self._shed += 1
            self._failed += 1
            telemetry.count("serve.shed")
        self._note_queue_depth()

    def _dispatch_one(self, kind: str, reqs: list[_Request],
                      attempt: int = 1) -> None:
        key = _breaker_key(kind, len(reqs))
        if self.breakers is not None and kind in ORACLE_KINDS \
                and not self.breakers.get(key).allow():
            self._serve_fallback(kind, reqs)
            return
        # request tracing: every member context closes its queue-wait
        # (or retry-detour) interval and learns its batch id — the
        # N-requests → 1-dispatch lineage the flow events render
        ctxs = [r.ctx for r in reqs if r.ctx is not None]
        batch_id = reqtrace.new_batch_id() if ctxs else None
        for ctx in ctxs:
            ctx.mark_dispatch(batch_id)
        # occupancy ledger: the span opens in host-prep now; the device
        # busy interval opens at mark_dispatch below and closes when
        # _settle_batch fetches the answer
        occ = occupancy.begin_batch(kind)
        try:
            # resilience seam: an injected fault here has exactly a real
            # host-prep failure's blast radius (THESE handles, no others)
            if faults.active():
                faults.maybe_inject("serve_pump", kind)
            bb = _ops_bls_batch()
            keys = None
            # block=False: the pipelined-dispatch contract — on
            # instrumented rounds the telemetry seam must not
            # block_until_ready between batches (see bls_batch._dispatch)
            if kind == "verify":
                if self.mesh is not None:
                    fut = self.mesh.verify_async(
                        [r.payload for r in reqs])
                else:
                    fut = bb.batch_verify_async(
                        [r.payload for r in reqs], block=False)
            elif kind == "committee":
                registry = reqs[0].payload[0]
                keys = registry.select([r.payload[1] for r in reqs],
                                       [r.payload[2] for r in reqs])
                fut = bb.batch_verify_async(
                    [(None, r.payload[3], r.payload[4]) for r in reqs],
                    block=False, pubkeys=keys)
            elif kind == "pairing":
                fut = bb.pairing_check_device_async(reqs[0].payload,
                                                    block=False)
            elif kind == "msm":
                fut = bb.g1_multi_exp_device_async(*reqs[0].payload,
                                                   block=False)
            elif kind == "sha256":
                from ..ops.sha256_jax import merkleize_words_jax_async
                fut = merkleize_words_jax_async(*reqs[0].payload)
            elif kind == "fr":
                from ..ops.fr_batch import barycentric_eval_async
                fut = barycentric_eval_async(*reqs[0].payload)
            elif kind == "das":
                from ..das.sampling import verify_sample_group_async
                # cross-sample batching: every queued sample's cell
                # statements fold into ONE RLC device batch (device
                # route always — the breaker's oracle fallback is the
                # host route)
                fut = verify_sample_group_async(
                    [r.payload for r in reqs])
            elif kind == "recover":
                from ..das.recover import \
                    recover_cells_and_kzg_proofs_async
                # one reconstruction per dispatch (the payload is a
                # whole damaged blob); the zero-poly FFT goes out now,
                # decode + FK20 re-prove run at settle
                fut = recover_cells_and_kzg_proofs_async(
                    *reqs[0].payload, device=True)
            elif kind == "fc_atts":
                # cross-request batching: every queued batch for this
                # store folds into ONE latest-message/weight dispatch;
                # the settle splits the accept mask per request
                store = reqs[0].payload[0]
                idx: list = []
                epochs: list = []
                roots: list = []
                for r in reqs:
                    idx.extend(r.payload[1])
                    epochs.extend(r.payload[2])
                    roots.extend(r.payload[3])
                fut = store.apply_attestations_async(idx, epochs, roots)
            elif kind == "head":
                fut = reqs[0].payload.get_head_async()
            else:   # proof
                from ..parallel.incremental import emit_proofs_async
                fut = emit_proofs_async(*reqs[0].payload)
        except Exception as exc:
            # host prep can fail before the batch ever reaches the
            # device (malformed payload, injected fault); same recovery
            # ladder as a failed device batch
            if occ is not None:
                occ.abandon()
            self._batch_failed(kind, reqs, exc, attempt, key)
            return
        for ctx in ctxs:
            ctx.mark_inflight()
        if batch_id is not None:
            reqtrace.note_batch(batch_id, kind,
                                [c.trace_id for c in ctxs], attempt,
                                len(reqs))
        if occ is not None:
            occ.mark_dispatch()
        self._inflight.append(_Batch(kind, fut, reqs, attempt=attempt,
                                     occ=occ, keys=keys))
        self._dispatched_batches += 1
        telemetry.count(f"serve.dispatch.{kind}")
        self._note_inflight()

    def _dispatch_queued(self) -> None:
        if not self._queue:
            return
        # partition the queue by kind, preserving arrival order within
        # each kind (the topological batches the futures settle in)
        by_kind: dict[str, list[_Request]] = {}
        while self._queue:
            req = self._queue.popleft()
            by_kind.setdefault(req.kind, []).append(req)
        self._note_queue_depth()
        for kind in KINDS:
            reqs = by_kind.get(kind)
            if not reqs:
                continue
            if kind in _BATCHED_KINDS:
                # batched kinds: up to max_batch requests per device
                # dispatch (das folds the samples' cell statements into
                # one RLC batch)
                for i in range(0, len(reqs), self.max_batch):
                    self._dispatch_one(kind, reqs[i:i + self.max_batch])
            elif kind == "fc_atts":
                # one merged dispatch per TARGET STORE, arrival order
                # preserved within each group
                groups: dict[int, list[_Request]] = {}
                for req in reqs:
                    groups.setdefault(id(req.payload[0]), []).append(req)
                for group in groups.values():
                    self._dispatch_one(kind, group)
            else:
                for req in reqs:
                    self._dispatch_one(kind, [req])

    def _settle_ready(self, settle_all: bool) -> None:
        while self._inflight and (settle_all
                                  or len(self._inflight) > self.depth):
            self._settle_batch(self._inflight.popleft())
            self._note_inflight()

    def _settle_until(self, fut: DeviceFuture, timeout=None) -> None:
        """Waiter hook for request handles: pump until `fut` settles
        (its batch may be queued, in flight, or already done).  With a
        `timeout` the wait is bounded: batch settles use the remaining
        budget and an exhausted budget returns with `fut` still pending
        (the future raises the typed `FutureTimeout`)."""
        deadline = None if timeout is None \
            else time.perf_counter() + float(timeout)
        self._shed_expired()
        self._dispatch_queued()
        while self._inflight and not fut.done():
            remaining = None
            if deadline is not None:
                remaining = deadline - time.perf_counter()
                if remaining <= 0:
                    return
            if not self._settle_batch(self._inflight.popleft(),
                                      timeout=remaining):
                return          # batch itself timed out (re-queued)
            self._note_inflight()

    def _verify_single(self, task) -> bool:
        """Per-statement verdict for a failed RLC batch (attribution)."""
        from ..ops.bls.ciphersuite import fast_aggregate_pairs

        return _ops_bls_batch().pairing_check_device(
            fast_aggregate_pairs(task))

    def _recheck_committee(self, batch: _Batch) -> list:
        """Per-statement verdicts for a failed committee batch, from its
        aggregate keys read back from the device."""
        points = batch.keys.points()
        return [pk is not None
                and self._verify_single((pk, r.payload[3], r.payload[4]))
                for pk, r in zip(points, batch.reqs)]

    def _note_keys(self, kind: str, reqs: list[_Request]) -> None:
        if kind == "committee":
            self._keys_aggregated += sum(int(r.payload[2].sum())
                                         for r in reqs)

    def _serve_fallback(self, kind: str, reqs: list[_Request]) -> None:
        """Degraded mode: answer on the pure-Python oracle (correct but
        slow) while the breaker holds the device path open.  Each
        request settles independently — an oracle failure poisons only
        its own handle."""
        with telemetry.span("serve.fallback", kind=kind,
                            requests=len(reqs)):
            for req in reqs:
                if req.ctx is not None:
                    req.ctx.mark_fallback_begin()
            now_latencies = []
            for req in reqs:
                try:
                    value = _oracle_compute(kind, req.payload)
                except Exception as exc:
                    req.future.set_exception(exc)
                    if req.ctx is not None:
                        req.ctx.complete("poisoned",
                                         final_component="detour")
                    self._failed += 1
                    telemetry.count("serve.failed")
                    continue
                req.future.set_result(value)
                if req.ctx is not None:
                    # oracle compute time is a resilience detour
                    req.ctx.complete("fallback",
                                     final_component="detour")
                now_latencies.append(req.t_enqueue)
                self._settled += 1
                self._note_keys(kind, [req])
            now = time.perf_counter()
            self.latencies_s.extend(now - t for t in now_latencies)
            self._fallbacks += len(reqs)
            telemetry.count(f"serve.fallback.{kind}", len(reqs))

    def _batch_failed(self, kind: str, reqs: list[_Request],
                      exc: Exception, attempt: int, key: str) -> None:
        """The recovery ladder for one failed batch: record the breaker
        failure, retry with backoff while the policy allows, then
        degrade to the oracle when the breaker is open — poisoning the
        handles only when no recovery path remains."""
        telemetry.count("serve.batch_failed")
        # the failed attempt's wall is a detour; an injected fault marks
        # its victims so the chaos harness can pin the blast radius to
        # exactly these trace ids
        faulted = isinstance(exc, faults.FaultInjected)
        for req in reqs:
            if req.ctx is not None:
                req.ctx.mark_attempt_failed(faulted=faulted)
        breaker = self.breakers.get(key) if self.breakers is not None \
            else None
        if breaker is not None:
            breaker.record_failure()
        if self.retry is not None and self.retry.should_retry(attempt):
            time.sleep(self.retry.backoff_s(attempt))
            self._retries += 1
            telemetry.count("serve.retry")
            self._dispatch_one(kind, reqs, attempt=attempt + 1)
            return
        if breaker is not None and breaker.state != "closed" \
                and kind in ORACLE_KINDS:
            self._serve_fallback(kind, reqs)
            return
        for req in reqs:
            req.future.set_exception(exc)
            if req.ctx is not None:
                req.ctx.complete("poisoned")
        self._failed += len(reqs)
        telemetry.count("serve.failed", len(reqs))
        # flight recorder: a poisoned batch is an incident event, and a
        # poison STORM (CST_FLIGHTREC_POISON_N) freezes the evidence
        # once — the bundle carries the fault plan and breaker arc that
        # explain it
        self._poisoned_batches += 1
        flightrec.record("batch_poisoned", batch_kind=kind,
                         requests=len(reqs), attempt=attempt,
                         error=f"{type(exc).__name__}: {exc}")
        n = flightrec.poison_dump_threshold()
        if n and self._poisoned_batches >= n \
                and not self._poison_dumped:
            self._poison_dumped = True
            try:
                flightrec.dump_bundle(reason="poison-storm")
                telemetry.count("serve.incident_bundles")
            except Exception:   # cst: allow(exc-swallow-device): evidence dump is best-effort — a failed incident write must never worsen the incident (the failure is counted)
                telemetry.count("serve.incident_dump_failed")

    def _settle_batch(self, batch: _Batch, timeout=None) -> bool:
        """Settle one in-flight batch; returns False (re-queueing the
        batch at the pipeline head) when a bounded wait ran out before
        the device answered."""
        with telemetry.span("serve.settle_batch", kind=batch.kind,
                            requests=len(batch.reqs)):
            key = _breaker_key(batch.kind, len(batch.reqs))
            ctxs = [r.ctx for r in batch.reqs if r.ctx is not None]
            try:
                out = batch.future.result() if timeout is None \
                    else batch.future.result(timeout=timeout)
                if batch.occ is not None:
                    batch.occ.mark_answer()
                for ctx in ctxs:
                    ctx.mark_device_done()
                if batch.kind in ("verify", "committee") \
                        and len(batch.reqs) > 1:
                    if out:
                        results = [True] * len(batch.reqs)
                    else:
                        self._rechecks += 1
                        telemetry.count("serve.batch_recheck")
                        results = (self._recheck_committee(batch)
                                   if batch.kind == "committee" else
                                   [self._verify_single(r.payload)
                                    for r in batch.reqs])
                        # the per-statement recheck wall is a detour,
                        # and the outcome label upgrades to "recheck"
                        for ctx in ctxs:
                            ctx.note_recheck()
                elif batch.kind == "das":
                    # the group future settles to per-sample verdicts
                    results = list(out)
                    assert len(results) == len(batch.reqs)
                elif batch.kind == "fc_atts":
                    # split the merged dispatch's accept mask back into
                    # per-request accepted counts
                    import numpy as np

                    mask = np.asarray(out)
                    results = []
                    off = 0
                    for req in batch.reqs:
                        n = len(req.payload[1])
                        results.append(int(np.count_nonzero(
                            mask[off:off + n])))
                        off += n
                else:
                    results = [out] * len(batch.reqs)
            except FutureTimeout:
                for ctx in ctxs:
                    ctx.note_timeout()      # provisional: still pending
                self._inflight.appendleft(batch)
                return False
            except Exception as exc:
                # a failed device batch — or a failed per-statement
                # recheck dispatch — walks the recovery ladder; the
                # executor itself keeps serving
                if batch.occ is not None:
                    batch.occ.abandon()
                self._batch_failed(batch.kind, batch.reqs, exc,
                                   batch.attempt, key)
                return True
            if self.breakers is not None:
                self.breakers.get(key).record_success()
            now = time.perf_counter()
            for req, value in zip(batch.reqs, results):
                req.future.set_result(value)
                if req.ctx is not None:
                    # outcome auto-resolves: recheck > retry > ok
                    req.ctx.complete()
                self.latencies_s.append(now - req.t_enqueue)
            self._settled += len(batch.reqs)
            self._note_keys(batch.kind, batch.reqs)
            telemetry.count("serve.settled", len(batch.reqs))
            if batch.occ is not None:
                batch.occ.mark_settled()
            return True

    # --- accounting ---------------------------------------------------------

    def status(self) -> dict:
        """Live ops snapshot as one JSON-able dict: queue depths (total
        + per kind + oldest age), in-flight batches/requests, the
        lifecycle counters, breaker states, and — on traced rounds
        (CST_TRACE_REQUESTS) — per-kind rolling p50/p99 with mean
        component attribution.  Dumped periodically from `pump()` when
        CST_SERVE_STATUS_EVERY > 0; callable on demand any time."""
        now = time.perf_counter()
        queue_by_kind: dict[str, int] = {}
        for req in self._queue:
            queue_by_kind[req.kind] = queue_by_kind.get(req.kind, 0) + 1
        inflight_by_kind: dict[str, int] = {}
        inflight_reqs = 0
        for batch in self._inflight:
            inflight_by_kind[batch.kind] = \
                inflight_by_kind.get(batch.kind, 0) + 1
            inflight_reqs += len(batch.reqs)
        out = {
            "ts": time.time(),
            "uptime_s": round(now - self._t_start, 3),
            "queue": {
                "depth": len(self._queue),
                "by_kind": queue_by_kind,
                "oldest_age_s": (round(now - self._queue[0].t_enqueue, 4)
                                 if self._queue else None),
            },
            "inflight": {
                "batches": len(self._inflight),
                "requests": inflight_reqs,
                "by_kind": inflight_by_kind,
            },
            "counters": {
                "submitted": self._submitted,
                "settled": self._settled,
                "failed": self._failed,
                "rechecks": self._rechecks,
                "batches": self._dispatched_batches,
                "retries": self._retries,
                "fallbacks": self._fallbacks,
                "shed": self._shed,
            },
            "tracing": reqtrace.enabled(),
        }
        if self.breakers is not None:
            out["breakers"] = self.breakers.states()
        if reqtrace.enabled():
            out["latency"] = reqtrace.rolling_summary()
        occ = occupancy.live_summary()
        if occ is not None:
            out["occupancy"] = {
                "device_busy_frac": occ["busy_frac"],
                "bubble_seconds": occ["bubbles_s"],
                "by_device": occ["devices"],
            }
        return out

    def _maybe_dump_status(self) -> None:
        """The CST_SERVE_STATUS_EVERY hook: at most one status line per
        interval, as `serve_status: {...}` on stderr (stdout stays the
        benches' one-JSON-line-per-metric contract)."""
        if self._status_every <= 0:
            return
        now = time.perf_counter()
        if now - self._status_last < self._status_every:
            return
        self._status_last = now
        telemetry.count("serve.status_dump")
        print("serve_status: " + json.dumps(self.status()),
              file=sys.stderr, flush=True)

    def stats(self) -> dict:
        """Plain-dict accounting for the bench `"serve"` block (does not
        depend on CST_TELEMETRY)."""
        out = {
            "submitted": self._submitted,
            "settled": self._settled,
            "failed": self._failed,
            "rechecks": self._rechecks,
            "batches": self._dispatched_batches,
            "retries": self._retries,
            "fallbacks": self._fallbacks,
            "shed": self._shed,
            "keys_aggregated": self._keys_aggregated,
            "queue_depth": {"max": self._queue_max,
                            "hist": dict(self._queue_hist)},
            "inflight_max": self._inflight_max,
        }
        if self.breakers is not None:
            out["breakers"] = self.breakers.states()
        if self.mesh is not None:
            out["mesh"] = self.mesh.block()
        return out
