"""Batched TPU BLS verification — the north star's hot path.

Public surface:

  pairing_check_device(pairs)      drop-in for the oracle's pairing_check
                                   (`ops/bls/pairing.py:160`): product of
                                   pairings == 1, one shared final exp,
                                   computed on device with HOST-precomputed
                                   fixed-argument Miller lines (the G2
                                   points of a pairing check are always
                                   host-known), so the device program has
                                   no G2 Jacobian arithmetic at all.
  batch_verify(tasks)              random-linear-combination batch of
                                   FastAggregateVerify-style checks: B
                                   signatures verified with B+1 pairings
                                   and ONE final exponentiation; the G1/G2
                                   scalar multiplications AND the message
                                   hash-to-curve (sha256 xmd + SVDW map +
                                   cofactor clearing, `h2c_jax`) also run
                                   on device, so the whole statement batch
                                   is device-resident end to end.
  g1_multi_exp_device(pts, ks)     G1 multiscalar multiplication via a
                                   windowed bucketed (Pippenger) kernel.
  registry.PubkeyRegistry          the node's validated keys on device;
                                   its committee aggregation program
                                   feeds batch_verify's pubkey side
                                   (`pubkeys=`) from aggregation bits.

Every entry point also has an `_async` variant returning a
`serve.futures.DeviceFuture` (the deferred-result contract): host prep +
kernel dispatch happen eagerly, the device→host transfer happens once at
`result()` — the serve executor pipelines batches through these, and the
synchronous names above are thin `.result()` facades kept for the spec /
block-executor call sites.

Host keeps parsing and subgroup checks (the oracle code); the device does
every pairing, scalar multiplication, and hash-to-curve.  Batch shapes are
padded to a 4-step bucket ladder so each jit entry point compiles at most
4 executables (`_bucket`).

Multi-pairing soundness (why ONE shared Fq12 accumulator and 128-bit RLC
scalars keep the forgery probability negligible, ~2^-127): the batch
check accepts iff
prod_i e(r_i PK_i, H_i) * e(-G1, sum_i r_i S_i) == 1, i.e. iff
prod_i e(PK_i, H_i)^{r_i} == prod_i e(G1, S_i)^{r_i}.  Writing
d_i = e(PK_i, H_i) / e(G1, S_i) (elements of the order-r multiplicative
group mu_r), acceptance means prod_i d_i^{r_i} == 1.  The sampling pins
r_0 = 1 and draws the other r_i as random ODD 128-bit values (2^127
possibilities each; odd => nonzero mod r).  A single false statement
with all others true is rejected deterministically when it sits at slot
0, else: conditioning on every other coefficient, at most one of the
2^127 values of r_i mod ord(d_i) can collapse the product to 1, so the
acceptance probability of any forged batch is at most 2^-127 — one bit
under the nominal 2^-RLC_SCALAR_BITS from the odd-only restriction, and
far below any feasible attack budget.  Folding the B Miller values into
one shared accumulator (f <- f^2 * prod_b line_b, `pairing_jax
.miller_product_batch`) computes exactly the same product of pairings —
conjugation and squaring are field automorphisms/homomorphisms, so the
algebraic predicate (and hence the bound) is unchanged; only the schedule
of Fq12 squarings differs (1 per loop bit instead of B).  See
`tests/formats/README.md` for the vector formats that pin the
accept/reject parity between this path and the oracle.

Replaces the reference's native backends behind
`eth2spec/utils/bls.py:141-296` (milagro `Verify`/`FastAggregateVerify`,
arkworks point ops).
"""

from __future__ import annotations

import functools
import os
import secrets
import time

import numpy as np

from ... import telemetry
from ...resilience import faults
from ...serve.futures import DeviceFuture, bool_future, value_future
from ...telemetry import costmodel, occupancy
from ...utils.jaxtools import jit
from ..bls import curve as _pycurve
from ..bls.hash_to_curve import DST_G2, hash_to_g2
from . import curve_jax as cj
from . import fq as _fq
from . import pairing_jax as pj
from . import tower as tw

RLC_SCALAR_BITS = 128     # soundness 2^-127 per forged batch (odd draws)

# batch-shape ladder: every entry point compiles at most these 4 shapes
# for realistic batch sizes (larger batches fall back to powers of two).
# Ratio-4 rungs bound padding waste at 4x while landing the BASELINE
# config shapes exactly (attestation batch 128+1 lanes, sync pairing 2->8)
_BUCKET_STEPS = (8, 32, 128, 512)


def _jnp():
    import jax.numpy as jnp
    return jnp


def _bucket(n: int) -> int:
    """Padded batch shape for n live lanes: the next power of two,
    quantized UP to the 4-step ladder so jit caches stay tiny.  n <= 1
    (including the n == 0 never-dispatched case) maps to the bottom rung;
    padded lanes are masked out, so correctness never depends on n."""
    b = 1 if n <= 1 else 1 << (n - 1).bit_length()
    for step in _BUCKET_STEPS:
        if b <= step:
            return step
    return b


# --- telemetry-aware kernel dispatch ----------------------------------------


def _dispatch(kernel: str, fn, args, block: bool = True):
    """Run a jitted kernel, attributing its wall time to compile vs run:
    the FIRST dispatch of a given (kernel, padded-shape) key pays
    trace + XLA compile (or a persistent-cache load — visible as an
    anomalously cheap first call), later dispatches are pure run.  Off
    (the default) this is a flag check and a tail call — no sync, no
    timing.

    `block=False` is the pipelined-caller contract (the serve executor
    threads it through the `*_async` entry points): after the first
    call of a (kernel, shape) key — which still blocks, the compile
    attribution and AOT cost capture need the built executable — later
    dispatches enqueue WITHOUT syncing and observe `dispatch_s` (host
    enqueue wall) instead of `run_s`, so an instrumented serve round
    keeps overlapping host prep with device execution instead of
    serializing the batch pipeline on every dispatch.

    This is also the cost-capture seam: on CST_COSTMODEL rounds the
    first dispatch of each (kernel, shape) additionally records XLA's
    cost/memory analysis for the compiled executable and samples the
    per-device memory watermark (both no-op flag checks otherwise).

    And it is the resilience fault seam (`resilience.faults`, OFF by
    default — one module-global read): an installed fault plan can
    raise here (dispatch exception / compile-fail-on-first-call /
    mesh-device loss, keyed by kernel name), inject latency, or corrupt
    the dispatched output (bit-flip/NaN, applied on device) — the
    deterministic chaos machinery the serve executor's recovery
    policies are tested against."""
    if faults.active():
        faults.maybe_inject("dispatch", kernel)
    if not telemetry.enabled():
        # the occupancy ledger has its own gate (CST_OCCUPANCY) — a
        # serve round can measure device busy without paying for the
        # full telemetry registry.  Without a sync we can't tell
        # enqueue from execute, so the span opens at enqueue and the
        # next future settle on this device closes it.
        if occupancy.enabled():
            t0 = time.perf_counter()
            out = fn(*args)
            occupancy.note_kernel_dispatched(kernel, t0=t0)
        else:
            out = fn(*args)
        return faults.corrupt("dispatch", kernel, out) \
            if faults.active() else out
    import jax

    first = telemetry.first_call(f"kernel.{kernel}")
    t0 = time.perf_counter()
    if first or block:
        out = jax.block_until_ready(fn(*args))
        which = "compile_first_s" if first else "run_s"
    else:
        out = fn(*args)
        which = "dispatch_s"
    dt = time.perf_counter() - t0
    if block or first:
        # blocking dispatch: the measured wall IS device busy
        occupancy.note_kernel_busy(kernel, t0, t0 + dt)
    else:
        # pipelined dispatch: busy opens at enqueue, the next future
        # settle on this device closes it (in-order stream)
        occupancy.note_kernel_dispatched(kernel, t0=t0)
    telemetry.observe(f"kernel.{which}", dt)
    telemetry.observe(f"kernel.{kernel}.{which}", dt)
    telemetry.count(f"kernel.{kernel}.calls")
    if first:
        # after the timing window: the AOT analysis pass must not
        # contaminate the compile-vs-run attribution above
        costmodel.capture(kernel, fn, args)
    costmodel.sample_watermark(f"kernel.{kernel}")
    if faults.active():
        out = faults.corrupt("dispatch", kernel, out)
    return out


def _count_lanes(live: int, padded: int) -> None:
    """Bucket-padding accounting: live lanes actually carrying a
    statement vs the `_bucket`-padded shape the kernel compiled for."""
    telemetry.count("bls.lanes.live", live)
    telemetry.count("bls.lanes.padded", padded)


# --- device helpers ---------------------------------------------------------


def g1_to_affine_dev(p):
    """Batched Jacobian -> affine on device; returns (x, y, inf_mask)."""
    X, Y, Z = p
    inf = _fq.fq_is_zero(Z)
    zi = _fq.fq_inv(Z)
    zi2 = _fq.fq_sqr(zi)
    return _fq.fq_mul(X, zi2), _fq.fq_mul(Y, _fq.fq_mul(zi2, zi)), inf


def g2_to_affine_dev(p):
    X, Y, Z = p
    inf = tw.fq2_is_zero(Z)
    zi = tw.fq2_inv(Z)
    zi2 = tw.fq2_sqr(zi)
    return tw.fq2_mul(X, zi2), tw.fq2_mul(Y, tw.fq2_mul(zi2, zi)), inf


# --- pairing check ----------------------------------------------------------


@functools.lru_cache(maxsize=16)
def _pairing_check_precomp_fn(batch: int):
    def pairing_check(xp, yp, lines, mask):
        return pj.multi_pairing_check_precomp(xp, yp, lines, mask)

    return jit(pairing_check)


def pairing_check_device_async(pairs, block: bool = True) -> DeviceFuture:
    """pairs: [(g1_jacobian, g2_jacobian)] oracle points.  Infinity pairs
    contribute the identity (matching the oracle's skip).  Returns a
    `DeviceFuture[bool]`: the kernel is dispatched asynchronously and
    the accept/reject bool crosses to the host only at `result()` —
    callers (the serve executor above all) keep feeding the pipeline
    instead of stalling on every check.

    The G2 arguments are host points by construction, so their Miller
    line coefficients are precomputed once per point on the host
    (`pj.precompute_g2_lines`, lru-cached) and shipped as scan constants:
    the device program is just the shared-accumulator line evaluation and
    one final exponentiation."""
    live = [(p, q) for p, q in pairs
            if not _pycurve.g1.is_inf(p) and not _pycurve.g2.is_inf(q)]
    if not live:
        return DeviceFuture.settled(True)
    jnp = _jnp()
    B = _bucket(len(live))
    with telemetry.span("bls.pairing_check_device", live=len(live),
                        padded=B):
        telemetry.count("bls.pairing_check.calls")
        _count_lanes(len(live), B)
        xp, yp = cj.g1_affine_to_limbs([p for p, _ in live])
        # (n_bits, B_live, 6, 2, 33): per-bit line coefficients per pair
        lines = np.stack([pj.precompute_g2_lines(q) for _, q in live],
                         axis=1)
        pad = B - len(live)
        if pad:
            xp = np.concatenate([xp, np.repeat(xp[:1], pad, 0)])
            yp = np.concatenate([yp, np.repeat(yp[:1], pad, 0)])
            lines = np.concatenate(
                [lines, np.repeat(lines[:, :1], pad, 1)], axis=1)
        mask = np.arange(B) < len(live)
        out = _dispatch(f"pairing_check@{B}", _pairing_check_precomp_fn(B),
                        (jnp.asarray(xp), jnp.asarray(yp),
                         jnp.asarray(lines), jnp.asarray(mask)),
                        block=block)
    return bool_future(out)


def pairing_check_device(pairs) -> bool:
    """Synchronous facade over `pairing_check_device_async` (the oracle
    `pairing_check` drop-in); the settle happens in `serve.futures`."""
    return pairing_check_device_async(pairs).result()


# --- RLC batch verify -------------------------------------------------------


def _rlc_pairing_core(pk_x, pk_y, sig_x, sig_y, h_x, h_y, h_ok,
                      r_bits, mask):
    """Traced body shared by the host-hash and device-hash RLC kernels:
    scalar-mul the B pubkeys and signatures by the random coefficients,
    sum the signature side, run the B+1 pairing product with the shared
    Fq12 accumulator.  Each stage runs under a `cst.rlc.*` named scope."""
    import jax
    jnp = _jnp()
    B = pk_x.shape[0]
    neg_g1 = cj.g1_affine_to_limbs([_pycurve.g1.neg(_pycurve.G1_GEN)])
    with jax.named_scope("cst.rlc.scalar_mul"):
        one1 = jnp.broadcast_to(jnp.asarray(_fq.ONE_MONT),
                                pk_x.shape).astype(jnp.int32)
        one2 = jnp.broadcast_to(jnp.asarray(tw.FQ2_ONE_L),
                                sig_x.shape).astype(jnp.int32)
        r_pk = cj.pt_scalar_mul(cj.F1, (pk_x, pk_y, one1), r_bits)
        r_sig = cj.pt_scalar_mul(cj.F2, (sig_x, sig_y, one2), r_bits)
    with jax.named_scope("cst.rlc.sig_sum"):
        # padding lanes -> infinity so they vanish from the signature sum
        r_sig = cj.pt_select(cj.F2, mask, r_sig,
                             cj.pt_infinity(cj.F2, r_sig))
        sum_sig = cj.pt_sum(cj.F2, r_sig, B)
        apx, apy, a_inf = g1_to_affine_dev(r_pk)
        sx, sy, s_inf = g2_to_affine_dev(tuple(c[None] for c in sum_sig))

    # pairing lanes: (r_i PK_i, H_i) for live i, plus (-G1, sum_sig),
    # as `pj.multi_pairing_check` runs them
    with jax.named_scope("cst.rlc.miller_loop"):
        xp = jnp.concatenate([apx, jnp.asarray(neg_g1[0])])
        yp = jnp.concatenate([apy, jnp.asarray(neg_g1[1])])
        xq = jnp.concatenate([h_x, sx])
        yq = jnp.concatenate([h_y, sy])
        lane_mask = jnp.concatenate([mask & ~a_inf & h_ok, ~s_inf])
        total = pj.miller_product_batch(xp, yp, xq, yq, lane_mask)
    with jax.named_scope("cst.rlc.final_exp"):
        return tw.fq12_is_one(pj.final_exponentiate(total))


@functools.lru_cache(maxsize=16)
def _rlc_kernel(batch: int):
    """Jitted RLC kernel, message hashes computed on host."""
    jnp = _jnp()

    def rlc_verify(pk_x, pk_y, sig_x, sig_y, h_x, h_y, r_bits, mask):
        h_ok = jnp.ones(pk_x.shape[0], dtype=bool)
        return _rlc_pairing_core(pk_x, pk_y, sig_x, sig_y, h_x, h_y,
                                 h_ok, r_bits, mask)

    return jit(rlc_verify)


@functools.lru_cache(maxsize=16)
def _rlc_kernel_h2c(batch: int):
    """Jitted RLC kernel with DEVICE hash-to-curve: the 32-byte message
    roots enter as uint32 words and the whole statement batch —
    expand_message_xmd, SVDW map, cofactor clearing, scalar muls,
    pairings — runs in one device program."""
    import jax

    from . import h2c_jax as h2c

    def rlc_verify_h2c(pk_x, pk_y, sig_x, sig_y, msg_words, r_bits, mask):
        with jax.named_scope("cst.rlc.h2c"):
            H = h2c.hash_to_g2_dev(msg_words)
            h_x, h_y, h_inf = g2_to_affine_dev(H)
        return _rlc_pairing_core(pk_x, pk_y, sig_x, sig_y, h_x, h_y,
                                 ~h_inf, r_bits, mask)

    return jit(rlc_verify_h2c)


@functools.lru_cache(maxsize=16)
def _msm_kernel(batch: int):
    """Jitted G1 MSM: batched 255-step double-and-add over all points at
    once, then a log-depth tree sum.  Fully uniform control flow; kept as
    the reference kernel and the `CST_MSM_ALGO=double-add` fallback."""
    jnp = _jnp()

    def msm(x, y, bits, mask):
        B = x.shape[0]
        one1 = jnp.broadcast_to(jnp.asarray(_fq.ONE_MONT),
                                x.shape).astype(jnp.int32)
        muls = cj.pt_scalar_mul(cj.F1, (x, y, one1), bits)
        muls = cj.pt_select(cj.F1, mask, muls,
                            cj.pt_infinity(cj.F1, muls))
        return cj.pt_sum(cj.F1, muls, B)

    return jit(msm)


@functools.lru_cache(maxsize=16)
def _msm_pippenger_kernel(batch: int, c: int):
    """Jitted G1 Pippenger MSM: one scan over the points scatters each
    into its per-window bucket (all ceil(255/c) windows in parallel),
    then suffix-sum bucket reduction and the windowed combine — total
    point-add work B + 2^(c+1) + 255/c instead of 255 doubles + adds per
    scalar.  Zero scalars (and padding lanes) land in bucket 0, which the
    reduction skips, so no mask input is needed."""
    jnp = _jnp()

    def msm_pippenger(x, y, digits):
        one1 = jnp.broadcast_to(jnp.asarray(_fq.ONE_MONT),
                                x.shape).astype(jnp.int32)
        return cj.pt_msm_pippenger(cj.F1, (x, y, one1), digits, c)

    return jit(msm_pippenger)


SCALAR_BITS = 255  # BLS12-381 subgroup order is 255 bits


def _msm_window(n: int) -> int:
    """Pippenger window size for an n-point batch (2^c buckets must stay
    well under n for the bucket phase to amortize)."""
    if n < 32:
        return 4
    if n < 256:
        return 6
    if n < 2048:
        return 8
    return 10


# Pippenger's bucket scatter is sequential in B while double-and-add is
# sequential only in the 255 scalar bits (B-wide each step): bucketed
# wins while the batch is latency-bound, the uniform kernel wins once B
# is wide enough to saturate the vector units.  Crossover set at the
# bucket ladder's top shape; CST_MSM_ALGO=pippenger|double-add forces one.
_MSM_PIPPENGER_MAX = 512


def _msm_algo(batch: int) -> str:
    algo = os.environ.get("CST_MSM_ALGO", "auto")
    if algo == "auto":
        return "pippenger" if batch <= _MSM_PIPPENGER_MAX else "double-add"
    return algo


def g1_multi_exp_device_async(points, scalars,
                              block: bool = True) -> DeviceFuture:
    """Device G1 multiscalar multiplication (bucketed Pippenger below
    the width crossover, batched double-and-add above it — see
    `_msm_algo`).

    points: oracle Jacobian G1 points; scalars: ints (reduced mod r).
    Returns a `DeviceFuture` settling to an oracle Jacobian point (the
    limb→oracle conversion runs host-side at settle time).  The KZG
    batch path's `g1_lincomb` (`specs/deneb/polynomial-commitments.md
    :415-460` algorithms) lands here when the jax backend is active."""
    import jax.numpy as jnp

    assert len(points) == len(scalars) and len(points) > 0
    live = []
    for p, s in zip(points, scalars):
        s = int(s) % _pycurve.R
        if s == 0 or _pycurve.g1.is_inf(p):
            continue
        live.append((p, s))
    if not live:
        return DeviceFuture.settled(_pycurve.g1.infinity())

    B = _bucket(len(live))
    algo = _msm_algo(B)
    with telemetry.span("bls.g1_multi_exp_device", live=len(live),
                        padded=B, algo=algo):
        telemetry.count("msm.device.calls")
        telemetry.count(f"msm.algo.{algo}")
        telemetry.observe("msm.device.n", len(live))
        _count_lanes(len(live), B)
        x, y = cj.g1_affine_to_limbs([p for p, _ in live])
        pad = B - len(live)
        if pad:
            x = np.concatenate([x, np.repeat(x[:1], pad, 0)])
            y = np.concatenate([y, np.repeat(y[:1], pad, 0)])

        if algo == "pippenger":
            c = _msm_window(B)
            digits = cj.scalars_to_digits([s for _, s in live],
                                          SCALAR_BITS, c)
            if pad:
                digits = np.concatenate(
                    [digits, np.zeros((pad,) + digits.shape[1:], np.int32)])
            out = _dispatch(f"msm_pippenger@{B}w{c}",
                            _msm_pippenger_kernel(B, c),
                            (jnp.asarray(x), jnp.asarray(y),
                             jnp.asarray(digits)), block=block)
        else:
            bits = cj.scalars_to_bits([s for _, s in live], SCALAR_BITS)
            if pad:
                bits = np.concatenate(
                    [bits, np.zeros((pad, SCALAR_BITS), np.int32)])
            mask = np.arange(B) < len(live)
            out = _dispatch(f"msm_double_add@{B}", _msm_kernel(B),
                            (jnp.asarray(x), jnp.asarray(y),
                             jnp.asarray(bits), jnp.asarray(mask)),
                            block=block)
    # the point leaves the device at settle time, once, in serve.futures
    return value_future(out, convert=cj.g1_limbs_to_oracle)


def g1_multi_exp_device(points, scalars):
    """Synchronous facade over `g1_multi_exp_device_async`; returns the
    oracle Jacobian point."""
    return g1_multi_exp_device_async(points, scalars).result()


@functools.lru_cache(maxsize=16)
def _msm_sharded_kernel(n_devices: int, per_shard: int, c: int,
                        axis: str, device_ids: tuple | None = None):
    """shard_map'd Pippenger MSM over a `Mesh` (built by the shared
    partition-registry builder): each device runs the bucket
    accumulation + window combine over its own point shard, the D
    partial points ride one `all_gather` across the mesh (the
    psum-style final fold — point addition has no hardware psum, so the
    log-depth `pt_sum` tree over the gathered partials is the exact
    group-sum equivalent), replicated output.  Zero digits (padding
    lanes) land in bucket 0 which the reduction skips, so no mask
    crosses the mesh.

    `device_ids` pins the mesh to the surviving-device subset
    (`resilience.mesh` form), same contract as `_rlc_kernel_sharded`."""
    import jax
    from jax.sharding import PartitionSpec as P

    from ...parallel.partition import build_mesh
    jnp = _jnp()

    mesh = build_mesh(n_devices=n_devices, device_ids=device_ids,
                      axis=axis)

    def msm_sharded(x, y, digits):
        one1 = jnp.broadcast_to(jnp.asarray(_fq.ONE_MONT),
                                x.shape).astype(jnp.int32)
        partial = cj.pt_msm_pippenger(cj.F1, (x, y, one1), digits, c)
        gathered = jax.tree_util.tree_map(
            lambda co: jax.lax.all_gather(co, axis), partial)
        return cj.pt_sum(cj.F1, gathered, n_devices)

    sharded = jax.shard_map(
        msm_sharded, mesh=mesh, in_specs=(P(axis), P(axis), P(axis)),
        out_specs=P(), check_vma=False)
    return jit(sharded)


def g1_multi_exp_sharded_async(points, scalars,
                               n_devices: int | None = None,
                               axis: str = "data",
                               device_ids=None,
                               block: bool = True) -> DeviceFuture:
    """`g1_multi_exp_device_async` distributed over the device mesh:
    points shard across `n_devices`, each device accumulates its own
    Pippenger buckets, and one all_gather + log-depth point-sum fold
    combines the partial results.  The settled oracle point is
    identical to the single-chip path (group addition is associative —
    only the summation schedule differs).

    `device_ids` pins the mesh to specific `jax.devices()` indices (the
    resilience layer's surviving-device set); when given it overrides
    `n_devices`.  A one-device request degrades to the single-chip
    path."""
    import jax
    import jax.numpy as jnp

    assert len(points) == len(scalars) and len(points) > 0
    available = len(jax.devices())
    if device_ids is not None:
        device_ids = tuple(int(i) for i in device_ids)
        assert device_ids and max(device_ids) < available, device_ids
        n_devices = len(device_ids)
    if n_devices is None:
        n_devices = available
    n_devices = min(n_devices, available)
    if n_devices <= 1 and device_ids is None:
        return g1_multi_exp_device_async(points, scalars, block=block)

    live = []
    for p, s in zip(points, scalars):
        s = int(s) % _pycurve.R
        if s == 0 or _pycurve.g1.is_inf(p):
            continue
        live.append((p, s))
    if not live:
        return DeviceFuture.settled(_pycurve.g1.infinity())

    per_shard = _bucket((len(live) + n_devices - 1) // n_devices)
    lanes = n_devices * per_shard
    c = _msm_window(per_shard)
    with telemetry.span("bls.g1_multi_exp_sharded", live=len(live),
                        devices=n_devices, per_shard=per_shard):
        telemetry.count("msm.sharded.calls")
        _count_lanes(len(live), lanes)
        x, y = cj.g1_affine_to_limbs([p for p, _ in live])
        digits = cj.scalars_to_digits([s for _, s in live],
                                      SCALAR_BITS, c)
        pad = lanes - len(live)
        if pad:
            # padded lanes repeat point 0 with ZERO digits: bucket 0 is
            # never read, so they contribute nothing — no mask needed
            x = np.concatenate([x, np.repeat(x[:1], pad, 0)])
            y = np.concatenate([y, np.repeat(y[:1], pad, 0)])
            digits = np.concatenate(
                [digits, np.zeros((pad,) + digits.shape[1:], np.int32)])
        # cst: allow(recompile-unbucketed-dim): the device count keys
        # the executable — one value per host topology, not per batch
        kernel = _msm_sharded_kernel(n_devices, per_shard, c, axis,
                                     device_ids)
        out = _dispatch(f"msm_sharded@{n_devices}x{per_shard}w{c}",
                        kernel,
                        (jnp.asarray(x), jnp.asarray(y),
                         jnp.asarray(digits)), block=block)
    return value_future(out, convert=cj.g1_limbs_to_oracle)


def g1_multi_exp_sharded(points, scalars, n_devices: int | None = None,
                         axis: str = "data", device_ids=None):
    """Synchronous facade over `g1_multi_exp_sharded_async`."""
    return g1_multi_exp_sharded_async(
        points, scalars, n_devices=n_devices, axis=axis,
        device_ids=device_ids).result()


def _prepare_rlc_inputs(tasks, rand, lanes: int, device_h2c: bool = False,
                        device_pubkeys: bool = False):
    """Host-side prep shared by the single-device and sharded RLC paths:
    drop trivial pairs, hash messages (host) or pack them as uint32 words
    (device h2c), build limb arrays padded to `lanes` (or the bucket
    ladder shape when `lanes` is None).

    Returns (arrays, n_live) with arrays None when a degenerate path
    already decided the answer (n_live then carries the bool).  With
    device_h2c the h_x/h_y slots of the array tuple are replaced by one
    (B, 8) big-endian message-word matrix.  With device_pubkeys the tasks'
    pubkey slots are None and so are the pk_x/pk_y slots of the arrays:
    the caller supplies them from the device; no lane is dropped, and a
    signature at infinity decides the batch False (only an aggregate at
    infinity could match it, and KeyValidate refuses that one; the
    recheck gives each statement its own verdict)."""
    live = []
    for pk, msg, sig in tasks:
        if not device_pubkeys and _pycurve.g1.is_inf(pk) \
                and _pycurve.g2.is_inf(sig):
            continue          # 1 == 1 trivially; mirrors oracle skip
        live.append((pk, bytes(msg), sig))
    if not live:
        return None, True
    if device_pubkeys and any(_pycurve.g2.is_inf(t[2]) for t in live):
        return None, False

    # infinity on only one side cannot go through the affine kernels —
    # fall back to per-task device checks (rare, adversarial-only)
    if not device_pubkeys and any(
            _pycurve.g1.is_inf(pk) or _pycurve.g2.is_inf(sig)
            for pk, _, sig in live):
        ok = all(
            pairing_check_device([(pk, hash_to_g2(msg, DST_G2)),
                                  (_pycurve.g1.neg(_pycurve.G1_GEN), s)])
            for pk, msg, s in live)
        return None, ok

    B = _bucket(len(live)) if lanes is None else lanes
    assert B >= len(live)
    pk_x = pk_y = None
    if not device_pubkeys:
        pk_x, pk_y = cj.g1_affine_to_limbs([t[0] for t in live])
    if device_h2c:
        from . import h2c_jax as h2c
        h_arrays = (h2c.msgs_to_words([t[1] for t in live]),)
    else:
        h_arrays = cj.g2_affine_to_limbs(
            [hash_to_g2(t[1], DST_G2) for t in live])
    sig_x, sig_y = cj.g2_affine_to_limbs([t[2] for t in live])
    scalars = [1] + [rand.getrandbits(RLC_SCALAR_BITS) | 1
                     for _ in range(len(live) - 1)]
    r_bits = cj.scalars_to_bits(scalars, RLC_SCALAR_BITS)

    pad = B - len(live)
    if pad:
        def _p(a):
            return None if a is None else np.concatenate(
                [a, np.repeat(a[:1], pad, 0)])
        pk_x, pk_y = _p(pk_x), _p(pk_y)
        h_arrays = tuple(_p(a) for a in h_arrays)
        sig_x, sig_y = _p(sig_x), _p(sig_y)
        r_bits = np.concatenate(
            [r_bits, np.zeros((pad, RLC_SCALAR_BITS), np.int32)])
    mask = np.arange(B) < len(live)
    return ((pk_x, pk_y, sig_x, sig_y) + h_arrays + (r_bits, mask),
            len(live))


def _no_infinite_key(n: int, host) -> bool:
    """The verdict of a batch whose keys the device aggregated: the RLC
    kernel's, and no live statement's aggregate key at infinity."""
    ok, inf = host
    return bool(ok) and not bool(np.any(inf[:n]))


def batch_verify_async(tasks, rng=None, device_h2c: bool | None = None,
                       block: bool = True, pubkeys=None) -> DeviceFuture:
    """tasks: [(g1_pubkey_jacobian, message_bytes, g2_sig_jacobian)].

    Verifies all FastAggregateVerify-style statements
    e(PK_i, H(m_i)) == e(G1, S_i) at once: random 128-bit coefficients
    r_i collapse them into   prod e(r_i PK_i, H_i) · e(-G1, Σ r_i S_i) == 1.
    Returns a `DeviceFuture[bool]`: host prep + dispatch happen here,
    the verdict crosses to the host only at `result()` — the serve
    executor dispatches the NEXT batch while this one executes.

    With device_h2c (the default for 32-byte message roots; opt out with
    CST_BLS_DEVICE_H2C=0) the message hashing runs on device too, so the
    host only parses points and draws coefficients.

    With `pubkeys` (a `registry.CommitteeKeys`, one committee selection
    per task) the tasks' pubkey slots are None: the committee aggregation
    program sums each statement's keys from the device registry and its
    output enters the RLC kernel as pk_x, pk_y.  The selection's host prep
    runs inside `bls.prepare` and its dispatch inside `bls.enqueue`; the
    verdict is False too where a live aggregate is infinity."""
    if not tasks:
        return DeviceFuture.settled(True)
    rand = rng if rng is not None else secrets.SystemRandom()
    if device_h2c is None:
        device_h2c = os.environ.get("CST_BLS_DEVICE_H2C", "1") != "0"
    # the device xmd kernel is specialized to 32-byte signing roots
    device_h2c = device_h2c and all(
        len(bytes(m)) == 32 for _, m, _ in tasks)
    with telemetry.span("bls.batch_verify", tasks=len(tasks),
                        device_h2c=device_h2c):
        telemetry.count("bls.batch_verify.calls")
        with telemetry.span("bls.prepare"):
            arrays, n = _prepare_rlc_inputs(
                tasks, rand, None, device_h2c=device_h2c,
                device_pubkeys=pubkeys is not None)
            if arrays is not None and pubkeys is not None:
                keys_host = pubkeys.prepare(_bucket(n))
        if arrays is None:
            # degenerate path: trivial skip or the per-task host
            # fallback — no statements reached the batched kernel
            return DeviceFuture.settled(bool(n))
        jnp = _jnp()
        # lanes=None above means _prepare_rlc_inputs padded to the
        # ladder shape for n live lanes — recompute it rather than
        # reading arrays[0].shape (a raw dim the analyzer would flag)
        B = _bucket(n)
        # h2c routing counted per LIVE lane, after prepare: the
        # degenerate paths above hash on the host (or not at all)
        telemetry.count("bls.h2c.device" if device_h2c else "bls.h2c.host",
                        n)
        _count_lanes(n, B)
        kernel = _rlc_kernel_h2c if device_h2c else _rlc_kernel
        name = f"rlc_{'h2c' if device_h2c else 'host_hash'}@{B}"
        with telemetry.span("bls.enqueue"):
            if pubkeys is None:
                args = tuple(jnp.asarray(a) for a in arrays)
            else:
                pk_x, pk_y, pk_inf = pubkeys.enqueue(keys_host, block=block)
                args = (pk_x, pk_y) + tuple(jnp.asarray(a)
                                            for a in arrays[2:])
            out = _dispatch(name, kernel(B), args, block=block)
    if pubkeys is None:
        return bool_future(out)
    return value_future((out, pk_inf),
                        convert=functools.partial(_no_infinite_key, n))


def batch_verify(tasks, rng=None, device_h2c: bool | None = None) -> bool:
    """Synchronous facade over `batch_verify_async` (the block
    executor's settle call); the bool fetch lives in `serve.futures`."""
    return batch_verify_async(tasks, rng=rng,
                              device_h2c=device_h2c).result()


@functools.lru_cache(maxsize=16)
def _rlc_kernel_sharded(n_devices: int, per_shard: int, axis: str,
                        device_ids: tuple | None = None):
    """shard_map'd RLC batch over a `Mesh`: every device scalar-muls and
    Miller-loops its own lane shard, partial signature sums and partial
    Miller products ride one `all_gather` each across the mesh (ICI, not
    host), and the single final exponentiation runs replicated.  The
    multi-chip form of `_rlc_kernel` — same predicate, same soundness.

    `device_ids` (a tuple of `jax.devices()` indices) builds the mesh
    from exactly those devices instead of the first `n_devices` — the
    mesh-resilience layer's shrunken-mesh form (`resilience.mesh`): a
    lost shard's statements re-bucket across the SURVIVING devices, not
    a renumbered prefix that might include the dead one.  The mesh
    itself comes from the shared partition-registry builder
    (`parallel.partition.build_mesh`) — one mesh-construction path for
    the RLC batch, the sharded MSM, the epoch step, and the sharded
    forests."""
    import jax
    from jax.sharding import PartitionSpec as P

    from ...parallel.partition import build_mesh
    jnp = _jnp()

    mesh = build_mesh(n_devices=n_devices, device_ids=device_ids,
                      axis=axis)
    neg_g1 = cj.g1_affine_to_limbs([_pycurve.g1.neg(_pycurve.G1_GEN)])

    def rlc_verify_sharded(pk_x, pk_y, sig_x, sig_y, h_x, h_y, r_bits,
                           mask):
        B = pk_x.shape[0]   # per-shard lanes
        one1 = jnp.broadcast_to(jnp.asarray(_fq.ONE_MONT),
                                pk_x.shape).astype(jnp.int32)
        one2 = jnp.broadcast_to(jnp.asarray(tw.FQ2_ONE_L),
                                sig_x.shape).astype(jnp.int32)

        r_pk = cj.pt_scalar_mul(cj.F1, (pk_x, pk_y, one1), r_bits)
        r_sig = cj.pt_scalar_mul(cj.F2, (sig_x, sig_y, one2), r_bits)
        r_sig = cj.pt_select(cj.F2, mask, r_sig,
                             cj.pt_infinity(cj.F2, r_sig))
        # local signature partial sum, then combine shards' partials
        local_sum = cj.pt_sum(cj.F2, r_sig, B)
        gathered = jax.tree_util.tree_map(
            lambda c: jax.lax.all_gather(c, axis), local_sum)
        sum_sig = cj.pt_sum(cj.F2, gathered, n_devices)

        # local pairing lanes (r_i PK_i, H_i): shared-accumulator Miller
        # product per shard (one Fq12 squaring per bit per device)
        apx, apy, a_inf = g1_to_affine_dev(r_pk)
        partial = pj.miller_product_batch(apx, apy, h_x, h_y,
                                          mask & ~a_inf)
        partials = jax.lax.all_gather(partial, axis)    # (D, <fq12>)
        total = pj._product_tree(partials, n_devices)

        # the shared (-G1, Σ r_i S_i) lane, multiplied in exactly once
        sx, sy, s_inf = g2_to_affine_dev(
            tuple(c[None] for c in sum_sig))
        f_extra = pj.miller_product_batch(
            jnp.asarray(neg_g1[0]), jnp.asarray(neg_g1[1]), sx, sy,
            ~s_inf)
        total = tw.fq12_mul(total, f_extra)
        return tw.fq12_is_one(pj.final_exponentiate(total))

    sharded = jax.shard_map(
        rlc_verify_sharded, mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis), P(axis), P(axis), P(axis),
                  P(axis), P(axis)),
        out_specs=P(), check_vma=False,
    )
    return jit(sharded)


def batch_verify_sharded_async(tasks, n_devices: int | None = None,
                               rng=None, axis: str = "data",
                               device_ids=None) -> DeviceFuture:
    """`batch_verify_async` distributed over the device mesh: lanes
    shard across `n_devices`, cross-device combination is two
    all_gathers (partial G2 sums, partial Miller products), one
    replicated final exponentiation.  Accept/reject is bit-identical to
    `batch_verify`.

    `device_ids` pins the mesh to specific `jax.devices()` indices (the
    resilience layer's surviving-device set after a `device_loss`);
    when given it overrides `n_devices`.  A one-device set degrades to
    the single-chip `batch_verify_async` path."""
    import jax

    if not tasks:
        return DeviceFuture.settled(True)
    available = len(jax.devices())
    if device_ids is not None:
        device_ids = tuple(int(i) for i in device_ids)
        assert device_ids and max(device_ids) < available, device_ids
        n_devices = len(device_ids)
    if n_devices is None:
        n_devices = available
    n_devices = min(n_devices, available)
    if n_devices <= 1 and device_ids is None:
        # a 1-wide IMPLICIT request degrades to the single-chip path;
        # an explicit one-survivor device set must keep the mesh form —
        # batch_verify_async has no device pinning, and the default
        # device may be exactly the dead one the caller is avoiding
        return batch_verify_async(tasks, rng=rng)
    rand = rng if rng is not None else secrets.SystemRandom()
    # pad lanes to devices x power-of-two per-shard bucket
    n_tasks = len(tasks)
    per_shard = _bucket((n_tasks + n_devices - 1) // n_devices)
    # resilience fault seam (one module-global read when idle): the
    # mesh chaos rounds inject `device_loss` here — the same boundary a
    # real XlaRuntimeError from a dead mesh device surfaces at
    if faults.active():
        faults.maybe_inject("dispatch",
                            f"rlc_sharded@{n_devices}x{per_shard}")
    arrays, n = _prepare_rlc_inputs(tasks, rand,
                                    n_devices * per_shard)
    if arrays is None:
        return DeviceFuture.settled(bool(n))
    jnp = _jnp()
    with telemetry.span("bls.batch_verify_sharded", tasks=n_tasks,
                        devices=n_devices, per_shard=per_shard):
        telemetry.count("bls.batch_verify_sharded.calls")
        _count_lanes(n, n_devices * per_shard)
        jargs = tuple(jnp.asarray(a) for a in arrays)
        # cst: allow(recompile-unbucketed-dim): the device count keys
        # the executable — one value per host topology, not per batch
        kernel = _rlc_kernel_sharded(n_devices, per_shard, axis,
                                     device_ids)
        out = kernel(*jargs)
    # cost-capture seam, outside the span so the AOT analysis pass does
    # not contaminate the measured wall (capture degrades to an error
    # record if the backend cannot analyze the mesh-sharded executable)
    costmodel.capture(f"rlc_sharded@{n_devices}x{per_shard}",
                      kernel, jargs)
    costmodel.sample_watermark("bls.batch_verify_sharded")
    return bool_future(out)


def batch_verify_sharded(tasks, n_devices: int | None = None,
                         rng=None, axis: str = "data",
                         device_ids=None) -> bool:
    """Synchronous facade over `batch_verify_sharded_async`."""
    return batch_verify_sharded_async(tasks, n_devices=n_devices,
                                      rng=rng, axis=axis,
                                      device_ids=device_ids).result()
