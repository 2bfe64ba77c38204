"""Batched SHA-256 + Merkle reduction on TPU via JAX/XLA.

Same data layout as `ops.sha256_np` (chunks as (N, 8) big-endian uint32
words) so results are bit-identical across the host and device paths.

Two device paths, for two different needs:

- 64-byte messages (every Merkle node, `sha256_64B_planes` and the
  functions over it): one Pallas TPU kernel over word-major planes with
  the batch on the lanes, (16, M) message words -> (8, M) digest words.
  It keeps each hash's eight working registers and its 16-word message
  window in vector registers for all 64 rounds of both compressions.
  The padding block of a 64-byte message is a constant, so its 64
  K[t] + W[t] are precomputed.  Lowered for any other platform, the same
  body runs as plain jnp (`lax.platform_dependent`).
- Variable-length messages inside larger programs (`h2c_jax`'s
  expand_message_xmd): `_compress`, the 64 rounds as a `lax.fori_loop`
  with a 16-word rolling schedule, so the HLO stays a small loop.  At
  registry batch sizes that loop carries its state through HBM every
  round, which is why the Merkle trees do not use it.

This is the TPU replacement for remerkleable's per-node Python hashing
(reference: `eth2spec/utils/ssz/ssz_impl.py:25` calling
`.get_backing().merkle_root()`).
"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import telemetry
from ..resilience import faults
from ..telemetry import costmodel
from .sha256_np import _IV, _K, _PAD64, ZERO_HASH_WORDS
from .sha256_np import sha256_64B_words as _host_sha256_64B

# Device constants stay PLAIN NUMPY at module level (the `fq.py`
# convention): materializing jnp arrays at import time leaks tracers
# when the first import of this module happens inside an active jit
# trace — `h2c_jax._sha_blocks` imports us lazily from traced code, so
# an import-time `jnp.asarray` there would bind these names to that
# trace's tracers and crash every later host-side use (found live by a
# batch_verify-then-merkleize drive; the analyzer's
# device-const-at-import rule now pins this).  jnp closes over numpy
# constants at trace time instead.
_K_np = np.asarray(_K)
_IV_np = np.asarray(_IV)
_ZEROS_np = np.stack(ZERO_HASH_WORDS[:64])   # (64, 8)


def _rotr(x, n):
    return (x >> jnp.uint32(n)) | (x << jnp.uint32(32 - n))


def _round(a, b, c, d, e, f, g, h, kt, wt):
    s1 = _rotr(e, 6) ^ _rotr(e, 11) ^ _rotr(e, 25)
    ch = (e & f) ^ (~e & g)
    t1 = h + s1 + ch + kt + wt
    s0 = _rotr(a, 2) ^ _rotr(a, 13) ^ _rotr(a, 22)
    maj = (a & b) ^ (a & c) ^ (b & c)
    t2 = s0 + maj
    return t1 + t2, a, b, c, d + t1, e, f, g


def _schedule_next(w):
    """Given rolling 16-word window w (..., 16), compute w[t+16] and roll."""
    s0 = _rotr(w[..., 1], 7) ^ _rotr(w[..., 1], 18) ^ (w[..., 1] >> jnp.uint32(3))
    s1 = _rotr(w[..., 14], 17) ^ _rotr(w[..., 14], 19) ^ (w[..., 14] >> jnp.uint32(10))
    nxt = w[..., 0] + s0 + w[..., 9] + s1
    return jnp.concatenate([w[..., 1:], nxt[..., None]], axis=-1)


def _compress_loop(state, block):
    """Compression as a lax.fori_loop over 64 rounds (small HLO)."""
    Kj = jnp.asarray(_K_np, dtype=jnp.uint32)   # t is traced: need jnp

    def body(t, carry):
        regs, w = carry
        regs = _round(*regs, Kj[t], w[..., 0])
        w = _schedule_next(w)
        return regs, w

    regs0 = tuple(state[..., i] for i in range(8))
    (regs, _) = lax.fori_loop(0, 64, body, (regs0, block))
    return state + jnp.stack(regs, axis=-1)


def _compress_unrolled(state, block):
    """Fully unrolled compression (max fusion; expensive to compile)."""
    w = [block[..., t] for t in range(16)]
    for t in range(16, 64):
        s0 = _rotr(w[t - 15], 7) ^ _rotr(w[t - 15], 18) ^ (w[t - 15] >> jnp.uint32(3))
        s1 = _rotr(w[t - 2], 17) ^ _rotr(w[t - 2], 19) ^ (w[t - 2] >> jnp.uint32(10))
        w.append(w[t - 16] + s0 + w[t - 7] + s1)
    regs = tuple(state[..., i] for i in range(8))
    for t in range(64):
        regs = _round(*regs, jnp.uint32(_K_np[t]), w[t])
    return state + jnp.stack(regs, axis=-1)


def _compress(state, block, unroll=False):
    return _compress_unrolled(state, block) if unroll else _compress_loop(state, block)


# --- 64-byte messages: one kernel ----------------------------------------

_LANES = 128
_SUBLANES = 8
_TILE = _SUBLANES * _LANES    # hashes per (8, 128) tile, one vreg a word


def _pad_round_constants() -> np.ndarray:
    """K[t] + W[t] for the fixed schedule of the padding block `_PAD64`."""
    def rotr(x, n):
        return ((x >> n) | (x << (32 - n))) & 0xFFFFFFFF

    w = [int(x) for x in _PAD64]
    for t in range(16, 64):
        s0 = rotr(w[t - 15], 7) ^ rotr(w[t - 15], 18) ^ (w[t - 15] >> 3)
        s1 = rotr(w[t - 2], 17) ^ rotr(w[t - 2], 19) ^ (w[t - 2] >> 10)
        w.append((w[t - 16] + s0 + w[t - 7] + s1) & 0xFFFFFFFF)
    return np.array([(int(k) + x) & 0xFFFFFFFF for k, x in zip(_K, w)],
                    dtype=np.uint32)


_PAD_KW_np = _pad_round_constants()
# 32-bit operations of `_hash64_body` per hash: 64 data rounds of 36
# (35 and K + W) with 64 schedule words of 21, 64 padding rounds of 35,
# and the two 8-word feed-forwards
_OPS_PER_HASH = 64 * (36 + 21) + 64 * 35 + 16

# trace-time counts of the kernel's seam (`kernel_stats`)
_KERNEL_STATS = {"hashes": 0, "padded_lanes": 0}


def kernel_stats() -> dict:
    """64-byte hashes asked of the kernel and the tile lanes padded to
    whole tiles, counted at trace time at the kernel's seam: a traced
    (or eager) call adds its counts once for each time one run of the
    program runs it (once, or once per level of `zero_ladder`'s loop)."""
    return dict(_KERNEL_STATS)


# The kernel's body is written in lax primitives, which trace several
# times faster than jnp's operators: with jnp the 2**20 epoch step's JAX
# trace and lowering took 10.2 s on a v5e host, with lax 4.6 s.
_add, _xor, _and, _or = lax.add, lax.bitwise_xor, lax.bitwise_and, lax.bitwise_or


def _rotr32(x, n):
    return _or(lax.shift_right_logical(x, np.uint32(n)),
               lax.shift_left(x, np.uint32(32 - n)))


def _sigma0(x):
    return _xor(_xor(_rotr32(x, 7), _rotr32(x, 18)),
                lax.shift_right_logical(x, np.uint32(3)))


def _sigma1(x):
    return _xor(_xor(_rotr32(x, 17), _rotr32(x, 19)),
                lax.shift_right_logical(x, np.uint32(10)))


def _kernel_round(regs, kw):
    """One round with K[t] + W[t] summed: `_round` with 3-op Ch and 4-op
    Maj."""
    a, b, c, d, e, f, g, h = regs
    s1 = _xor(_xor(_rotr32(e, 6), _rotr32(e, 11)), _rotr32(e, 25))
    ch = _xor(g, _and(e, _xor(f, g)))
    t1 = _add(_add(h, s1), ch) + kw
    s0 = _xor(_xor(_rotr32(a, 2), _rotr32(a, 13)), _rotr32(a, 22))
    maj = _or(_and(a, b), _and(c, _or(a, b)))
    return _add(t1, _add(s0, maj)), a, b, c, _add(d, t1), e, f, g


def _hash64_body(words, k, kw):
    """SHA-256 of one 64-byte message per element: `words` its 16 words
    (arrays of one shape), `k` the 64 round constants and `kw` the
    padding block's K + W (arrays, or SMEM refs in the kernel).  Each
    compression is 4 iterations of 16 unrolled rounds; window word j is
    rewritten in place with W[t+16] once round t has used it, so every
    index into the window is static."""
    iv = tuple(jnp.full(words[0].shape, int(v), jnp.uint32) for v in _IV_np)

    def data_rounds(i, carry):
        regs, w = carry
        w = list(w)
        for j in range(16):
            regs = _kernel_round(regs, w[j] + k[16 * i + j])
            w[j] = _add(_add(w[j], _sigma0(w[(j + 1) % 16])),
                        _add(w[(j + 9) % 16], _sigma1(w[(j + 14) % 16])))
        return regs, tuple(w)

    def pad_rounds(i, regs):
        for j in range(16):
            regs = _kernel_round(regs, kw[16 * i + j])
        return regs

    four = (jnp.int32(0), jnp.int32(4))
    regs, _ = lax.fori_loop(*four, data_rounds, (iv, tuple(words)))
    mid = tuple(map(_add, iv, regs))
    regs = lax.fori_loop(*four, pad_rounds, mid)
    return tuple(map(_add, mid, regs))


@jax.jit
def _hash64_tile(k, kw, *words):
    """`_hash64_body` on one (8, 128) tile.  Jitted so that its trace is
    made once per process (once for SMEM refs, once for arrays) and every
    level shape's kernel and jnp branch reuse it."""
    return _hash64_body(words, k, kw)


def _hash64_kernel(k_ref, kw_ref, blk_ref, out_ref):
    digest = _hash64_tile(k_ref, kw_ref, *[blk_ref[j] for j in range(16)])
    for j, word in enumerate(digest):
        out_ref[j] = word


def _table_index(r):
    return (jnp.int32(0),)    # index maps stay int32 under x64


def _rows_index(r):
    return (jnp.int32(0), r, jnp.int32(0))


def _hash64_pallas(k, kw, blocks):
    rows = blocks.shape[1]
    table = pl.BlockSpec((64,), _table_index, memory_space=pltpu.SMEM)
    return pl.pallas_call(
        _hash64_kernel,
        out_shape=jax.ShapeDtypeStruct((8, rows, _LANES), jnp.uint32),
        grid=(rows // _SUBLANES,),
        in_specs=[table, table,
                  pl.BlockSpec((16, _SUBLANES, _LANES), _rows_index)],
        out_specs=pl.BlockSpec((8, _SUBLANES, _LANES), _rows_index),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        name="sha256_64B",
    )(k, kw, blocks)


def _hash64_jnp(k, kw, blocks):
    tiles = blocks.reshape(16, -1, _SUBLANES, _LANES).transpose(1, 0, 2, 3)
    digests = lax.map(lambda t: jnp.stack(_hash64_tile(k, kw, *t)), tiles)
    return digests.transpose(1, 0, 2, 3).reshape(8, -1, _LANES)


@jax.jit
def _hash64_tiles(blocks):
    """(16, R, 128) message planes -> (8, R, 128) digest planes, R a
    multiple of 8: the kernel where the program is lowered for a TPU,
    the same body in jnp elsewhere."""
    return lax.platform_dependent(
        jnp.asarray(_K_np, dtype=jnp.uint32), jnp.asarray(_PAD_KW_np),
        blocks, tpu=_hash64_pallas, default=_hash64_jnp)


def _hash_planes(blocks, runs: int):
    """The kernel's one seam: (16, M) message planes -> (8, M) digest
    planes, M padded to whole (8, 128) tiles.  Counts M hashes and the
    padded lanes (`kernel_stats`) `runs` times: the times the traced call
    runs per run of the program."""
    m = blocks.shape[1]
    rows = -(-m // _TILE) * _SUBLANES
    lanes = rows * _LANES
    _KERNEL_STATS["hashes"] += m * runs
    _KERNEL_STATS["padded_lanes"] += (lanes - m) * runs
    # cost-capture seam: XLA's cost analysis cannot see into the kernel,
    # so its operations and bytes are recorded from its shape
    costmodel.record_cost(f"sha256_64B@r{rows}",
                          flops=_OPS_PER_HASH * lanes,
                          bytes_accessed=(16 + 8) * 4 * lanes)
    with telemetry.span("sha256.kernel.trace", hashes=m, rows=rows):
        if lanes > m:
            blocks = jnp.pad(blocks, ((0, 0), (0, lanes - m)))
        digests = _hash64_tiles(blocks.reshape(16, rows, _LANES))
        return digests.reshape(8, lanes)[:, :m]


def sha256_64B_planes(blocks):
    """SHA-256 of M 64-byte messages held as word planes: (16, M) uint32,
    word j of message m at [j, m] -> (8, M) digest planes."""
    return _hash_planes(blocks, 1)


def zero_ladder(root, from_depth: int, depth: int):
    """Fold the zero-subtree hashes of levels [from_depth, depth) over a
    (8,) root: one kernel call, run once per level in a loop."""
    runs = depth - from_depth
    if runs <= 0:
        return root
    zeros = jnp.asarray(_ZEROS_np)

    def level(d, node):
        blk = jnp.concatenate([node, zeros[d]])
        return _hash_planes(blk[:, None], runs)[:, 0]

    return lax.fori_loop(jnp.int32(from_depth), jnp.int32(depth), level,
                         root)


def sha256_64B_words(blocks):
    """SHA-256 of (..., 16)-word 64-byte messages -> (..., 8)-word digests."""
    lead = blocks.shape[:-1]
    planes = blocks.reshape(-1, 16).T
    return sha256_64B_planes(planes).T.reshape(lead + (8,))


def _pair_planes(level):
    """One Merkle level's sibling pairs as blocks: (8, 2N) node planes ->
    (16, N) message planes, the left child's words over the right's."""
    return level.reshape(8, -1, 2).transpose(2, 0, 1).reshape(16, -1)


def reduce_planes(level, levels: int):
    """`levels` Merkle levels over (8, N) node planes."""
    for _ in range(levels):
        level = sha256_64B_planes(_pair_planes(level))
    return level


@partial(jax.jit, static_argnames=("depth",))
def merkle_root_pow2(words, depth: int):
    """Root of a full 2**depth-leaf tree given as (2**depth, 8) uint32 words.

    One kernel call per level over word planes, the whole reduction a
    single device dispatch."""
    assert words.shape[0] == 1 << depth
    return reduce_planes(words.T, depth)[:, 0]


def _fold_zero_levels(root: np.ndarray, depth: int,
                      limit_depth: int) -> np.ndarray:
    """Host-side tail of a merkleization: fold precomputed zero-subtree
    hashes over a (8,) uint32 root up to `limit_depth`.  Runs at settle
    time on the fetched root."""
    for lvl in range(depth, limit_depth):
        blk = np.concatenate([root, ZERO_HASH_WORDS[lvl]]).astype(np.uint32)
        root = _host_sha256_64B(blk[None, :])[0]
    return root


def merkleize_words_jax_async(words: np.ndarray, limit_depth: int):
    """Device-side equivalent of sha256_np.merkleize_words, deferred.

    Pads the actual chunks to the next power of two on host (zero
    chunks), dispatches the device reduction, and returns a
    `serve.futures.DeviceFuture` settling to (8,) uint32 root words —
    the root crosses to the host (and the zero-subtree fold runs) only
    at `result()`, so callers can merkleize many subtrees back-to-back
    without serializing the dispatch pipeline."""
    from ..serve.futures import DeviceFuture, value_future

    n = words.shape[0]
    assert n <= (1 << limit_depth)
    if n == 0:
        return DeviceFuture.settled(
            np.array(ZERO_HASH_WORDS[limit_depth], copy=True))
    d = max(n - 1, 0).bit_length()
    # resilience fault seam (same contract as bls_batch._dispatch —
    # this module dispatches its own kernel, so it hooks its own key)
    if faults.active():
        faults.maybe_inject("dispatch", f"sha256_merkle@d{d}")
    padded = np.zeros((1 << d, 8), dtype=np.uint32)
    padded[:n] = words
    with telemetry.span("sha256.merkleize_words", depth=d):
        dev_words = jnp.asarray(padded)
        # cst: allow(recompile-unbucketed-dim): the static tree depth keys
        # the executable — log-bounded (<= limit_depth distinct compiles),
        # and each depth's program is one kernel call per level
        root = merkle_root_pow2(dev_words, d)
    # cost-capture seam (CST_COSTMODEL rounds): flop/byte budget of the
    # depth-d reduction, once per depth per process — outside the span
    # so the AOT analysis pass does not contaminate the measured wall
    costmodel.capture(f"sha256_merkle@d{d}", merkle_root_pow2,
                      (dev_words, d))
    if faults.active():
        root = faults.corrupt("dispatch", f"sha256_merkle@d{d}", root)
    return value_future(
        root, convert=lambda host: _fold_zero_levels(host, d, limit_depth))


def merkleize_words_jax(words: np.ndarray, limit_depth: int) -> np.ndarray:
    """Synchronous facade over `merkleize_words_jax_async` (the host
    API boundary of the device reduction); the root fetch lives in
    `serve.futures`."""
    return merkleize_words_jax_async(words, limit_depth).result()
