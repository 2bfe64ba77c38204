"""Batched SHA-256 kernels vs hashlib ground truth (host and JAX paths)."""

import hashlib

import numpy as np
import pytest

from consensus_specs_tpu.ops import sha256_np


def _ref_parent(left: bytes, right: bytes) -> bytes:
    return hashlib.sha256(left + right).digest()


def test_sha256_64B_matches_hashlib():
    rng = np.random.default_rng(1234)
    msgs = rng.integers(0, 256, size=(33, 64), dtype=np.uint8)
    words = sha256_np.chunks_to_words(msgs.reshape(-1, 32)).reshape(-1, 16)
    got = sha256_np.words_to_chunks(sha256_np.sha256_64B_words(words))
    for i in range(msgs.shape[0]):
        assert got[i].tobytes() == hashlib.sha256(msgs[i].tobytes()).digest()


def test_zero_hashes():
    z = b"\x00" * 32
    for i in range(5):
        assert sha256_np.ZERO_HASH_BYTES[i + 1] == _ref_parent(
            sha256_np.ZERO_HASH_BYTES[i], sha256_np.ZERO_HASH_BYTES[i])
    assert sha256_np.ZERO_HASH_BYTES[0] == z


def _naive_merkle(chunks: list[bytes], limit: int) -> bytes:
    n = 1
    while n < limit:
        n *= 2
    padded = chunks + [b"\x00" * 32] * (n - len(chunks))
    while len(padded) > 1:
        padded = [_ref_parent(padded[i], padded[i + 1])
                  for i in range(0, len(padded), 2)]
    return padded[0]


def test_merkleize_chunks_bytes():
    rng = np.random.default_rng(7)
    for count, limit in [(1, 1), (2, 2), (3, 4), (5, 8), (8, 8), (5, 64),
                         (0, 4), (1, 16)]:
        chunks = [rng.integers(0, 256, 32, dtype=np.uint8).tobytes()
                  for _ in range(count)]
        got = sha256_np.merkleize_chunks_bytes(b"".join(chunks), limit)
        assert got == _naive_merkle(chunks, max(limit, 1)), (count, limit)


def test_jax_path_matches_numpy():
    from consensus_specs_tpu.ops import sha256_jax

    rng = np.random.default_rng(99)
    words = rng.integers(0, 2**32, size=(16, 8), dtype=np.uint64).astype(np.uint32)
    np_root = sha256_np.merkleize_words(words, 4)
    jx_root = sha256_jax.merkleize_words_jax(words, 4)
    assert np.array_equal(np_root, jx_root)
    # non-power-of-two + virtual limit
    np_root = sha256_np.merkleize_words(words[:5], 10)
    jx_root = sha256_jax.merkleize_words_jax(words[:5], 10)
    assert np.array_equal(np_root, jx_root)


# --- the 64-byte kernel's body (the jnp branch of `_hash64_tiles` here) ------


def _blocks_bytes(blocks: np.ndarray) -> list[bytes]:
    """(M, 16) big-endian words -> M 64-byte messages."""
    return [row.astype(">u4").tobytes() for row in blocks]


def _kernel_digests(blocks: np.ndarray) -> np.ndarray:
    """(M, 16) words through the kernel's seam as planes -> (M, 8)."""
    from consensus_specs_tpu.ops import sha256_jax

    return np.asarray(sha256_jax.sha256_64B_planes(blocks.T)).T


@pytest.mark.parametrize("m", [1, 127, 1024, 1025])
def test_kernel_matches_hashlib_and_host(m):
    rng = np.random.default_rng(27 + m)
    blocks = rng.integers(0, 2**32, size=(m, 16), dtype=np.uint64).astype(
        np.uint32)
    got = _kernel_digests(blocks)
    assert np.array_equal(got, sha256_np.sha256_64B_words(blocks))
    for i in sorted({0, m // 2, m - 1}):
        want = hashlib.sha256(_blocks_bytes(blocks[i:i + 1])[0]).digest()
        assert got[i].astype(">u4").tobytes() == want


def test_kernel_matches_hashlib_on_edge_blocks():
    """All-zero, all-ones, and each of the 512 single-bit blocks."""
    single = np.zeros((512, 16), dtype=np.uint32)
    single[np.arange(512), np.arange(512) // 32] = (
        np.uint32(1) << (31 - np.arange(512) % 32).astype(np.uint32))
    blocks = np.concatenate([np.zeros((1, 16), np.uint32),
                             np.full((1, 16), 0xFFFFFFFF, np.uint32),
                             single])
    got = _kernel_digests(blocks)
    want = [hashlib.sha256(b).digest() for b in _blocks_bytes(blocks)]
    assert [g.astype(">u4").tobytes() for g in got] == want


def test_sha256_64B_words_keeps_leading_shape():
    from consensus_specs_tpu.ops import sha256_jax

    rng = np.random.default_rng(5)
    blocks = rng.integers(0, 2**32, size=(3, 5, 16), dtype=np.uint64).astype(
        np.uint32)
    got = np.asarray(sha256_jax.sha256_64B_words(blocks))
    assert got.shape == (3, 5, 8)
    assert np.array_equal(got.reshape(-1, 8),
                          sha256_np.sha256_64B_words(blocks.reshape(-1, 16)))


def test_one_level_of_planes_matches_host_hash_pairs():
    """Sibling pairing over node planes, 1100 hashes (a tile and part of
    a second): one level equals the host's `hash_pairs_words`."""
    from consensus_specs_tpu.ops import sha256_jax

    rng = np.random.default_rng(11)
    words = rng.integers(0, 2**32, size=(2 * 1100, 8), dtype=np.uint64).astype(
        np.uint32)
    got = np.asarray(sha256_jax.reduce_planes(words.T, 1)).T
    assert np.array_equal(got, sha256_np.hash_pairs_words(words))


@pytest.mark.parametrize("ladder", [0, 3], ids=["no_ladder", "ladder"])
@pytest.mark.parametrize("depth", [0, 1, 5, 12])
def test_subtree_root_matches_host_merkleize(depth, ladder):
    from consensus_specs_tpu.parallel import merkle

    rng = np.random.default_rng(depth * 10 + ladder)
    words = rng.integers(0, 2**32, size=(1 << depth, 8),
                         dtype=np.uint64).astype(np.uint32)
    got = np.asarray(merkle.subtree_root(words, depth + ladder))
    assert np.array_equal(got, sha256_np.merkleize_words(words,
                                                         depth + ladder))


def test_kernel_stats_count_a_known_tree():
    """A 32-leaf tree under an 8-deep limit: five data levels of 16, 8, 4,
    2 and 1 hashes, then three ladder hashes, each call padded to one
    (8, 128) tile of 1024 lanes."""
    from consensus_specs_tpu.ops import sha256_jax
    from consensus_specs_tpu.parallel import merkle

    words = np.arange(32 * 8, dtype=np.uint32).reshape(32, 8)
    before = sha256_jax.kernel_stats()
    root = merkle.subtree_root(words, 8)
    after = sha256_jax.kernel_stats()
    assert np.array_equal(np.asarray(root), sha256_np.merkleize_words(words, 8))
    assert after["hashes"] - before["hashes"] == 31 + 3
    assert after["padded_lanes"] - before["padded_lanes"] == (
        8 * 1024 - (31 + 3))
