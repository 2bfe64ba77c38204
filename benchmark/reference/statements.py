"""Aggregate-attestation statements for FastAggregateVerify, from a seed.

Each message stands for one AttestationData signing root, and the
statements that share it stand for the aggregates of that committee's
aggregators.  Per message: one hash to G2, one G1 and one G2 scalar
multiplication by a key drawn from the seed; statement k (k = 1, 2, ...)
carries the key k times that one, derived by point additions, as
aggregating more signers adds points.  Every statement is valid, and no two
are equal: within a message the keys differ, and the messages differ.

`tamper` makes a batch invalid in one half, in one of two ways: a
statement signed over another message, or two statements of one message
with their signatures swapped.  The second passes a batch check whose
coefficients are all 1, since the sum of the signatures is unchanged.

The encodings are the wire's: a 48-byte compressed G1 pubkey and a 96-byte
compressed G2 signature.  Generation and verification run in worker
processes started with `spawn`, which import this module and nothing of
JAX.
"""

from __future__ import annotations

import hashlib
import multiprocessing
import random
from concurrent.futures import ProcessPoolExecutor

from . import bls

Statement = tuple[bytes, bytes, bytes]      # (pubkey, message, signature)
TAMPERINGS = ("wrong_message", "swapped_signatures")


def _message_rng(seed: int, index: int) -> random.Random:
    return random.Random(hashlib.sha256(f"{seed}/{index}".encode()).digest())


def message_statements(seed: int, index: int, per_message: int
                       ) -> list[Statement]:
    """The `per_message` statements of message `index` under `seed`."""
    rng = _message_rng(seed, index)
    msg = rng.randbytes(32)
    sk = rng.randrange(1, bls.R)
    pk0 = bls.g1.mul(bls.G1_GEN, sk)
    sig0 = bls.g2.mul(bls.hash_to_g2(msg, bls.DST_G2), sk)
    out = []
    pk, sig = pk0, sig0
    for _ in range(per_message):
        out.append((bls.g1_to_bytes(pk), msg, bls.g2_to_bytes(sig)))
        pk, sig = bls.g1.add(pk, pk0), bls.g2.add(sig, sig0)
    return out


def _chunk(args) -> list[Statement]:
    seed, first, count, per_message = args
    out = []
    for index in range(first, first + count):
        out.extend(message_statements(seed, index, per_message))
    return out


def _spawn_pool(workers: int) -> ProcessPoolExecutor:
    return ProcessPoolExecutor(max_workers=workers,
                               mp_context=multiprocessing.get_context("spawn"))


def make_statements(seed: int, n_messages: int, per_message: int,
                    workers: int = 1) -> list[Statement]:
    """Messages 0 .. n_messages - 1 in order, `per_message` statements
    each; with `workers` > 1 spread over that many spawned processes."""
    if workers <= 1 or n_messages < 2 * workers:
        return _chunk((seed, 0, n_messages, per_message))
    step = -(-n_messages // (4 * workers))
    jobs = [(seed, i, min(step, n_messages - i), per_message)
            for i in range(0, n_messages, step)]
    with _spawn_pool(workers) as pool:
        return [s for part in pool.map(_chunk, jobs) for s in part]


def tamper(batch: list[Statement], how: str, half: int,
           rng: random.Random) -> tuple[list[Statement], list[int]]:
    """A copy of `batch` made invalid in its first (`half` 0) or second
    half, and the positions changed."""
    lo, hi = (0, len(batch) // 2) if half == 0 else (len(batch) // 2,
                                                     len(batch))
    out = list(batch)
    if how == "wrong_message":
        i = rng.randrange(lo, hi)
        others = sorted({m for _, m, _ in batch} - {batch[i][1]})
        out[i] = (batch[i][0], rng.choice(others), batch[i][2])
        return out, [i]
    if how == "swapped_signatures":
        i = rng.choice([j for j in range(lo, hi - 1)
                        if batch[j][1] == batch[j + 1][1]])
        out[i] = batch[i][:2] + (batch[i + 1][2],)
        out[i + 1] = batch[i + 1][:2] + (batch[i][2],)
        return out, [i, i + 1]
    raise ValueError(f"no tampering {how!r}: one of {TAMPERINGS}")


def _verify_chunk(chunk: list[Statement]) -> list[bool]:
    return [bls.FastAggregateVerify([pk], msg, sig) for pk, msg, sig in chunk]


def verify_all(stmts: list[Statement], workers: int = 1, during=None):
    """The reference's verdict on each statement, over `workers` spawned
    processes, and the value of `during()`, which runs in this process
    meanwhile."""
    if workers <= 1 or len(stmts) < 2 * workers:
        extra = during() if during else None
        return _verify_chunk(stmts), extra
    step = -(-len(stmts) // (2 * workers))
    with _spawn_pool(workers) as pool:
        parts = [pool.submit(_verify_chunk, stmts[i:i + step])
                 for i in range(0, len(stmts), step)]
        extra = during() if during else None
        return [v for p in parts for v in p.result()], extra
