"""Committee aggregates over a pubkey registry, from a seed, and the
spec's verdict on them.

The registry: validator i's secret key is sk_i = a + i * d mod r, with a
and d drawn from the seed, so key i is key i - 1 plus D = d * G1: one
point addition per key.  The keys leave as raw affine coordinates,
(N, 2, 48) uint8 big-endian x and y, the form a node's key database
holds.  `registry_point` computes any key alone, by a scalar
multiplication, to check the chain and what the system made of it.

The committees: one epoch's, a seeded permutation of the validators cut
into slots x committees_per_slot committees of `size` members (not the
spec's swap-or-not shuffle, which is not what is measured).

The statements: per committee, one message (its AttestationData root)
and `per_committee` aggregates of it.  Each member attested with
probability `p_attest`, one draw per committee that all its aggregates
share; each aggregate then misses each attester with probability
`p_miss`.  An aggregate's signature is (sum of sk_i over its set bits mod
r) * H(m), computed as |S| * (a H(m)) + (sum of i over S) * (d H(m)): two
short scalar multiplications per aggregate beside two full ones per
committee.  No two aggregates of a committee share their bits, and no
aggregate is empty.  A statement is (slot, committee index, SSZ Bitlist
bytes, message, compressed signature).

`verify` is the spec's verdict: FastAggregateVerify over the committee
members the bits name (`get_attesting_indices`), the members' points
added here, with this package's own point arithmetic, then the pairing.
The keys are taken as validated, as a node validates each once, at
deposit.  Generation and verification run in worker processes started
with `spawn`, which import this module and nothing of JAX.
"""

from __future__ import annotations

import hashlib
import random

import numpy as np

from . import bls
from .bls.fields import Q
from .statements import _spawn_pool

COORD_BYTES = 48
TAMPERINGS = ("flipped_bit", "wrong_committee", "swapped_signatures")


def _rng(seed: int, *what) -> random.Random:
    key = "/".join(str(w) for w in (seed,) + what)
    return random.Random(hashlib.sha256(key.encode()).digest())


def registry_scalars(seed: int) -> tuple[int, int]:
    """(a, d): sk_i = a + i * d mod r."""
    rng = _rng(seed, "registry")
    return rng.randrange(1, bls.R), rng.randrange(1, bls.R)


def secret_key(seed: int, i: int) -> int:
    a, d = registry_scalars(seed)
    return (a + i * d) % bls.R


def registry_point(seed: int, i: int) -> tuple[int, int]:
    """Key i's affine (x, y), by one scalar multiplication."""
    return bls.g1.to_affine(bls.g1.mul(bls.G1_GEN, secret_key(seed, i)))


def _registry_chunk(args) -> bytes:
    """Keys first .. first + count - 1 as 96 bytes each: the chain of
    additions, then one shared inversion for all of them."""
    seed, first, count = args
    a, d = registry_scalars(seed)
    step = bls.g1.mul(bls.G1_GEN, d)
    p = bls.g1.mul(bls.G1_GEN, (a + first * d) % bls.R)
    points = [p]
    for _ in range(count - 1):
        p = bls.g1.add(p, step)
        points.append(p)
    zs = [pt[2] % Q for pt in points]
    if not all(zs):
        raise ValueError("a registry key is the point at infinity")
    prefix, acc = [], 1
    for z in zs:
        prefix.append(acc)
        acc = acc * z % Q
    inv = pow(acc, -1, Q)
    out = [b""] * count
    for k in range(count - 1, -1, -1):
        zi = inv * prefix[k] % Q          # 1 / z_k
        inv = inv * zs[k] % Q
        zi2 = zi * zi % Q
        x, y, _ = points[k]
        out[k] = ((x * zi2 % Q).to_bytes(COORD_BYTES, "big")
                  + (y * zi2 * zi % Q).to_bytes(COORD_BYTES, "big"))
    return b"".join(out)


def make_registry(seed: int, n: int, workers: int = 1) -> np.ndarray:
    """Keys 0 .. n - 1, (n, 2, 48) uint8; with `workers` > 1 the chain is
    cut into pieces that that many spawned processes make."""
    step = max(1, -(-n // (4 * max(1, workers))))
    jobs = [(seed, i, min(step, n - i)) for i in range(0, n, step)]
    if workers <= 1 or len(jobs) < 2:
        parts = [_registry_chunk(j) for j in jobs]
    else:
        with _spawn_pool(workers) as pool:
            parts = list(pool.map(_registry_chunk, jobs))
    return np.frombuffer(b"".join(parts), dtype=np.uint8).reshape(
        n, 2, COORD_BYTES)


def make_committees(seed: int, n: int, slots: int, per_slot: int,
                    size: int) -> np.ndarray:
    """One epoch's committees, (slots, per_slot, size) validator indices,
    from a seeded permutation of the n validators."""
    if slots * per_slot * size > n:
        raise ValueError("more committee seats than validators")
    digest = hashlib.sha256(f"{seed}/committees".encode()).digest()
    perm = np.random.default_rng(int.from_bytes(digest, "little")
                                 ).permutation(n)
    return perm[:slots * per_slot * size].reshape(slots, per_slot, size)


def encode_bitlist(bits) -> bytes:
    """SSZ `Bitlist`: the bits little-endian in each byte, then one
    delimiter bit."""
    bits = list(bits)
    out = bytearray(len(bits) // 8 + 1)
    for i, b in enumerate(bits + [True]):
        if b:
            out[i // 8] |= 1 << (i % 8)
    return bytes(out)


def decode_bitlist(data: bytes):
    """The bits of an SSZ `Bitlist`, or None where it has no delimiter."""
    if not data or data[-1] == 0:
        return None
    n = 8 * (len(data) - 1) + data[-1].bit_length() - 1
    return [bool(data[i // 8] >> (i % 8) & 1) for i in range(n)]


def committee_statements(seed: int, slot: int, index: int, members,
                         per_committee: int, p_attest: float,
                         p_miss: float) -> list:
    """The `per_committee` aggregates of committee (slot, index)."""
    rng = _rng(seed, "committee", slot, index)
    msg = rng.randbytes(32)
    attested = [rng.random() < p_attest for _ in members]
    h = bls.hash_to_g2(msg, bls.DST_G2)
    a, d = registry_scalars(seed)
    a_h, d_h = bls.g2.mul(h, a), bls.g2.mul(h, d)
    seen, out = set(), []
    while len(out) < per_committee:
        bits = tuple(att and rng.random() >= p_miss for att in attested)
        if not any(bits) or bits in seen:
            continue
        seen.add(bits)
        count = sum(bits)
        index_sum = sum(int(m) for m, b in zip(members, bits) if b)
        sig = bls.g2.add(bls.g2.mul(a_h, count), bls.g2.mul(d_h, index_sum))
        out.append((slot, index, encode_bitlist(bits), msg,
                    bls.g2_to_bytes(sig)))
    return out


def _statements_chunk(args) -> list:
    seed, jobs, per_committee, p_attest, p_miss = args
    out = []
    for slot, index, members in jobs:
        out.extend(committee_statements(seed, slot, index, members,
                                        per_committee, p_attest, p_miss))
    return out


def make_statements(seed: int, table: np.ndarray, committees,
                    per_committee: int, p_attest: float, p_miss: float,
                    workers: int = 1) -> list:
    """The aggregates of each (slot, index) of `committees`, in order,
    over `workers` spawned processes."""
    jobs = [(s, i, [int(m) for m in table[s, i]]) for s, i in committees]
    step = max(1, -(-len(jobs) // (4 * max(1, workers))))
    parts = [(seed, jobs[k:k + step], per_committee, p_attest, p_miss)
             for k in range(0, len(jobs), step)]
    if workers <= 1 or len(parts) < 2:
        return [s for part in map(_statements_chunk, parts) for s in part]
    with _spawn_pool(workers) as pool:
        return [s for part in pool.map(_statements_chunk, parts)
                for s in part]


def tamper(batch: list, how: str, half: int, rng: random.Random,
           per_slot: int) -> tuple[list, list[int]]:
    """A copy of `batch` made invalid in its first (`half` 0) or second
    half, and the positions changed: one aggregation bit toggled, the
    neighbouring committee named with the same bits, or two aggregates
    of one message with their signatures swapped (which a batch check
    whose coefficients are all 1 accepts)."""
    lo, hi = (0, len(batch) // 2) if half == 0 else (len(batch) // 2,
                                                     len(batch))
    out = list(batch)
    if how == "flipped_bit":
        i = rng.randrange(lo, hi)
        slot, index, bits, msg, sig = batch[i]
        bits = decode_bitlist(bits)
        k = rng.randrange(len(bits))
        bits[k] = not bits[k]
        out[i] = (slot, index, encode_bitlist(bits), msg, sig)
        return out, [i]
    if how == "wrong_committee":
        i = rng.randrange(lo, hi)
        slot, index, bits, msg, sig = batch[i]
        out[i] = (slot, (index + 1) % per_slot, bits, msg, sig)
        return out, [i]
    if how == "swapped_signatures":
        i = rng.choice([j for j in range(lo, hi - 1)
                        if batch[j][3] == batch[j + 1][3]])
        out[i] = batch[i][:4] + (batch[i + 1][4],)
        out[i + 1] = batch[i + 1][:4] + (batch[i][4],)
        return out, [i, i + 1]
    raise ValueError(f"no tampering {how!r}: one of {TAMPERINGS}")


def verify(stmt, n_members: int, keys: np.ndarray) -> bool:
    """FastAggregateVerify of `stmt` over `keys`, the (k, 2, 48) rows of
    the committee members its bits name, in order, of a committee of
    `n_members`."""
    _, _, bits, msg, signature = stmt
    bits = decode_bitlist(bits)
    if bits is None or len(bits) != n_members or not any(bits):
        return False
    agg = bls.g1.infinity()
    for row in keys:
        x, y = (int.from_bytes(c.tobytes(), "big") for c in row)
        agg = bls.g1.add(agg, (x, y, 1))
    if bls.g1.is_inf(agg):
        return False
    try:
        sig = bls.g2_from_bytes(signature)
    except ValueError:
        return False
    if not bls.subgroup_check_g2(sig):
        return False
    return bls.pairing_check([(agg, bls.hash_to_g2(msg, bls.DST_G2)),
                              (bls.g1.neg(bls.G1_GEN), sig)])


def _verify_chunk(chunk) -> list[bool]:
    return [verify(*job) for job in chunk]


def _job(stmt, table: np.ndarray, coords: np.ndarray):
    slot, index, bits, _, _ = stmt
    slots, per_slot, size = table.shape
    if not 0 <= index < per_slot:
        return stmt, -1, coords[:0]
    members = table[slot % slots, index]
    decoded = decode_bitlist(bits)
    if decoded is None or len(decoded) != size:
        return stmt, size, coords[:0]
    return stmt, size, coords[members[np.asarray(decoded, dtype=bool)]]


def verify_all(stmts: list, table: np.ndarray, coords: np.ndarray,
               workers: int = 1, during=None):
    """The reference's verdict on each statement, over `workers` spawned
    processes, and the value of `during()`, which runs in this process
    meanwhile."""
    jobs = [_job(s, table, coords) for s in stmts]
    if workers <= 1 or len(jobs) < 2 * workers:
        extra = during() if during else None
        return _verify_chunk(jobs), extra
    step = -(-len(jobs) // (2 * workers))
    with _spawn_pool(workers) as pool:
        parts = [pool.submit(_verify_chunk, jobs[i:i + step])
                 for i in range(0, len(jobs), step)]
        extra = during() if during else None
        return [v for p in parts for v in p.result()], extra


def check_registry(seed: int, indices, read, workers: int = 1) -> int:
    """How many of the keys `indices` differ between `read` ((x, y) ints
    each, as the system holds them) and `registry_point`."""
    if workers <= 1 or len(indices) < 2 * workers:
        want = [registry_point(seed, i) for i in indices]
    else:
        with _spawn_pool(workers) as pool:
            want = list(pool.map(registry_point, [seed] * len(indices),
                                 indices, chunksize=8))
    return sum(tuple(w) != tuple(r) for w, r in zip(want, read))
