"""Device-resident pubkey registry and the committee aggregation program.

A beacon node checks a `beacon_aggregate_and_proof` aggregate by
FastAggregateVerify over the committee members its `aggregation_bits`
name (`get_attesting_indices`), with the members' keys taken from its own
cache of decompressed, validated pubkeys, never from the wire.
`PubkeyRegistry` is that cache on the device:

  - every key as affine Montgomery limbs, x and y each (N, 33) int32
    (2**20 keys: 277 MB);
  - the epoch's committee table, (slots * committees_per_slot, size)
    int32 validator indices (32 x 64 x 512: 4 MB).

It fills from raw affine coordinates, (N, 2, 48) uint8 big-endian x and
y as a node's key database holds them, converted to limbs on the device
(`registry_fill`); the raw array stays on the host as the registry's
mirror, which the executor's oracle fallback sums.  Keys are taken as
validated: KeyValidate runs once per key, at deposit, not here.

The committee aggregation program (`pk_aggregate`), for B statements:
gather each statement's committee row from the table and its members'
keys from the registry, set the lanes of unset bits to infinity
(`curve_jax.pt_select`, `pt_infinity`), sum over the key axis with
`curve_jax.pt_sum`'s log-depth tree, and convert the sums to affine.  Its
output stays on the device and feeds the RLC kernel as its pk_x, pk_y
(`bls_batch.batch_verify_async(..., pubkeys=CommitteeKeys)`), dispatched
just before it on the same in-order queue.

The tree adds equal partial sums and infinity lanes correctly, with no
path of its own: `curve_jax.pt_add` computes the general sum and then
selects, lane by lane, the doubling where H == 0 and r == 0 (the summands
are equal), infinity where H == 0 and r != 0 (they are negatives), and
the other summand where one is infinity (Z == 0).  So unset bits, padding
lanes, whole subtrees of infinity, and two equal halves all sum exactly;
an aggregate that is itself infinity (no bit set, or keys that cancel)
comes out with its `inf` flag set, and the verdict on it is False, as
KeyValidate of the aggregate requires.
"""

from __future__ import annotations

import functools

import numpy as np

from ... import telemetry
from ...serve.futures import value_future
from ...utils.jaxtools import jit
from ..bls import curve as _pycurve
from . import _bucket, _dispatch, g1_to_affine_dev
from . import curve_jax as cj
from . import fq as _fq

COORD_BYTES = 48
# keys converted per run of the fill program
FILL_CHUNK = 1 << 16
# R**2 mod q as plain limbs: a Montgomery product with it maps x to x*R
_R2_LIMBS = _fq.int_to_limbs(_fq.R_MONT * _fq.R_MONT % _fq.Q)


def _jnp():
    import jax.numpy as jnp
    return jnp


def decode_bitlist(data, length: int):
    """SSZ `Bitlist` bytes -> bool array of `length` bits, or None where
    the encoding is malformed or its length is not `length` (the spec's
    `len(aggregation_bits) == len(committee)`)."""
    raw = np.frombuffer(bytes(data), dtype=np.uint8)
    if raw.size == 0 or raw[-1] == 0:
        return None             # no delimiter bit in the last byte
    bits = np.unpackbits(raw, bitorder="little")
    n = bits.size - 1 - int(np.argmax(bits[::-1]))   # the delimiter
    if n != length:
        return None
    return bits[:n].astype(bool)


@functools.lru_cache(maxsize=4)
def _fill_kernel(chunk: int):
    """Raw big-endian affine coordinates -> canonical Montgomery limbs."""
    jnp = _jnp()

    def registry_fill(raw):
        lead = raw.shape[:-1]
        le = raw[..., ::-1].astype(jnp.int32).reshape(lead + (16, 3))
        lo = le[..., 0] | ((le[..., 1] & 0xF) << 8)
        hi = (le[..., 1] >> 4) | (le[..., 2] << 4)
        limbs = jnp.stack([lo, hi], axis=-1).reshape(lead + (32,))
        limbs = jnp.concatenate(
            [limbs, jnp.zeros(lead + (1,), jnp.int32)], axis=-1)
        mont = _fq.fq_canon(_fq.fq_mul(limbs, jnp.asarray(_R2_LIMBS)))
        return mont[:, 0], mont[:, 1]

    return jit(registry_fill)


@functools.lru_cache(maxsize=16)
def _pk_aggregate_kernel(batch: int, size: int):
    """The committee aggregation program for `batch` statements over
    committees of `size` members."""
    import jax
    jnp = _jnp()

    def pk_aggregate(reg_x, reg_y, table, committee_ids, bits):
        with jax.named_scope("cst.pk_aggregate"):
            idx = table[committee_ids].T            # (size, batch)
            x, y = reg_x[idx], reg_y[idx]           # (size, batch, 33)
            one = jnp.broadcast_to(jnp.asarray(_fq.ONE_MONT), x.shape)
            keys = (x, y, one)
            keys = cj.pt_select(cj.F1, bits.T, keys,
                                cj.pt_infinity(cj.F1, keys))
            return g1_to_affine_dev(cj.pt_sum(cj.F1, keys, size))

    return jit(pk_aggregate)


class PubkeyRegistry:
    """All validators' keys and one epoch's committees on the device.

    coords: (N, 2, 48) uint8, key i's affine x and y, big-endian.
    committees: (slots, committees_per_slot, size) validator indices;
    every committee has `size` members.  A statement names its committee
    by (slot, committee index), the slot taken modulo `slots`."""

    def __init__(self, coords, committees):
        jnp = _jnp()
        coords = np.ascontiguousarray(coords, dtype=np.uint8)
        if coords.ndim != 3 or coords.shape[1:] != (2, COORD_BYTES):
            raise ValueError(f"coords must be (N, 2, {COORD_BYTES}) bytes, "
                             f"not {coords.shape}")
        table = np.asarray(committees)
        if table.ndim != 3:
            raise ValueError("committees must be (slots, per_slot, size)")
        n = coords.shape[0]
        if table.size and (table.min() < 0 or table.max() >= n):
            raise ValueError("a committee names a validator outside the "
                             "registry")
        self.coords = coords
        self.slots, self.per_slot, self.size = table.shape
        self.members = table.reshape(-1, self.size).astype(np.int32)
        with telemetry.span("bls.registry_fill", keys=n):
            self.x, self.y = self._fill(coords)
            self.table = jnp.asarray(self.members)

    @staticmethod
    def _fill(coords):
        jnp = _jnp()
        n = coords.shape[0]
        chunk = min(FILL_CHUNK, n)
        # cst: allow(recompile-unbucketed-dim): one chunk shape per
        # registry size, set once per node at set-up, not per batch
        kernel = _fill_kernel(chunk)
        xs, ys = [], []
        for lo in range(0, n, chunk):
            part = coords[lo:lo + chunk]
            if part.shape[0] < chunk:
                part = np.concatenate([part, np.zeros(
                    (chunk - part.shape[0],) + part.shape[1:], np.uint8)])
            x, y = kernel(jnp.asarray(part))
            xs.append(x)
            ys.append(y)
        if len(xs) == 1:
            return xs[0][:n], ys[0][:n]
        return jnp.concatenate(xs)[:n], jnp.concatenate(ys)[:n]

    def committee_id(self, slot: int, committee_index: int):
        """Row of the committee table, or None where the index is out of
        range for the slot."""
        if not 0 <= committee_index < self.per_slot:
            return None
        return (slot % self.slots) * self.per_slot + committee_index

    def host_point(self, i: int):
        """Key i from the host mirror, as an oracle Jacobian point."""
        x, y = (int.from_bytes(c.tobytes(), "big") for c in self.coords[i])
        return (x, y, 1)

    def host_aggregate(self, committee_id: int, bits):
        """The pure-Python sum of the keys `bits` selects from the host
        mirror (the oracle fallback's aggregation)."""
        acc = _pycurve.g1.infinity()
        for i in self.members[committee_id][np.asarray(bits, dtype=bool)]:
            acc = _pycurve.g1.add(acc, self.host_point(int(i)))
        return acc

    def read_back(self, indices) -> list:
        """Keys `indices` as the device holds them, (x, y) ints each."""
        jnp = _jnp()
        idx = jnp.asarray(np.asarray(indices, dtype=np.int32))
        x, y = value_future((self.x[idx], self.y[idx])).result()
        return [(_fq.from_mont(a), _fq.from_mont(b)) for a, b in zip(x, y)]

    def select(self, committee_ids, bits) -> "CommitteeKeys":
        return CommitteeKeys(self, committee_ids, bits)


class CommitteeKeys:
    """The pubkey side of one batch of committee statements: the committee
    each names and its bits.  `batch_verify_async` calls `prepare` inside
    its `bls.prepare` span and `enqueue` inside its `bls.enqueue` span;
    the aggregates stay on the device (`device`) for the verdict, and a
    recheck reads them back (`points`)."""

    def __init__(self, registry: PubkeyRegistry, committee_ids, bits):
        self.registry = registry
        self.committee_ids = list(committee_ids)
        self.bits = [np.asarray(b, dtype=bool) for b in bits]
        if len(self.bits) != len(self.committee_ids):
            raise ValueError("one bit vector per statement")
        self.device = None

    def __len__(self) -> int:
        return len(self.committee_ids)

    def prepare(self, lanes: int):
        """Committee rows and bit masks padded to `lanes`, by repeating
        statement 0 (the RLC kernel masks padding lanes out)."""
        pad = lanes - len(self)
        ids = np.asarray(self.committee_ids + self.committee_ids[:1] * pad,
                         dtype=np.int32)
        return ids, np.stack(self.bits + self.bits[:1] * pad)

    def enqueue(self, host, block: bool = True):
        """Dispatch the aggregation program; returns (x, y, inf) on the
        device, B lanes each."""
        jnp = _jnp()
        ids, bits = host
        lanes = _bucket(len(ids))       # prepare() padded to a rung
        reg = self.registry
        self.device = _dispatch(
            f"pk_aggregate@{lanes}", _pk_aggregate_kernel(lanes, reg.size),
            (reg.x, reg.y, reg.table, jnp.asarray(ids), jnp.asarray(bits)),
            block=block)
        return self.device

    def points(self) -> list:
        """Each statement's aggregate key read back from the device, an
        oracle Jacobian point, or None where it is infinity."""
        x, y, inf = value_future(self.device).result()
        return [None if inf[i] else
                (_fq.from_mont(x[i]), _fq.from_mont(y[i]), 1)
                for i in range(len(self))]
