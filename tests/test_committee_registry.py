"""The device-resident pubkey registry and the committee aggregation
program (`ops/bls_batch/registry.py`), and committee submits through the
serve executor, on the CPU at small sizes against the benchmark's plain
reference (`benchmark/reference/committees.py`): a registry of 2**10 keys,
committees of 16 and 64, seeded bits with all, one and none set.

The RLC program itself is the unchanged `rlc_verify_h2c`, whose device
compile is too slow for tier 1 (its parity tests carry `slow`); here a
host stand-in takes its place, computing the same random-linear-combination
check on the oracle from the very arrays the program would get — the
committee aggregation program's device output among them.
"""

from __future__ import annotations

import random

import numpy as np
import pytest

from benchmark.reference import committees as ref
from consensus_specs_tpu.ops import bls_batch
from consensus_specs_tpu.ops.bls import ciphersuite, curve
from consensus_specs_tpu.ops.bls.hash_to_curve import DST_G2, hash_to_g2
from consensus_specs_tpu.ops.bls_batch import curve_jax as cj
from consensus_specs_tpu.ops.bls_batch import fq, tower
from consensus_specs_tpu.ops.bls_batch.registry import (
    PubkeyRegistry,
    decode_bitlist,
)
from consensus_specs_tpu.serve.executor import ServeExecutor, _oracle_compute

SEED = 2**31 + 11
N_KEYS = 1 << 10
LAYOUTS = {16: (32, 2), 64: (4, 4)}     # size -> (slots, per_slot)
LANES = 8


@pytest.fixture(scope="module")
def coords():
    return ref.make_registry(SEED, N_KEYS)


@pytest.fixture(scope="module", params=sorted(LAYOUTS))
def registry(request, coords):
    slots, per_slot = LAYOUTS[request.param]
    table = ref.make_committees(SEED, N_KEYS, slots, per_slot, request.param)
    return PubkeyRegistry(coords, table)


def _affine(row) -> tuple:
    return tuple(int.from_bytes(c.tobytes(), "big") for c in row)


def _reference_sum(reg, committee_id, bits):
    """The reference's own sum of the selected members' points."""
    acc = ref.bls.g1.infinity()
    for m in reg.members[committee_id][bits]:
        acc = ref.bls.g1.add(acc, _affine(reg.coords[m]) + (1,))
    return ref.bls.g1.to_affine(acc)


# --- bits ---------------------------------------------------------------------


@pytest.mark.parametrize("bits", [[True] * 16, [False] * 15 + [True],
                                  [False] * 16, [True, False] * 8,
                                  [True] * 13])
def test_bitlist_decodes_what_the_reference_encodes(bits):
    got = decode_bitlist(ref.encode_bitlist(bits), len(bits))
    assert got.tolist() == bits
    assert ref.decode_bitlist(ref.encode_bitlist(bits)) == bits


@pytest.mark.parametrize("data,length", [(b"", 16), (b"\xff\xff\x00", 16),
                                         (b"\xff\xff\x01", 15),
                                         (b"\xff\x03", 16)])
def test_bitlist_of_another_length_or_malformed_is_none(data, length):
    assert decode_bitlist(data, length) is None


# --- registry -------------------------------------------------------------------


def test_registry_limbs_are_the_montgomery_form_of_the_coordinates(
        registry, coords):
    xs, ys = np.asarray(registry.x), np.asarray(registry.y)
    assert xs.shape == ys.shape == (N_KEYS, fq.N_LIMBS)
    for i in (0, 1, 511, N_KEYS - 1):
        x, y = _affine(coords[i])
        assert (xs[i] == fq.to_mont(x)).all()
        assert (ys[i] == fq.to_mont(y)).all()


def test_registry_reads_back_the_reference_keys(registry):
    picks = [0, 7, 300, N_KEYS - 1]
    assert ref.check_registry(SEED, picks, registry.read_back(picks)) == 0


def test_registry_fill_over_several_chunks(coords, monkeypatch):
    from consensus_specs_tpu.ops.bls_batch import registry as reg_mod

    monkeypatch.setattr(reg_mod, "FILL_CHUNK", 384)     # 3 runs, one padded
    table = ref.make_committees(SEED, N_KEYS, 4, 4, 64)
    reg = reg_mod.PubkeyRegistry(coords, table)
    assert reg.x.shape == (N_KEYS, fq.N_LIMBS)
    picks = [0, 383, 384, 1000, N_KEYS - 1]
    assert ref.check_registry(SEED, picks, reg.read_back(picks)) == 0


def test_registry_refuses_a_committee_outside_it(coords):
    table = ref.make_committees(SEED, N_KEYS, 4, 4, 64)
    with pytest.raises(ValueError, match="outside"):
        PubkeyRegistry(coords[:512], table)


def test_committee_id_and_host_mirror(registry):
    assert registry.committee_id(0, registry.per_slot) is None
    assert registry.committee_id(registry.slots + 1, 1) \
        == registry.per_slot + 1
    bits = np.ones(registry.size, bool)
    want = _reference_sum(registry, 3, bits)
    assert curve.g1.to_affine(registry.host_aggregate(3, bits)) == want


# --- the aggregation program ----------------------------------------------------


@pytest.mark.parametrize("pattern", ["all", "one", "none", "seeded"])
def test_aggregation_program_sums_as_the_reference(registry, pattern):
    rng = random.Random(f"{SEED}/{pattern}")
    size, n_committees = registry.size, len(registry.members)
    ids, bits = [], []
    for _ in range(LANES - 2):
        if pattern == "all":
            b = np.ones(size, bool)
        elif pattern == "one":
            b = np.zeros(size, bool)
            b[rng.randrange(size)] = True
        elif pattern == "none":
            b = np.zeros(size, bool)
        else:
            b = np.array([rng.random() < 0.9 for _ in range(size)])
        ids.append(rng.randrange(n_committees))
        bits.append(b)
    keys = registry.select(ids, bits)
    keys.enqueue(keys.prepare(LANES))
    got = keys.points()
    assert len(got) == len(ids)
    for cid, b, pt in zip(ids, bits, got):
        want = _reference_sum(registry, cid, b)
        assert (None if pt is None else pt[:2]) == want
        if pt is not None:
            assert curve.g1.on_curve(pt)


def test_aggregation_of_keys_that_cancel_is_infinity(coords):
    """Two members whose keys are negatives of each other: every partial
    sum of the pair meets its negative, and the aggregate is infinity."""
    rows = coords.copy()
    _, y = _affine(rows[1])
    rows[1, 1] = np.frombuffer(((-y) % fq.Q).to_bytes(48, "big"), np.uint8)
    rows[0] = coords[1]
    table = np.arange(64).reshape(1, 4, 16)
    reg = PubkeyRegistry(rows[:64], table)
    bits = np.zeros(16, bool)
    bits[:2] = True
    keys = reg.select([0, 0], [bits, np.ones(16, bool)])
    keys.enqueue(keys.prepare(LANES))
    pts = keys.points()
    assert pts[0] is None
    assert pts[1][:2] == _reference_sum(reg, 0, np.ones(16, bool))


def test_pt_add_on_equal_summands_and_infinity_lanes():
    """`pt_add`'s masked selects: P + P doubles, P + (-P) and O + O are
    infinity, O + P and P + O are P, P + Q is the general sum."""
    import jax

    p = curve.g1.mul(curve.G1_GEN, 1234567)
    q = curve.g1.mul(curve.G1_GEN, 7654321)
    inf = curve.g1.infinity()
    cases = [(p, p), (p, curve.g1.neg(p)), (inf, p), (p, inf), (inf, inf),
             (p, q)]

    def limbs(points):
        return tuple(np.stack([fq.to_mont(pt[k]) for pt in points])
                     for k in range(3))

    lhs, rhs = limbs([a for a, _ in cases]), limbs([b for _, b in cases])
    out = jax.jit(lambda a, b: cj.pt_add(cj.F1, a, b))(lhs, rhs)
    out = [np.asarray(c) for c in out]
    for k, (a, b) in enumerate(cases):
        got = cj.g1_limbs_to_oracle(tuple(c[k] for c in out))
        want = curve.g1.add(a, b)
        assert curve.g1.is_inf(got) == curve.g1.is_inf(want), k
        if not curve.g1.is_inf(want):
            assert curve.g1.to_affine(got) == curve.g1.to_affine(want), k


# --- through the serve executor -------------------------------------------------


def _host_rlc(batch):
    """A host stand-in for `rlc_verify_h2c`: the same arrays in, the same
    random-linear-combination predicate on the oracle."""
    import jax.numpy as jnp

    def rlc_verify_h2c(pk_x, pk_y, sig_x, sig_y, msg_words, r_bits, mask):
        pk_x, pk_y, sig_x, sig_y, msg_words, r_bits, mask = (
            np.asarray(a) for a in (pk_x, pk_y, sig_x, sig_y, msg_words,
                                    r_bits, mask))
        by_msg, sig_sum = {}, curve.g2.infinity()
        for i in np.flatnonzero(mask):
            r = int("".join(str(int(b)) for b in r_bits[i]), 2)
            pk = (fq.from_mont(pk_x[i]), fq.from_mont(pk_y[i]), 1)
            sig = (tower.fq2_to_oracle(sig_x[i]),
                   tower.fq2_to_oracle(sig_y[i]), curve.g2.F_one)
            msg = msg_words[i].astype(">u4").tobytes()
            by_msg[msg] = curve.g1.add(by_msg.get(msg, curve.g1.infinity()),
                                       curve.g1.mul(pk, r))
            sig_sum = curve.g2.add(sig_sum, curve.g2.mul(sig, r))
        pairs = [(pk, hash_to_g2(m, DST_G2)) for m, pk in by_msg.items()]
        pairs.append((curve.g1.neg(curve.G1_GEN), sig_sum))
        return jnp.asarray(ciphersuite._pairing_check(pairs))

    return rlc_verify_h2c


@pytest.fixture
def host_pairings(monkeypatch):
    monkeypatch.setattr(bls_batch, "_rlc_kernel_h2c", _host_rlc)
    monkeypatch.setattr(bls_batch, "pairing_check_device",
                        ciphersuite._pairing_check)


@pytest.fixture(scope="module")
def small():
    """A registry with committees of 16, and 2 committees' 4 aggregates
    each, made by the reference."""
    coords = ref.make_registry(SEED, N_KEYS)
    table = ref.make_committees(SEED, N_KEYS, 32, 2, 16)
    stmts = ref.make_statements(SEED, table, [(0, 0), (0, 1)], 4, 0.97, 0.03)
    return PubkeyRegistry(coords, table), table, coords, stmts


def _submit(ex, stmts):
    return [ex.submit_committee_aggregate_verify(*s) for s in stmts]


def test_committee_submits_match_the_reference(small, host_pairings):
    reg, table, coords, stmts = small
    ex = ServeExecutor(max_batch=LANES, depth=1, registry=reg)
    futs = _submit(ex, stmts)
    ex.drain()
    want, _ = ref.verify_all(stmts, table, coords)
    assert want == [True] * len(stmts)
    assert [f.result() for f in futs] == want
    st = ex.stats()
    assert st["batches"] == 1 and st["rechecks"] == 0
    assert st["keys_aggregated"] == sum(
        sum(ref.decode_bitlist(s[2])) for s in stmts)


@pytest.mark.parametrize("how", ref.TAMPERINGS)
def test_probe_tampering_is_refused_and_rechecked_per_statement(
        small, host_pairings, how):
    reg, table, coords, stmts = small
    bad, where = ref.tamper(stmts, how, 1, random.Random(how), 2)
    want, _ = ref.verify_all(bad, table, coords)
    assert [i for i, ok in enumerate(want) if not ok] == where
    # the two programs refuse the batch
    tasks, ids, bits = [], [], []
    for slot, index, bitlist, msg, sig in bad:
        tasks.append((None, msg, curve.g2_from_bytes(sig)))
        ids.append(reg.committee_id(slot, index))
        bits.append(decode_bitlist(bitlist, reg.size))
    assert bls_batch.batch_verify_async(
        tasks, pubkeys=reg.select(ids, bits)).result() is False
    # the executor gives each statement its own verdict
    ex = ServeExecutor(max_batch=LANES, depth=1, registry=reg)
    futs = _submit(ex, bad)
    ex.drain()
    assert [f.result() for f in futs] == want
    assert ex.stats()["rechecks"] == 1


def test_aggregate_at_infinity_is_refused(small, host_pairings):
    """No bit set reaches the programs only past the submit, which
    refuses it; the batch verdict refuses it too, whatever the RLC
    program says of its lane."""
    reg, _, _, stmts = small
    slot, index, bitlist, msg, sig = stmts[0]
    none = np.zeros(reg.size, bool)
    out = bls_batch.batch_verify_async(
        [(None, msg, curve.g2_from_bytes(sig))],
        pubkeys=reg.select([reg.committee_id(slot, index)], [none]))
    assert out.result() is False


def test_eager_rejects_settle_false(small, host_pairings):
    reg, _, _, stmts = small
    slot, index, bitlist, msg, sig = stmts[0]
    ex = ServeExecutor(max_batch=LANES, depth=1, registry=reg)
    bits = ref.decode_bitlist(bitlist)
    cases = [
        (slot, reg.per_slot, bitlist, msg, sig),           # no committee
        (slot, index, ref.encode_bitlist(bits[:-1]), msg, sig),  # length
        (slot, index, ref.encode_bitlist([False] * len(bits)), msg, sig),
        (slot, index, bitlist, msg, b"\x00" * 96),         # bad signature
        (slot, index, bitlist, msg,
         curve.g2_to_bytes(curve.g2.infinity())),          # infinity
    ]
    futs = _submit(ex, cases)
    assert all(f.done() and f.result() is False for f in futs)
    assert ex.stats()["submitted"] == 0


def test_committee_submit_needs_a_registry(small):
    _, _, _, stmts = small
    with pytest.raises(ValueError, match="registry"):
        ServeExecutor().submit_committee_aggregate_verify(*stmts[0])


def test_oracle_fallback_matches_the_reference(small):
    reg, table, coords, stmts = small
    bad, _ = ref.tamper(stmts, "flipped_bit", 0, random.Random(3), 2)
    for s, want in zip(bad[:2], ref.verify_all(bad[:2], table, coords)[0]):
        slot, index, bitlist, msg, sig = s
        payload = (reg, reg.committee_id(slot, index),
                   decode_bitlist(bitlist, reg.size), msg,
                   curve.g2_from_bytes(sig))
        assert _oracle_compute("committee", payload) is want
