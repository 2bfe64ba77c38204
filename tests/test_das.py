"""DAS subsystem tests — kernel-vs-oracle bit-exactness, the rung
ladder, the async facade contract, and the serve `das` lane.

The fulu spec oracle (`models/fulu/polynomial_commitments_sampling.py`)
is the correctness reference throughout: the host route must match it
statement-for-statement (challenge bytes, interpolation coefficients,
accept/reject verdicts, raise-on-malformed), and the device route must
match the host route.  Tests that compile the curve kernels (pairing /
MSM) at large shapes are @slow like every other RLC-compiling test;
the fr_batch coset kernels compile in well under a second on CPU and
stay tier-1.
"""

import pytest

from consensus_specs_tpu.das import ciphersuite as das_cs
from consensus_specs_tpu.das import compute as das_compute
from consensus_specs_tpu.das import sampling as das_sampling
from consensus_specs_tpu.das import verify as das_verify
from consensus_specs_tpu.models.builder import build_spec
from consensus_specs_tpu.ops import bls


@pytest.fixture(scope="module")
def spec():
    return build_spec("fulu", "minimal")


@pytest.fixture()
def real_bls():
    prev = bls.bls_active
    bls.bls_active = True
    yield
    bls.bls_active = prev


@pytest.fixture(scope="module")
def matrix():
    """A small valid closed-form matrix: 2 rows x 4 columns spanning
    both domain halves."""
    return das_cs.closed_form_matrix(2, columns=[0, 3, 64, 127])


def _tamper_cell(cells, k):
    cells = list(cells)
    cells[k] = cells[k][:-32] + int.to_bytes(7, 32, "big")
    return cells


# --- ciphersuite: tables, challenge, parsing --------------------------------


def test_coset_tables_match_oracle(spec):
    for k in (0, 1, 63, 64, 127):
        assert das_cs.coset_shift(k) == int(
            spec.coset_shift_for_cell(spec.CellIndex(k)))
        assert list(das_cs.coset_points(k)) == [
            int(v) for v in spec.coset_for_cell(spec.CellIndex(k))]


def test_challenge_matches_oracle_bit_for_bit(spec, matrix):
    com, idx, cells, proofs = matrix
    batch = das_cs.parse_cell_batch(com, idx, cells, proofs)
    want = spec.compute_verify_cell_kzg_proof_batch_challenge(
        [spec.KZGCommitment(c) for c in batch.commitment_bytes],
        batch.commitment_indices,
        [spec.CellIndex(i) for i in batch.cell_indices],
        [[spec.BLSFieldElement(e) for e in row] for row in batch.evals],
        [spec.KZGProof(p) for p in batch.proof_bytes])
    assert batch.r == int(want)
    # r_powers are the oracle's compute_powers
    assert batch.r_powers == [int(p) for p in spec.compute_powers(
        want, len(batch.cell_indices))]


def test_parse_rejects_malformed_like_oracle(spec, matrix, real_bls):
    com, idx, cells, proofs = matrix

    def mutations():
        yield com[:-1], idx, cells, proofs              # length mismatch
        yield com, [int(spec.CELLS_PER_EXT_BLOB)] + idx[1:], cells, \
            proofs                                      # index range
        yield com, idx, [cells[0][:-1]] + cells[1:], proofs  # cell size
        yield com, idx, cells, [b"\x01" * 48] + proofs[1:]   # bad point
        yield [b"\xaa" * 48] + com[1:], idx, cells, proofs   # bad point
        # a cell carrying a non-canonical field element
        big = (das_cs.BLS_MODULUS).to_bytes(32, "big")
        yield com, idx, [cells[0][:-32] + big] + cells[1:], proofs

    for m_com, m_idx, m_cells, m_proofs in mutations():
        with pytest.raises(AssertionError):
            das_cs.parse_cell_batch(m_com, m_idx, m_cells, m_proofs)
        with pytest.raises(AssertionError):
            spec.verify_cell_kzg_proof_batch(
                m_com, m_idx, [spec.Cell(c) if len(c) == int(
                    spec.BYTES_PER_CELL) else c for c in m_cells],
                m_proofs)


def test_interpolation_matches_oracle(spec, matrix):
    _, idx, cells, _ = matrix
    k = idx[1]
    evals = [int(e) for e in spec.cell_to_coset_evals(
        spec.Cell(cells[1]))]
    want = [int(c) for c in spec.interpolate_polynomialcoeff(
        spec.coset_for_cell(spec.CellIndex(k)),
        [spec.BLSFieldElement(e) for e in evals])]
    assert das_cs.interpolate_coset_coeffs(k, evals) == want


# --- fr_batch coset kernels --------------------------------------------------


def test_coset_interpolate_kernel_matches_host(matrix):
    from consensus_specs_tpu.ops import fr_batch

    com, idx, cells, proofs = matrix
    batch = das_cs.parse_cell_batch(com, idx, cells, proofs)
    weights = das_verify._rli_weight_rows(batch)
    got = fr_batch.coset_interpolate_sum(
        batch.evals, das_cs.coset_idft_matrix(), weights)
    assert got == das_verify._host_rli_coeffs(batch)


def test_coset_interpolate_rung_ladder_shapes(matrix):
    from consensus_specs_tpu.ops import fr_batch

    assert [fr_batch.das_rung(n) for n in (1, 2, 16, 17, 128, 129,
                                           1024, 1025, 4096)] == \
        [16, 16, 16, 128, 128, 1024, 1024, 2048, 4096]
    # batches inside one rung share the compiled kernel: K=3 and the
    # K=8 matrix fixture both pad to rung 16, so the lru-cached jit
    # factory hands back the SAME callable (one compiled executable)
    com, idx, cells, proofs = matrix
    batch = das_cs.parse_cell_batch(com[:3], idx[:3], cells[:3],
                                    proofs[:3])
    before = fr_batch._coset_interpolate_kernel.cache_info().currsize
    fr_batch.coset_interpolate_sum(
        batch.evals, das_cs.coset_idft_matrix(),
        das_verify._rli_weight_rows(batch))
    full = das_cs.parse_cell_batch(com, idx, cells, proofs)
    fr_batch.coset_interpolate_sum(
        full.evals, das_cs.coset_idft_matrix(),
        das_verify._rli_weight_rows(full))
    after = fr_batch._coset_interpolate_kernel.cache_info().currsize
    assert after <= max(before, 1)


def test_barycentric_coset_shift_matches_host(matrix):
    from consensus_specs_tpu.ops import fr_batch

    _, idx, cells, _ = matrix
    z = 0xFEEDFACE
    got = das_verify.evaluate_cells_at(cells[:2], idx[:2], z,
                                       device=True)
    want = das_verify.evaluate_cells_at(cells[:2], idx[:2], z,
                                        device=False)
    assert got == want
    # h=1 keeps the classic roots-of-unity formula bit-compatible
    from consensus_specs_tpu.serve.executor import _oracle_barycentric

    r = fr_batch.R_MODULUS
    g = pow(7, (r - 1) // 8, r)
    roots = [pow(g, i, r) for i in range(8)]
    poly = [(5 * i + 3) % r for i in range(8)]
    assert fr_batch.barycentric_eval(poly, roots, 0x5050) == \
        _oracle_barycentric(poly, roots, 0x5050)


def test_evaluate_cells_at_in_domain_short_circuits(matrix):
    _, idx, cells, _ = matrix
    k = idx[0]
    point = das_cs.coset_points(k)[5]
    evals = [int.from_bytes(cells[0][i * 32:(i + 1) * 32], "big")
             for i in range(64)]
    for device in (False, True):
        assert das_verify.evaluate_cells_at(
            [cells[0]], [k], point, device=device) == [evals[5]]


# --- verification: host route vs the spec oracle ----------------------------


def test_host_verify_matches_oracle_verdicts(spec, matrix, real_bls):
    com, idx, cells, proofs = matrix
    sub = slice(0, 2)
    wrapped = [spec.Cell(c) for c in cells[sub]]
    assert spec.verify_cell_kzg_proof_batch(
        com[sub], idx[sub], wrapped, proofs[sub]) is True
    assert das_verify.verify_cell_proof_batch_host(
        com[sub], idx[sub], cells[sub], proofs[sub]) is True
    # one tampered cell flips both verdicts
    bad = _tamper_cell(cells, 1)
    assert spec.verify_cell_kzg_proof_batch(
        com[sub], idx[sub], [spec.Cell(c) for c in bad[sub]],
        proofs[sub]) is False
    assert das_verify.verify_cell_proof_batch_host(
        com[sub], idx[sub], bad[sub], proofs[sub]) is False


def test_host_verify_closed_form_matrix_and_tampering(matrix):
    com, idx, cells, proofs = matrix
    assert das_verify.verify_cell_proof_batch_host(com, idx, cells,
                                                   proofs)
    assert not das_verify.verify_cell_proof_batch_host(
        com, idx, _tamper_cell(cells, 2), proofs)
    bad_proofs = list(proofs)
    bad_proofs[0] = proofs[4]
    assert not das_verify.verify_cell_proof_batch_host(
        com, idx, cells, bad_proofs)
    # empty batch accepts (the oracle's trivial case)
    assert das_verify.verify_cell_proof_batch_host([], [], [], [])


def test_host_isolation_flags_exactly_the_bad_cell(matrix):
    com, idx, cells, proofs = matrix
    bad = _tamper_cell(cells, 2)
    ok, per = das_verify.verify_and_isolate(com, idx, bad, proofs,
                                            device=False)
    assert ok is False
    assert per == [True, True, False] + [True] * (len(idx) - 3)


def test_duplicate_commitments_dedup_like_oracle(spec, real_bls):
    # 2 rows from the SAME closed-form polynomial: the commitment list
    # carries duplicates, dedup folds their weights
    com, idx, cells, proofs = das_cs.closed_form_matrix(
        1, columns=[0, 64])
    com2 = com + com
    idx2 = idx + idx
    cells2 = cells + cells
    proofs2 = proofs + proofs
    batch = das_cs.parse_cell_batch(com2, idx2, cells2, proofs2)
    assert len(batch.commitments) == 1
    assert batch.commitment_indices == [0, 0, 0, 0]
    assert das_verify.verify_cell_proof_batch_host(
        com2, idx2, cells2, proofs2)
    assert spec.verify_cell_kzg_proof_batch(
        com2, idx2, [spec.Cell(c) for c in cells2], proofs2)


# --- the async facade contract ----------------------------------------------


def test_async_facade_settles_once_and_propagates_errors(matrix):
    com, idx, cells, proofs = matrix
    fut = das_verify.verify_cell_proof_batch_async(
        com[:1], idx[:1], cells[:1], proofs[:1], device=False)
    assert fut.done()            # host route settles eagerly
    assert fut.result() is True
    assert fut.result() is True  # idempotent
    # malformed input fails the handle instead of raising at submit
    bad = das_verify.verify_cell_proof_batch_async(
        com[:1], idx[:1], [cells[0][:-1]], proofs[:1], device=False)
    assert bad.exception() is not None
    with pytest.raises(AssertionError):
        bad.result()


def test_coset_interpolate_async_is_deferred(matrix):
    from consensus_specs_tpu.ops import fr_batch

    com, idx, cells, proofs = matrix
    batch = das_cs.parse_cell_batch(com, idx, cells, proofs)
    fut = fr_batch.coset_interpolate_sum_async(
        batch.evals, das_cs.coset_idft_matrix(),
        das_verify._rli_weight_rows(batch))
    assert not fut.done()        # device-backed: settles at result()
    out = fut.result()
    assert fut.done() and fut.result() is out


# --- compute: producer parity ------------------------------------------------


def test_compute_cells_matches_spec(spec):
    blob = b"".join(
        int.to_bytes(pow(11, i + 3, das_cs.BLS_MODULUS), 32, "big")
        for i in range(4096))
    got = das_compute.compute_cells(blob)
    want = [bytes(c) for c in spec.compute_cells(spec.Blob(blob))]
    assert got == want


def test_column_proof_matches_oracle_multiproof(spec):
    blob = b"".join(
        int.to_bytes(pow(11, i + 3, das_cs.BLS_MODULUS), 32, "big")
        for i in range(4096))
    k = 65
    got = das_compute.cell_proof_for_column(blob, k, device=False)
    coeff = spec.polynomial_eval_to_coeff(
        spec.blob_to_polynomial(spec.Blob(blob)))
    want, ys = spec.compute_kzg_proof_multi_impl(
        coeff, spec.coset_for_cell(spec.CellIndex(k)))
    assert got == bytes(want)
    # and the produced statement verifies through the das host route
    commitment = bytes(spec.blob_to_kzg_commitment(spec.Blob(blob)))
    cells = das_compute.compute_cells(blob)
    assert das_verify.verify_cell_proof_batch_host(
        [commitment], [k], [cells[k]], [got])


# --- sampling ----------------------------------------------------------------


def test_inclusion_proof_walk():
    from hashlib import sha256

    leaf = b"\x01" * 32
    sib0 = b"\x02" * 32
    sib1 = b"\x03" * 32
    # index 2 (binary 10): leaf hashes LEFT at level 0, RIGHT at level 1
    level1 = sha256(leaf + sib0).digest()
    root = sha256(sib1 + level1).digest()
    proof = das_sampling.InclusionProof(leaf=leaf, branch=[sib0, sib1],
                                        index=2, root=root)
    assert das_sampling.verify_inclusion(proof)
    assert not das_sampling.verify_inclusion(
        das_sampling.InclusionProof(leaf=sib0, branch=[sib0, sib1],
                                    index=2, root=root))


def test_verify_sample_structural_and_inclusion_rejects(matrix):
    com, idx, cells, proofs = matrix
    sample = das_sampling.sample_from_matrix(com, idx, cells, proofs, 3)
    assert sample.column_index == 3 and len(sample.cells) == 2
    assert das_sampling.verify_sample(sample, device=False)
    # length mismatch rejects before any crypto
    broken = das_sampling.DasSample(
        column_index=3, commitments=sample.commitments,
        cells=sample.cells, proofs=sample.proofs[:-1])
    assert das_sampling.verify_sample(broken, device=False) is False
    # column index out of range
    oob = das_sampling.DasSample(
        column_index=das_cs.CELLS_PER_EXT_BLOB,
        commitments=sample.commitments, cells=sample.cells,
        proofs=sample.proofs)
    assert das_sampling.verify_sample(oob, device=False) is False
    # failing inclusion proof rejects without touching the cells
    bad_inc = das_sampling.DasSample(
        column_index=3, commitments=sample.commitments,
        cells=sample.cells, proofs=sample.proofs,
        inclusion=das_sampling.InclusionProof(
            leaf=b"\x00" * 32, branch=[b"\x01" * 32], index=0,
            root=b"\x02" * 32))
    assert das_sampling.verify_sample(bad_inc, device=False) is False


def test_sample_from_sidecar_roundtrip(spec):
    """The zero-blob closed-form sidecar (no MSMs) adapts into a
    DasSample whose inclusion proof passes the host walk."""
    from consensus_specs_tpu.testlib.context import (
        default_activation_threshold)
    from consensus_specs_tpu.testlib.helpers.block import (
        build_empty_block_for_next_slot, sign_block)
    from consensus_specs_tpu.testlib.helpers.genesis import (
        create_genesis_state)

    g1_inf = b"\xc0" + b"\x00" * 47
    state = create_genesis_state(
        spec, [int(spec.MAX_EFFECTIVE_BALANCE)] * 64,
        default_activation_threshold(spec))
    n_cells = int(spec.CELLS_PER_EXT_BLOB)
    block = build_empty_block_for_next_slot(spec, state)
    block.body.blob_kzg_commitments = [spec.KZGCommitment(g1_inf)]
    signed = sign_block(spec, state, block)
    sidecar = spec.get_data_column_sidecars_from_block(
        signed, [([spec.Cell()] * n_cells,
                  [spec.KZGProof(g1_inf)] * n_cells)])[0]
    sample = das_sampling.sample_from_sidecar(spec, sidecar)
    assert das_sampling.verify_inclusion(sample.inclusion)
    assert das_sampling.verify_sample(sample, device=False)
    # a tampered commitment list fails the inclusion walk
    sample.inclusion.leaf = b"\xff" * 32
    assert das_sampling.verify_sample(sample, device=False) is False


# --- the serve `das` lane ----------------------------------------------------


def test_serve_das_lane_host_routed(matrix, monkeypatch):
    """submit_das_sample end to end with the dispatch routed to the
    host verifier (the device arc is @slow below): valid and invalid
    samples settle their own verdicts through the per-pump group
    batch, and the group's per-sample recheck isolates the bad one."""
    from consensus_specs_tpu.das import sampling as sampling_mod
    from consensus_specs_tpu.serve.executor import ServeExecutor

    orig_group = sampling_mod.verify_sample_group_async
    monkeypatch.setattr(
        sampling_mod, "verify_sample_group_async",
        lambda samples, device=True: orig_group(samples, device=False))

    com, idx, cells, proofs = matrix
    good = das_sampling.sample_from_matrix(com, idx, cells, proofs, 0)
    bad = das_sampling.sample_from_matrix(
        com, idx, _tamper_cell(cells, 0), proofs, 0)
    ex = ServeExecutor(max_batch=8, depth=1)
    f_good = ex.submit_das_sample(good)
    f_bad = ex.submit_das_sample(bad)
    ex.drain()
    assert f_good.result() is True
    assert f_bad.result() is False
    st = ex.stats()
    assert st["settled"] == 2 and st["failed"] == 0
    # the two queued samples rode ONE group dispatch
    assert st["batches"] == 1


def test_serve_das_cross_sample_batching(matrix, monkeypatch):
    """The per-pump fold: N queued das samples dispatch as ONE device
    batch (the RLC equation over all their cell statements), and each
    request still settles its own verdict."""
    from consensus_specs_tpu.das import sampling as sampling_mod
    from consensus_specs_tpu.serve.executor import ServeExecutor

    calls = {"groups": 0, "samples": 0}
    orig_group = sampling_mod.verify_sample_group_async

    def counting_group(samples, device=True):
        calls["groups"] += 1
        calls["samples"] += len(samples)
        return orig_group(samples, device=False)

    monkeypatch.setattr(sampling_mod, "verify_sample_group_async",
                        counting_group)
    com, idx, cells, proofs = matrix
    samples = [das_sampling.sample_from_matrix(com, idx, cells,
                                               proofs, c)
               for c in (0, 3, 64)]
    ex = ServeExecutor(max_batch=8, depth=1)
    futs = [ex.submit_das_sample(s) for s in samples]
    ex.drain()
    assert [f.result() for f in futs] == [True, True, True]
    assert calls == {"groups": 1, "samples": 3}
    assert ex.stats()["batches"] == 1


def test_serve_das_breaker_falls_back_to_host_oracle(matrix,
                                                     monkeypatch):
    """A das dispatch failure walks the recovery ladder: the breaker
    trips and the pure-host oracle answers (bit-identical verdicts)."""
    from consensus_specs_tpu.das import sampling as sampling_mod
    from consensus_specs_tpu.resilience.policies import BreakerRegistry
    from consensus_specs_tpu.serve.executor import ServeExecutor

    calls = {"n": 0}

    def exploding(samples, device=True):
        calls["n"] += 1
        raise RuntimeError("device sick")

    monkeypatch.setattr(sampling_mod, "verify_sample_group_async",
                        exploding)
    com, idx, cells, proofs = matrix
    sample = das_sampling.sample_from_matrix(com, idx, cells, proofs, 0)
    ex = ServeExecutor(max_batch=8, depth=1,
                       breakers=BreakerRegistry(threshold=1))
    f1 = ex.submit_das_sample(sample)
    ex.drain()
    # first dispatch failed and (threshold=1) tripped the breaker;
    # the ladder answered on the host oracle — bit-identical verdict
    assert f1.result() is True
    assert ex.stats()["fallbacks"] >= 1
    f2 = ex.submit_das_sample(sample)
    ex.drain()
    assert f2.result() is True
    assert calls["n"] == 1       # breaker OPEN: no second device try


def test_loadgen_schedule_carries_the_das_lane(monkeypatch):
    from consensus_specs_tpu.serve import loadgen

    class _StubEx:
        def __init__(self):
            self.kinds = []

        def submit_verify_task(self, t):
            self.kinds.append("verify")

        def submit_pairing(self, p):
            self.kinds.append("pairing")

        def submit_barycentric(self, *a):
            self.kinds.append("fr")

        def submit_sha256_root(self, *a):
            self.kinds.append("sha256")

        def submit_proof_request(self, *a):
            self.kinds.append("proof")

        def submit_das_sample(self, s):
            self.kinds.append("das")
            self.sample = s

    monkeypatch.setattr(loadgen, "DAS_SAMPLES_PER_SLOT", 2)
    monkeypatch.setattr(loadgen, "STATEMENTS_PER_SLOT", 76)
    ex = _StubEx()
    samples = ["s0", "s1", "s2"]
    submit, kinds = loadgen.make_submitter(
        ex, ["task"], {"pairing": None, "fr": (1, 2, 3),
                       "sha256": (None, 1), "proof": (None, [0]),
                       "das": samples})
    for _ in range(76):
        submit()
    assert kinds["das"] == 2
    assert ex.kinds.count("das") == 2
    assert ex.sample in samples


# --- G1-FFT kernel + FK20 producer: host-side contracts ----------------------
# (the kernels themselves compile curve arithmetic, so every test that
# actually dispatches one is @slow at the bottom of this file)


def test_g1fft_domain_matches_ciphersuite():
    from consensus_specs_tpu.ops.bls_batch import g1fft_jax as gf

    for n in (8, 128):
        assert gf.fft_domain(n) == das_cs.roots_of_unity(n)


def test_g1fft_rung_ladder():
    from consensus_specs_tpu.ops.bls_batch import g1fft_jax as gf

    assert [gf.g1fft_rung(n) for n in (1, 3, 8, 9, 64, 128, 129,
                                       300)] == \
        [8, 8, 8, 128, 128, 128, 256, 512]


def test_g1fft_stage_plan_is_log2_rounds_of_disjoint_pairs():
    from consensus_specs_tpu.ops.bls_batch import g1fft_jax as gf

    for n in (8, 128):
        u, v, digs = gf._stage_plan(n, False)
        # one shape-uniform row per butterfly round: log2(n) rounds,
        # each pairing every position exactly once
        assert u.shape == v.shape == (n.bit_length() - 1, n // 2)
        assert digs.shape[:2] == (n.bit_length() - 1, n // 2)
        for r in range(u.shape[0]):
            touched = sorted(u[r].tolist() + v[r].tolist())
            assert touched == list(range(n)), (n, r)


def test_g1fft_limbs_roundtrip_and_infinity_padding():
    from consensus_specs_tpu.ops.bls import curve as pycurve
    from consensus_specs_tpu.ops.bls_batch import g1fft_jax as gf

    pts = [pycurve.g1.mul(pycurve.G1_GEN, s) for s in (1, 2, 3)]
    x, y, z = gf.points_to_limbs(pts, pad_to=8)
    assert x.shape == (8, gf._fq.N_LIMBS)
    back = gf.limbs_to_oracle_list((x, y, z))
    for a, b in zip(back[:3], pts):
        assert pycurve.g1.eq_points(a, b)
    # padded lanes are the canonical infinity encoding (Z == 0)
    for p in back[3:]:
        assert pycurve.g1.to_affine(p) is None


def test_fk20_producer_route_knob(monkeypatch):
    # the host route never takes FK20; the device default does; the
    # CST_DAS_PRODUCER=du pin forces the D_u baseline on device too
    monkeypatch.delenv("CST_DAS_PRODUCER", raising=False)
    assert das_compute._producer_route(False) == "du"
    assert das_compute._producer_route(True) == "fk20"
    monkeypatch.setenv("CST_DAS_PRODUCER", "du")
    assert das_compute._producer_route(True) == "du"


# --- erasure recovery (das/recover) ------------------------------------------


def test_recover_vanishing_poly_and_batch_inverse():
    from consensus_specs_tpu.das import recover as das_recover

    P = das_recover.P
    missing = [1, 7, 127]
    short = das_recover._short_vanishing(missing)
    assert len(short) == len(missing) + 1 and short[-1] == 1
    roots128 = das_cs.roots_of_unity(128)
    for k in range(128):
        val = sum(c * pow(roots128[das_cs.reverse_bits(k, 128)], i, P)
                  for i, c in enumerate(short)) % P
        assert (val == 0) == (k in missing), k
    # the stride-64 embedding: Z_ext(x) = Z_short(x^64)
    ext = das_recover.construct_vanishing_poly(missing)
    assert len(ext) == das_recover.M_EXT
    assert [ext[i * 64] for i in range(len(short))] == short
    assert all(v == 0 for i, v in enumerate(ext) if i % 64)
    vals = [3, 5, 0xDEADBEEF, P - 2]
    assert das_recover._batch_inverse(vals) == \
        [pow(v, P - 2, P) for v in vals]


def test_recover_rejects_malformed_like_oracle():
    """Both routes enforce the spec oracle's argument contract: the
    device facade asserts EAGERLY (before any dispatch), the host
    oracle raises the same AssertionError."""
    from consensus_specs_tpu.das import recover as das_recover

    cell = b"\x00" * das_cs.BYTES_PER_CELL
    bad_inputs = [
        (list(range(63)), [cell] * 63),          # below half
        ([0, 0] + list(range(2, 64)), [cell] * 64),   # duplicate index
        ([128] + list(range(1, 64)), [cell] * 64),    # out of range
        (list(range(64)), [cell] * 63),          # length mismatch
        (list(range(64)), [cell] * 63 + [cell[:-1]]),  # short cell
    ]
    for idx, cls in bad_inputs:
        with pytest.raises(AssertionError):
            das_recover.recover_cells_and_kzg_proofs_async(
                idx, cls, device=True)
        with pytest.raises(AssertionError):
            das_recover.recover_cells_and_kzg_proofs_host(idx, cls)


def test_recover_route_knob(monkeypatch):
    from consensus_specs_tpu.das import recover as das_recover

    monkeypatch.delenv("CST_DAS_RECOVER_ROUTE", raising=False)
    assert das_recover._recover_route(True) is True
    assert das_recover._recover_route(False) is False
    monkeypatch.setenv("CST_DAS_RECOVER_ROUTE", "host")
    assert das_recover._recover_route(True) is False


def test_serve_recover_lane_round_trips(monkeypatch):
    """submit_recover_request end to end with the device facade
    stubbed: the payload normalizes to (int indices, bytes cells) and
    the settled (cells, proofs) pair rides back on the request's own
    future."""
    from consensus_specs_tpu.das import recover as das_recover
    from consensus_specs_tpu.serve.executor import ServeExecutor
    from consensus_specs_tpu.serve.futures import DeviceFuture

    seen = {}

    def stub(cell_indices, cells, device=None):
        seen["args"] = (cell_indices, cells, device)
        return DeviceFuture.settled((["cells"], ["proofs"]))

    monkeypatch.setattr(das_recover,
                        "recover_cells_and_kzg_proofs_async", stub)
    cell = b"\x07" * das_cs.BYTES_PER_CELL
    ex = ServeExecutor(max_batch=8, depth=1)
    fut = ex.submit_recover_request(range(64), [bytearray(cell)] * 64)
    ex.drain()
    assert fut.result() == (["cells"], ["proofs"])
    idx, cls, device = seen["args"]
    assert idx == list(range(64)) and device is True
    assert cls == [cell] * 64 and all(type(c) is bytes for c in cls)
    assert ex.stats()["failed"] == 0


def test_serve_recover_breaker_falls_back_to_host_oracle(monkeypatch):
    """A recover dispatch failure walks the same recovery ladder as
    every other kind: the breaker trips and the pure-host spec oracle
    answers."""
    from consensus_specs_tpu.das import recover as das_recover
    from consensus_specs_tpu.resilience.policies import BreakerRegistry
    from consensus_specs_tpu.serve.executor import ServeExecutor

    calls = {"device": 0, "host": 0}

    def exploding(cell_indices, cells, device=None):
        calls["device"] += 1
        raise RuntimeError("device sick")

    def host_stub(cell_indices, cells):
        calls["host"] += 1
        return (["oracle-cells"], ["oracle-proofs"])

    monkeypatch.setattr(das_recover,
                        "recover_cells_and_kzg_proofs_async", exploding)
    monkeypatch.setattr(das_recover,
                        "recover_cells_and_kzg_proofs_host", host_stub)
    cell = b"\x01" * das_cs.BYTES_PER_CELL
    ex = ServeExecutor(max_batch=8, depth=1,
                       breakers=BreakerRegistry(threshold=1))
    f1 = ex.submit_recover_request(list(range(64)), [cell] * 64)
    ex.drain()
    assert f1.result() == (["oracle-cells"], ["oracle-proofs"])
    assert ex.stats()["fallbacks"] >= 1
    f2 = ex.submit_recover_request(list(range(64)), [cell] * 64)
    ex.drain()
    assert f2.result() == (["oracle-cells"], ["oracle-proofs"])
    assert calls["device"] == 1      # breaker OPEN: no second try
    assert calls["host"] == 2


def test_loadgen_schedule_carries_the_recover_lane(monkeypatch):
    from consensus_specs_tpu.serve import loadgen

    class _StubEx:
        def __init__(self):
            self.kinds = []

        def submit_verify_task(self, t):
            self.kinds.append("verify")

        def submit_pairing(self, p):
            self.kinds.append("pairing")

        def submit_barycentric(self, *a):
            self.kinds.append("fr")

        def submit_sha256_root(self, *a):
            self.kinds.append("sha256")

        def submit_proof_request(self, *a):
            self.kinds.append("proof")

        def submit_das_sample(self, s):
            self.kinds.append("das")

        def submit_recover_request(self, idx, cells):
            self.kinds.append("recover")
            self.recover_args = (idx, cells)

    monkeypatch.setattr(loadgen, "RECOVER_PER_SLOT", 2)
    monkeypatch.setattr(loadgen, "DAS_SAMPLES_PER_SLOT", 1)
    # the recover entries sit at the END of the slot schedule: park the
    # fork-choice lanes so one full cycle reaches them with a stub
    monkeypatch.setattr(loadgen, "FC_ATTS_PER_SLOT", 0)
    monkeypatch.setattr(loadgen, "HEAD_POLLS_PER_SLOT", 0)
    monkeypatch.setattr(loadgen, "STATEMENTS_PER_SLOT", 77)
    ex = _StubEx()
    payloads = [([0, 1], ["c0"]), ([2, 3], ["c1"])]
    submit, kinds = loadgen.make_submitter(
        ex, ["task"], {"pairing": None, "fr": (1, 2, 3),
                       "sha256": (None, 1), "proof": (None, [0]),
                       "das": ["s0"], "recover": payloads})
    for _ in range(77):
        submit()
    assert kinds["recover"] == 2
    assert ex.kinds.count("recover") == 2
    assert ex.recover_args in payloads


# --- the device route at the smallest rung ----------------------------------


@pytest.mark.parametrize("tampered", [None, 1], ids=["valid", "one_bad_cell"])
def test_device_route_verdicts_and_isolation(matrix, real_bls, tampered):
    """The device route on a closed-form matrix (valid by construction):
    the batch verdict, and the mixed-invalid arc — the RLC batch fails
    and the per-statement recheck isolates exactly the bad cell."""
    com, idx, cells, proofs = matrix
    if tampered is not None:
        cells = _tamper_cell(cells, tampered)
    ok, per = das_verify.verify_and_isolate(com, idx, cells, proofs,
                                            device=True)
    assert ok is (tampered is None)
    assert [i for i, v in enumerate(per) if not v] == (
        [] if tampered is None else [tampered])


# --- @slow: device-route end to end ------------------------------------------


@pytest.mark.slow
def test_device_verify_matches_host_and_oracle(spec, matrix, real_bls):
    com, idx, cells, proofs = matrix
    assert das_verify.verify_cell_proof_batch(
        com, idx, cells, proofs, device=True) is True
    bad = _tamper_cell(cells, 1)
    assert das_verify.verify_cell_proof_batch(
        com, idx, bad, proofs, device=True) is False
    # direct oracle agreement on the same statements
    assert spec.verify_cell_kzg_proof_batch(
        com[:2], idx[:2], [spec.Cell(c) for c in cells[:2]],
        proofs[:2]) is True
    assert das_verify.verify_cell_proof_batch(
        com[:2], idx[:2], cells[:2], proofs[:2], device=True) is True


@pytest.mark.slow
def test_device_verify_full_column_batch(real_bls):
    """One full 128-column row x 2 blobs (256 cells, rung 1024...):
    device verdict matches the host route on the identical batch, and
    the mixed-invalid arc isolates exactly the bad cell."""
    com, idx, cells, proofs = das_cs.closed_form_matrix(2)
    assert len(idx) == 256
    assert das_verify.verify_cell_proof_batch(
        com, idx, cells, proofs, device=True) is True
    assert das_verify.verify_cell_proof_batch_host(
        com, idx, cells, proofs) is True
    bad = _tamper_cell(cells, 200)
    ok, per = das_verify.verify_and_isolate(com, idx, bad, proofs,
                                            device=True)
    assert ok is False
    assert [i for i, v in enumerate(per) if not v] == [200]


@pytest.mark.slow
def test_device_full_compute_matches_column_route_and_oracle(spec):
    """The D_u-partial full-proof route vs the independent per-column
    quotient route (all 128 columns) and the oracle (2 columns)."""
    blob = b"".join(
        int.to_bytes(pow(13, i + 9, das_cs.BLS_MODULUS), 32, "big")
        for i in range(4096))
    cells, proofs = das_compute.compute_cells_and_kzg_proofs(
        blob, device=False)
    for k in range(0, 128, 17):
        assert proofs[k] == das_compute.cell_proof_for_column(
            blob, k, device=False), k
    coeff = spec.polynomial_eval_to_coeff(
        spec.blob_to_polynomial(spec.Blob(blob)))
    for k in (0, 100):
        want, _ = spec.compute_kzg_proof_multi_impl(
            coeff, spec.coset_for_cell(spec.CellIndex(k)))
        assert proofs[k] == bytes(want)
    assert cells == das_compute.compute_cells(blob)


@pytest.mark.slow
def test_spec_namespace_routes_to_device_path(spec, real_bls):
    """Under the jax backend the spec's own verify_cell_kzg_proof_batch
    routes through the das device route with identical verdicts."""
    com, idx, cells, proofs = das_cs.closed_form_matrix(
        1, columns=[0, 9])
    prev = bls.backend_name()
    bls.use_backend("jax")
    try:
        assert spec.verify_cell_kzg_proof_batch(
            com, idx, [spec.Cell(c) for c in cells], proofs) is True
        assert spec.verify_cell_kzg_proof_batch(
            com, idx, [spec.Cell(c) for c in _tamper_cell(cells, 0)],
            proofs) is False
    finally:
        bls.use_backend(prev)


@pytest.mark.slow
def test_serve_das_lane_device_end_to_end(matrix):
    from consensus_specs_tpu.serve.executor import ServeExecutor

    com, idx, cells, proofs = matrix
    good = das_sampling.sample_from_matrix(com, idx, cells, proofs, 64)
    ex = ServeExecutor(max_batch=8, depth=1)
    fut = ex.submit_das_sample(good)
    ex.drain()
    assert fut.result() is True
    assert ex.stats()["failed"] == 0


# --- @slow: G1-FFT kernel + FK20 + recovery ----------------------------------


def _closed_form_blob_and_truth(c2=90001, c1=80001, c0=70001):
    """(blob bytes, true cells, true proofs) for the degree-65 closed
    form f = c2*X^65 + c1*X^64 + c0 — the one blob family whose full
    proof set is known WITHOUT running any producer (see
    `closed_form_row`), and low-degree enough that the pure-Python
    oracle stays tractable (its MSM skips the ~4030 zero scalars)."""
    m = das_cs.FIELD_ELEMENTS_PER_BLOB
    p = das_cs.BLS_MODULUS
    roots = das_cs.roots_of_unity(m)
    evals = [(c2 * pow(roots[das_cs.reverse_bits(i, m)], 65, p)
              + c1 * pow(roots[das_cs.reverse_bits(i, m)], 64, p)
              + c0) % p for i in range(m)]
    blob = das_cs._encode_evals(evals)
    _, per_cell = das_cs.closed_form_row(c2, c1, c0, range(128))
    return (blob, [per_cell[k][0] for k in range(128)],
            [per_cell[k][1] for k in range(128)])


@pytest.mark.slow
def test_g1fft_matches_naive_and_shares_rung_compiles():
    """The batched G1 FFT against per-point naive evaluation on the
    bottom rung, the ifft(fft(x)) == x round-trip, rung-ladder compile
    sharing (3 live points and 5 live points ride the SAME n=8
    executable), and the butterfly-round telemetry (log2(rung) per
    dispatch)."""
    from consensus_specs_tpu import telemetry
    from consensus_specs_tpu.ops.bls import curve as pycurve
    from consensus_specs_tpu.ops.bls_batch import g1fft_jax as gf

    p = das_cs.BLS_MODULUS
    dom = gf.fft_domain(8)
    pts = [pycurve.g1.mul(pycurve.G1_GEN, s) for s in (5, 9, 11)]
    padded = pts + [pycurve.g1.infinity()] * 5

    was_enabled = telemetry.enabled()
    telemetry.configure(enabled=True)
    try:
        telemetry.reset()
        before = gf._g1_fft_kernel.cache_info()
        out = gf.g1_fft(pts)
        assert telemetry.counter_value("g1fft.butterfly_rounds") == 3
        for i in range(8):
            want = None
            for j, pt in enumerate(padded):
                t = pycurve.g1.mul(pt, dom[(i * j) % 8])
                want = t if want is None else pycurve.g1.add(want, t)
            assert pycurve.g1.eq_points(out[i], want), i
        # the inverse transform recovers the padded input exactly
        back = gf.g1_fft(out, inverse=True)
        for a, b in zip(back, padded):
            assert pycurve.g1.eq_points(a, b)
        # a 5-point vector pads to the same rung: no new compile
        mid = gf._g1_fft_kernel.cache_info()
        out5 = gf.g1_fft(pts + [pycurve.g1.mul(pycurve.G1_GEN, 13),
                                pycurve.g1.infinity()])
        after = gf._g1_fft_kernel.cache_info()
        assert after.misses == mid.misses
        assert after.hits > mid.hits
        assert mid.misses > before.misses  # the first call DID compile
        assert len(out5) == 8
    finally:
        telemetry.configure(enabled=was_enabled)
    # domain pinned to the spec derivation (w = 7^((r-1)/n))
    assert pow(dom[1], 8, p) == 1 and pow(dom[1], 4, p) != 1


@pytest.mark.slow
def test_fk20_proofs_match_du_route_and_oracle():
    """The FK20 device producer vs the D_u-partial host route vs the
    spec oracle's multiproof, all on one closed-form blob whose true
    proof set is known in closed form."""
    blob, true_cells, true_proofs = _closed_form_blob_and_truth()
    fk_cells, fk_proofs = das_compute.compute_cells_and_kzg_proofs(
        blob, device=True, route="fk20")
    assert fk_cells == true_cells
    assert fk_proofs == true_proofs
    # the D_u route (host MSMs — the oracle msm skips the ~4030 zero
    # scalars, so the low-degree blob keeps this tractable)
    du_cells, du_proofs = das_compute.compute_cells_and_kzg_proofs(
        blob, device=False)
    assert du_cells == fk_cells
    assert du_proofs == fk_proofs
    # the spec oracle's own multiproof on a sample of cosets
    fulu = build_spec("fulu", "mainnet")
    coeff = fulu.polynomial_eval_to_coeff(
        fulu.blob_to_polynomial(fulu.Blob(blob)))
    for k in (0, 65, 127):
        want, _ = fulu.compute_kzg_proof_multi_impl(
            coeff, fulu.coset_for_cell(fulu.CellIndex(k)))
        assert fk_proofs[k] == bytes(want), k


@pytest.mark.slow
def test_recover_device_matches_truth_and_host_oracle():
    """Erasure recovery end to end on an exactly-half survival set:
    the device decode + FK20 re-prove byte-equals both the closed-form
    ground truth and the pure-Python spec oracle run on the SAME
    surviving cells."""
    from consensus_specs_tpu.das import recover as das_recover

    _, true_cells, true_proofs = _closed_form_blob_and_truth()
    keep = list(range(0, 128, 2))
    kept = [true_cells[k] for k in keep]
    dev_cells, dev_proofs = das_recover.recover_cells_and_kzg_proofs(
        keep, kept, device=True)
    assert dev_cells == true_cells
    assert dev_proofs == true_proofs
    o_cells, o_proofs = das_recover.recover_cells_and_kzg_proofs_host(
        keep, kept)
    assert o_cells == dev_cells
    assert o_proofs == dev_proofs
