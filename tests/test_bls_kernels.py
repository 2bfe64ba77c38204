"""Device BLS kernel correctness vs the pure-Python oracle — the
component-level counterpart of `test_bls_jax.py`'s accept/reject parity:

- device `hash_to_g2` (sha256 xmd + SVDW + cofactor) vs
  `ops/bls/hash_to_curve.py` on random messages;
- Pippenger bucketed MSM vs the double-and-add kernel vs the host
  Pippenger (`ops/bls/curve.py:msm`);
- precomputed-line (fixed-G2-argument) pairing vs `ops/bls/pairing.py`;
- the shared-accumulator invariant: ONE unbatched Fq12 squaring per
  Miller-loop bit in the traced program, independent of batch size;
- `_bucket` shape-ladder regression (n = 0/1 edges, <= 4 jit shapes);
- the RLC kernel's program name and its `cst.rlc.*` stage scopes.

All CPU-runnable with small batch buckets (JAX_PLATFORMS=cpu is pinned by
conftest).  The full hash/pairing programs compile for tens of seconds on
CPU, so those carry the `slow` marker the same way `test_bls_jax.py`
does; the host-side and trace-level checks stay in the fast lane.
"""

import random

import numpy as np
import pytest

from consensus_specs_tpu.ops import bls_batch as bb
from consensus_specs_tpu.ops.bls import curve as C
from consensus_specs_tpu.ops.bls import hash_to_curve as H
from consensus_specs_tpu.ops.bls import pairing as P


def test_bucket_edge_cases_and_shape_ladder():
    """n=0/1 land on the bottom rung (padded lanes are masked, so the
    degenerate sizes need no special shape); every realistic batch lands
    on one of at most 4 compiled shapes; the bucket always covers the
    batch and never pads more than 4x (beyond the bottom rung)."""
    assert bb._bucket(0) == 8
    assert bb._bucket(1) == 8
    shapes = {bb._bucket(n) for n in range(513)}
    assert shapes == {8, 32, 128, 512}, shapes
    for n in range(513):
        assert bb._bucket(n) >= n
        if n > 8:
            assert bb._bucket(n) < 4 * n
    # the BASELINE config shapes land exactly
    assert bb._bucket(128) == 128
    # beyond the ladder: next power of two (rare, still one shape per
    # power)
    assert bb._bucket(513) == 1024


def test_scalars_to_digits_layout():
    from consensus_specs_tpu.ops.bls_batch import curve_jax as cj

    s = 0b1011_0110_0001
    digits = cj.scalars_to_digits([s], 12, 4)
    assert digits.tolist() == [[0b1011, 0b0110, 0b0001]]
    # ragged top window
    digits = cj.scalars_to_digits([s], 13, 4)
    assert digits.tolist() == [[0b0, 0b1011, 0b0110, 0b0001]]


def test_expand_message_xmd_device_matches_oracle():
    from consensus_specs_tpu.ops.bls_batch import h2c_jax as h2c

    msgs = [bytes([i * 17]) * 32 for i in range(2)]
    out = np.asarray(h2c.expand_message_xmd_dev(h2c.msgs_to_words(msgs)))
    for i, m in enumerate(msgs):
        want = H.expand_message_xmd(m, H.DST_G2, 256)
        assert out[i].astype(">u4").tobytes() == want


def test_shared_accumulator_one_fq12_squaring_per_bit():
    """Trace the multi-pairing check at two batch sizes and record every
    fq12_sqr argument shape: the Miller accumulator (and everything in
    the final exponentiation) must be UNBATCHED — the per-bit squaring
    count is 1 regardless of B."""
    import jax
    import jax.numpy as jnp

    from consensus_specs_tpu.ops.bls_batch import pairing_jax as pj
    from consensus_specs_tpu.ops.bls_batch import tower as tw

    fq12_shape = tw.FQ12_ONE_L.shape
    recorded = {}
    orig = tw.fq12_sqr

    def recording_sqr(a):
        recorded["shapes"].append(tuple(a.shape))
        return orig(a)

    counts = {}
    for B in (4, 8):
        recorded["shapes"] = []
        tw.fq12_sqr = recording_sqr
        try:
            jax.make_jaxpr(pj.multi_pairing_check)(
                jnp.zeros((B, 33), jnp.int32),
                jnp.zeros((B, 33), jnp.int32),
                jnp.zeros((B, 2, 33), jnp.int32),
                jnp.zeros((B, 2, 33), jnp.int32),
                jnp.zeros((B,), bool))
        finally:
            tw.fq12_sqr = orig
        shapes = recorded["shapes"]
        assert shapes, "tracing recorded no squarings"
        assert all(s == fq12_shape for s in shapes), \
            f"batched Fq12 squaring leaked into the trace at B={B}: " \
            f"{set(shapes)}"
        counts[B] = len(shapes)
    # and the traced squaring count does not grow with B
    assert counts[4] == counts[8]


@pytest.mark.slow
def test_hash_to_g2_device_matches_oracle():
    from consensus_specs_tpu.ops.bls_batch import curve_jax as cj
    from consensus_specs_tpu.ops.bls_batch import h2c_jax as h2c

    rng = random.Random(42)
    msgs = [bytes(rng.randrange(256) for _ in range(32)) for _ in range(2)]
    X, Y, Z = (np.asarray(c)
               for c in h2c.hash_to_g2_dev(h2c.msgs_to_words(msgs)))
    for i, m in enumerate(msgs):
        want = C.g2.to_affine(H.hash_to_g2(m, H.DST_G2))
        got = C.g2.to_affine(cj.g2_limbs_to_oracle((X[i], Y[i], Z[i])))
        assert got == want, f"device hash_to_g2 diverges on msg {i}"


@pytest.mark.slow
def test_pippenger_msm_matches_double_add_and_oracle(monkeypatch):
    rng = random.Random(5)
    pts = [C.g1.mul(C.G1_GEN, rng.randrange(1, C.R)) for _ in range(10)]
    ks = [rng.randrange(C.R) for _ in range(10)]
    # degenerate lanes: zero scalar and infinity point must drop out
    pts += [C.g1.mul(C.G1_GEN, 7), C.g1.infinity()]
    ks += [0, 12345]
    want = C.g1.msm(pts, ks)

    monkeypatch.setenv("CST_MSM_ALGO", "pippenger")
    assert C.g1.eq_points(bb.g1_multi_exp_device(pts, ks), want)
    monkeypatch.setenv("CST_MSM_ALGO", "double-add")
    assert C.g1.eq_points(bb.g1_multi_exp_device(pts, ks), want)


@pytest.mark.slow
def test_precomputed_line_pairing_matches_oracle():
    """pairing_check_device (host-precomputed Miller lines) against the
    oracle pairing_check on accepting and rejecting pair sets."""
    k = 97531
    Ppt = C.g1.mul(C.G1_GEN, 1337)
    good = [(Ppt, C.g2.mul(C.G2_GEN, k)),
            (C.g1.mul(C.g1.neg(Ppt), k), C.G2_GEN)]
    bad = [(Ppt, C.g2.mul(C.G2_GEN, k)),
           (C.g1.mul(C.g1.neg(Ppt), k + 1), C.G2_GEN)]
    for pairs in (good, bad):
        assert bb.pairing_check_device(pairs) == P.pairing_check(pairs)
    # infinity pairs skip, as in the oracle
    assert bb.pairing_check_device([(C.g1.infinity(), C.G2_GEN)]) is True


@pytest.mark.slow
def test_batch_verify_device_h2c_parity():
    """batch_verify with device hash-to-curve agrees with the host-hash
    path on accept AND reject."""
    tasks = []
    for i, k in enumerate([5, 6, 7, 8]):
        msg = bytes([i + 1]) * 32
        pk = C.g1.mul(C.G1_GEN, k)
        sig = C.g2.mul(H.hash_to_g2(msg, H.DST_G2), k)
        tasks.append((pk, msg, sig))
    rng = random.Random(99)
    assert bb.batch_verify(tasks, rng=rng, device_h2c=True) is True
    assert bb.batch_verify(tasks, rng=rng, device_h2c=False) is True
    bad = list(tasks)
    bad[1] = (bad[1][0], bad[1][1], C.g2.mul(C.G2_GEN, 31337))
    assert bb.batch_verify(bad, rng=rng, device_h2c=True) is False
    assert bb.batch_verify(bad, rng=rng, device_h2c=False) is False


def test_rlc_kernel_lowering_names_its_stages():
    """The device-hash RLC kernel at the bottom rung lowers as its own
    program, `rlc_verify_h2c`, with each stage under a `cst.rlc.*` scope
    (scopes are metadata: the program is the same)."""
    B = bb._bucket(1)
    args = (np.zeros((B, 33), np.int32), np.zeros((B, 33), np.int32),
            np.zeros((B, 2, 33), np.int32), np.zeros((B, 2, 33), np.int32),
            np.zeros((B, 8), np.uint32), np.zeros((B, 128), np.int32),
            np.zeros((B,), bool))
    text = bb._rlc_kernel_h2c(B).lower(*args).as_text(debug_info=True)
    assert "module @jit_rlc_verify_h2c" in text
    for scope in ("cst.rlc.h2c", "cst.rlc.scalar_mul", "cst.rlc.sig_sum",
                  "cst.rlc.miller_loop", "cst.rlc.final_exp"):
        assert scope in text, scope
