"""Subgroup membership: the endomorphism tests against the full-order one.

`subgroup_check_g1` decides sigma(P) == -[x^2]P and `subgroup_check_g2`
decides psi(P) == [x]P (eprint 2021/1130, 2022/352).  Both must answer
exactly as `[r]P == O` does on every point of the curve, so each case
below compares them with that check, kept here as the reference: subgroup
points, random curve points, points of each small prime order dividing the
cofactor, the eigenpoints of the endomorphism among those, their sums with
subgroup points, and infinity.  The wire-level tests follow an
off-subgroup point through every parse that refuses it.
"""

import random
from functools import lru_cache
from typing import Callable, NamedTuple

import pytest

from consensus_specs_tpu.ops.bls import ciphersuite
from consensus_specs_tpu.ops.bls.curve import (
    B1,
    B2,
    G1_GEN,
    G2_GEN,
    H1,
    H2,
    _random_twist_point,
    g1,
    g1_from_bytes,
    g1_to_bytes,
    g2,
    g2_from_bytes,
    g2_to_bytes,
    psi_g2,
    sigma_g1,
    subgroup_check_g1,
    subgroup_check_g2,
)
from consensus_specs_tpu.ops.bls.fields import BLS_X, Q, R, Fq2, _fq_sqrt


def _full_order_check(grp, p) -> bool:
    """The reference: on the curve and [r]P == O."""
    return grp.on_curve(p) and grp.is_inf(grp.mul_full(p, R))


def _random_g1_point(rng):
    while True:
        x = rng.randrange(Q)
        y = _fq_sqrt(x * x * x + B1)
        if y is not None:
            return g1.from_affine(x, y)


def _random_g2_point(rng):
    while True:
        x = Fq2(rng.randrange(Q), rng.randrange(Q))
        y = (x.square() * x + B2).sqrt()
        if y is not None:
            return g2.from_affine(x, y)


class Group(NamedTuple):
    grp: object
    check: Callable
    gen: tuple
    cofactor: int
    random_point: Callable
    endo: Callable
    # endo^2 - trace endo + norm = 0 on the whole curve
    trace: int
    norm: int


GROUPS = {
    "G1": Group(g1, subgroup_check_g1, G1_GEN, H1, _random_g1_point,
                sigma_g1, -1, 1),
    "G2": Group(g2, subgroup_check_g2, G2_GEN, H2, _random_g2_point,
                psi_g2, BLS_X + 1, Q),
}

SMALL_PRIMES = {
    "G1": [3, 11, 10177, 859267],
    "G2": [13, 23, 2713, 11953, 262069],
}


def _small_prime_factors(n: int, bound: int = 10 ** 6) -> list[int]:
    found = []
    for ell in range(2, bound):
        if n % ell == 0:
            found.append(ell)
            while n % ell == 0:
                n //= ell
    return found


def _point_of_order(g: Group, ell: int, rng):
    """[R H / ell^e]Q for a random Q, ell^e the power of ell in H, then
    multiplied by ell down to order ell: where the ell-part has exponent
    ell, [R H / ell]Q would be O for every Q."""
    grp = g.grp
    ell_e = ell
    while g.cofactor % (ell_e * ell) == 0:
        ell_e *= ell
    p = grp.infinity()
    while grp.is_inf(p):
        p = grp.mul_full(g.random_point(rng), R * g.cofactor // ell_e)
    while not grp.is_inf(grp.mul_full(p, ell)):
        p = grp.mul_full(p, ell)
    return p


def _eigenpoints(g: Group, ell: int, rng, draws: int = 4) -> list:
    """For each eigenvalue lam of the endomorphism mod ell, a point of order
    ell on which it acts as [lam]: endo(T) - [mu]T, mu the other eigenvalue,
    for random T of order ell until each eigenvalue has one.  These are the
    points that a check with a wrong eigenvalue would accept."""
    grp = g.grp
    roots = [lam for lam in range(ell)
             if (lam * lam - g.trace * lam + g.norm) % ell == 0]
    found = {}
    for _ in range(draws if roots else 0):
        t = _point_of_order(g, ell, rng)
        for lam in roots:
            mu = next((m for m in roots if m != lam), lam)
            e = grp.add(g.endo(t), grp.neg(grp.mul_full(t, mu)))
            if lam not in found and not grp.is_inf(e):
                assert grp.eq_points(g.endo(e), grp.mul_full(e, lam))
                found[lam] = e
        if len(found) == len(roots):
            break
    return list(found.values())


@lru_cache(maxsize=None)
def _points(group: str) -> dict:
    """Every case's points for one group, drawn once from a fixed seed."""
    g = GROUPS[group]
    grp = g.grp
    rng = random.Random(f"subgroup-{group}")
    subgroup = [grp.mul(g.gen, rng.randrange(1, R)) for _ in range(16)]
    assert all(not grp.feq(p[2], grp.F_one) for p in subgroup)
    small_order = [_point_of_order(g, ell, rng)
                   for ell in _small_prime_factors(g.cofactor)]
    eigen = [e for ell in _small_prime_factors(g.cofactor)
             for e in _eigenpoints(g, ell, rng)]
    return {
        "subgroup_jacobian": subgroup,
        "subgroup_affine": [grp.from_affine(*grp.to_affine(p))
                            for p in subgroup],
        "off_subgroup": [g.random_point(rng) for _ in range(16)],
        "small_order": small_order,
        "small_order_eigenpoints": eigen,
        "subgroup_plus_small_order": [
            grp.add(subgroup[i % 16], p)
            for i, p in enumerate(small_order + eigen)],
        "infinity": [grp.infinity()],
    }


EXPECTED = {
    "subgroup_jacobian": True,
    "subgroup_affine": True,
    "off_subgroup": False,
    "small_order": False,
    "small_order_eigenpoints": False,
    "subgroup_plus_small_order": False,
    "infinity": True,
}


@pytest.mark.parametrize("case", list(EXPECTED))
@pytest.mark.parametrize("group", list(GROUPS))
def test_endomorphism_check_matches_full_order(group, case):
    g = GROUPS[group]
    points = _points(group)[case]
    assert points
    for p in points:
        want = _full_order_check(g.grp, p)
        assert want is EXPECTED[case]
        assert g.check(p) is want


@pytest.mark.parametrize("group", list(GROUPS))
def test_cofactor_small_primes_found_by_trial_division(group):
    assert _small_prime_factors(GROUPS[group].cofactor) == SMALL_PRIMES[group]
    assert len(_points(group)["small_order"]) == len(SMALL_PRIMES[group])


def test_sigma_acts_on_g1_as_minus_x_squared():
    # `mul` reduces its scalar mod r, a path apart from the check's ladders
    for p in (G1_GEN, _points("G1")["subgroup_jacobian"][0]):
        assert g1.eq_points(sigma_g1(p), g1.mul(p, -(BLS_X ** 2)))
        assert g1.eq_points(sigma_g1(sigma_g1(sigma_g1(p))), p)
        assert not g1.eq_points(sigma_g1(p), p)


def test_psi_acts_on_g2_as_x():
    for p in (G2_GEN, _points("G2")["subgroup_jacobian"][0]):
        assert g2.eq_points(psi_g2(p), g2.mul(p, BLS_X))
        assert not g2.eq_points(psi_g2(p), p)


# --- wire level -------------------------------------------------------------


def _uncached(fn):
    """The session memo of the test suite wraps the point parses."""
    return getattr(fn, "__wrapped__", fn)


@lru_cache(maxsize=1)
def _off_subgroup_pubkey() -> bytes:
    from consensus_specs_tpu.testlib.kzg_fixtures import invalid_g1_points

    data = invalid_g1_points()[2]
    p = g1_from_bytes(data)
    assert g1.on_curve(p) and not _full_order_check(g1, p)
    assert g1_to_bytes(p) == data
    return data


@lru_cache(maxsize=1)
def _off_subgroup_signature() -> bytes:
    q = _random_twist_point(12345)
    assert g2.on_curve(q) and not _full_order_check(g2, q)
    data = g2_to_bytes(q)
    assert g2.eq_points(g2_from_bytes(data), q)
    return data


@lru_cache(maxsize=1)
def _valid_statement():
    sk, msg = 2024, b"\x5a" * 32
    return ciphersuite.SkToPk(sk), msg, ciphersuite.Sign(sk, msg)


def _statement_with(bad: str):
    pk, msg, sig = _valid_statement()
    if bad == "pubkey":
        return [pk, _off_subgroup_pubkey()], msg, sig
    return [pk], msg, _off_subgroup_signature()


def test_key_validate_refuses_off_subgroup_pubkey():
    assert ciphersuite.KeyValidate(_valid_statement()[0])
    assert not ciphersuite.KeyValidate(_off_subgroup_pubkey())


def test_point_parses_raise_on_off_subgroup_points():
    with pytest.raises(ValueError, match="invalid pubkey"):
        _uncached(ciphersuite._pk_to_point)(_off_subgroup_pubkey())
    with pytest.raises(ValueError, match="not in G2 subgroup"):
        _uncached(ciphersuite._sig_to_point)(_off_subgroup_signature())


@pytest.mark.parametrize("bad", ["pubkey", "signature"])
def test_parse_fast_aggregate_task_refuses_off_subgroup(bad):
    pk, msg, sig = _valid_statement()
    assert ciphersuite.parse_fast_aggregate_task([pk], msg, sig) is not None
    assert ciphersuite.parse_fast_aggregate_task(*_statement_with(bad)) is None


@pytest.mark.parametrize("bad", ["pubkey", "signature"])
def test_serve_submit_settles_false_without_dispatch(bad, monkeypatch):
    from consensus_specs_tpu.serve import executor as ex_mod

    monkeypatch.setattr(ex_mod, "_ops_bls_batch",
                        lambda: pytest.fail("an invalid submit dispatched"))
    ex = ex_mod.ServeExecutor()
    fut = ex.submit_fast_aggregate_verify(*_statement_with(bad))
    assert fut.done() and fut.result() is False
    ex.drain()
    st = ex.stats()
    assert st["submitted"] == 0 and st["batches"] == 0
