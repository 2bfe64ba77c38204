"""Batched G1 FFT butterflies + the FK20 circulant MSM — the producer
kernels behind `das/compute.py`'s all-proofs path.

FK20 (the polynomial-multiproofs route) factors the 128 cell proofs of
one blob through three linear stages over the order-128 root-of-unity
domain:

    hext_j = sum_c  FFT_fr(B^c)_j * X_fft^c_j      (the one MSM)
    C      = IFFT_G1(hext);  E_d = C_{127-d} (d<63), infinity otherwise
    proofs = brp( FFT_G1(E) )

where X_fft^c = FFT_G1 of the residue-c trusted-setup vector — the
bit-reversed Toeplitz/circulant extended-setup tables, computed here as
ONE batched 64-lane G1 FFT at first use and pinned device-resident for
the life of the process (`das/compute.py` owns the cache; this module
owns the kernels).

A G1 FFT is the field FFT with the butterfly's twiddle multiply lifted
to scalar-times-point: log2(n) butterfly rounds, each one windowed
scalar multiplication of the v half (the twiddles are HOST-KNOWN
constants per (n, stage), so each lane's digit schedule bakes into the
kernel and the multiply costs ~64 window steps instead of a 255-step
generic double-and-add) and two point additions.  Shapes ride a pow2
rung ladder (`g1fft_rung`) so jit caches stay tiny; padded lanes are
the point at infinity, which the branchless `curve_jax` formulas
absorb — zero-padding a coefficient vector just evaluates the same
polynomial on the larger domain.

The hext stage is a per-output-position MSM (for each j, a 64-point
sum over the residue classes) run as `pt_msm_pippenger` vmapped over
the 128 positions — digits enter host-side (the field FFT settles to
canonical ints first), points stay device-resident.
"""

from __future__ import annotations

import functools

import numpy as np

from ... import telemetry
from ...utils.jaxtools import jit
from ..bls import curve as _pycurve
from ..fr_batch import R_MODULUS
from . import curve_jax as cj
from . import fq as _fq

# primitive root of the scalar field (the KZG PRIMITIVE_ROOT_OF_UNITY);
# the domain derivation must match `das.ciphersuite.roots_of_unity`
_PRIMITIVE_ROOT = 7

# windowed twiddle multiply: 4-bit windows are the sweet spot for a
# 16-entry shared table per butterfly lane (evens by doubling, odds by
# one add) against ceil(255/4) = 64 window steps
_TW_WINDOW = 4

# FK20 hext MSMs are 64 points each (one per residue class): 16 buckets
# keep the scatter phase at 64 steps and the suffix reduction tiny
_FK20_WINDOW = 4

# point-vector shape ladder: the bottom rung covers the tiny parity
# domains the unit tests drive, the top rung IS the FK20 extended
# domain (CELLS_PER_EXT_BLOB); larger vectors fall back to powers of two
_G1FFT_STEPS = (8, 128)


def _jnp():
    import jax.numpy as jnp
    return jnp


def g1fft_rung(n: int) -> int:
    """Padded point-vector shape for an n-point transform (the
    compile-key launderer the analyzer recognizes, like `_bucket` /
    `das_rung`)."""
    b = 1 if n <= 1 else 1 << (n - 1).bit_length()
    for step in _G1FFT_STEPS:
        if b <= step:
            return step
    return b


@functools.lru_cache(maxsize=8)
def fft_domain(n: int) -> tuple:
    """Order-n roots of unity (w^0 .. w^(n-1)) — same derivation as
    `das.ciphersuite.roots_of_unity` (pinned by tests)."""
    assert n and n & (n - 1) == 0
    w = pow(_PRIMITIVE_ROOT, (R_MODULUS - 1) // n, R_MODULUS)
    return tuple(pow(w, i, R_MODULUS) for i in range(n))


@functools.lru_cache(maxsize=8)
def _bitrev_perm(n: int) -> tuple:
    bits = n.bit_length() - 1
    return tuple(int(f"{i:0{bits}b}"[::-1], 2) if bits else 0
                 for i in range(n))


@functools.lru_cache(maxsize=8)
def _stage_plan(n: int, inverse: bool) -> tuple:
    """Shape-uniform butterfly schedule: every round pairs the same
    n/2 lane count, so the rounds ride ONE `lax.scan` (one compiled
    stage body regardless of log n — per-round shapes would compile
    log n bodies).  Returns (u_idx, v_idx, digits) stacked over the
    log2(n) rounds: round s (half-width h = 2^s) pairs positions
    (b*2h + i, b*2h + h + i) and multiplies the v half by
    roots[i * n/(2h)], encoded as MSB-first window digits."""
    roots = list(fft_domain(n))
    if inverse:
        roots = [roots[0]] + roots[:0:-1]
    half = n // 2
    u_rows, v_rows, d_rows = [], [], []
    h = 1
    while h < n:
        stride = n // (2 * h)
        u_idx = np.empty(half, dtype=np.int32)
        v_idx = np.empty(half, dtype=np.int32)
        tw = []
        for lane in range(half):
            b, i = divmod(lane, h)
            u_idx[lane] = b * 2 * h + i
            v_idx[lane] = u_idx[lane] + h
            tw.append(roots[i * stride])
        u_rows.append(u_idx)
        v_rows.append(v_idx)
        d_rows.append(cj.scalars_to_digits(tw, 255, _TW_WINDOW))
        h *= 2
    return (np.stack(u_rows), np.stack(v_rows), np.stack(d_rows))


def _windowed_mul(v, digs):
    """p -> k*p for per-lane scalars known as window digits: a
    16-entry multiple table (built once per round over every lane) and
    one scan over the MSB-first windows — 4 doublings and one
    table-gather add per step.  Digit 0 gathers the infinity entry,
    which `pt_add` absorbs."""
    import jax
    jnp = _jnp()

    table_n = 1 << _TW_WINDOW
    T = [cj.pt_infinity(cj.F1, v), v]
    for d in range(2, table_n):
        T.append(cj.pt_double(cj.F1, T[d // 2]) if d % 2 == 0
                 else cj.pt_add(cj.F1, T[d - 1], v))
    # (table_n, ..., h, 33) per coordinate
    table = tuple(jnp.stack([t[i] for t in T]) for i in range(3))
    lane = jnp.arange(v[0].shape[-2])

    def step(acc, d):
        for _ in range(_TW_WINDOW):
            acc = cj.pt_double(cj.F1, acc)
        sel = tuple(jnp.moveaxis(tc[d, ..., lane, :], 0, -2)
                    for tc in table)
        return cj.pt_add(cj.F1, acc, sel), None

    acc0 = cj.pt_infinity(cj.F1, v)
    acc, _ = jax.lax.scan(step, acc0, jnp.moveaxis(digs, -1, 0))
    return acc


@functools.lru_cache(maxsize=8)
def _g1_fft_kernel(n: int, batch: int, inverse: bool):
    """Jitted batched G1 FFT: coords (B, n, 33) int32 Jacobian limbs in
    BIT-REVERSED order (Z == 0 encodes infinity), natural-order output.
    One scan over the log2(n) butterfly rounds — each round gathers its
    (u, v) pairs, windowed-multiplies v by its twiddle, and scatters
    u + t / u - t back in place.  The inverse transform runs the
    reversed-root rounds then one fixed scalar multiply by 1/n
    (`pt_scalar_mul_const` — the bit schedule rides the scan's xs)."""
    import jax
    jnp = _jnp()

    plan = _stage_plan(n, inverse)
    inv_bits = None
    if inverse:
        inv_n = pow(n, R_MODULUS - 2, R_MODULUS)
        inv_bits = np.array([int(b) for b in bin(inv_n)[2:]],
                            dtype=np.int32)

    def stage(p, xs):
        u_idx, v_idx, digs = xs
        u = tuple(c[:, u_idx] for c in p)
        v = tuple(c[:, v_idx] for c in p)
        t = _windowed_mul(v, digs)
        plus = cj.pt_add(cj.F1, u, t)
        minus = cj.pt_add(cj.F1, u, cj.pt_neg(cj.F1, t))
        p = tuple(c.at[:, ui].set(pl).at[:, vi].set(mi)
                  for c, ui, vi, pl, mi in zip(
                      p, (u_idx,) * 3, (v_idx,) * 3, plus, minus))
        return p, None

    def g1_fft(x, y, z):
        xs = tuple(jnp.asarray(a) for a in plan)
        p, _ = jax.lax.scan(stage, (x, y, z), xs)
        if inv_bits is not None:
            p = cj.pt_scalar_mul_const(cj.F1, p, inv_bits)
        return p

    return jit(g1_fft)


@functools.lru_cache(maxsize=4)
def _fk20_hext_kernel(n_residues: int, width: int):
    """Jitted FK20 circulant MSM: for each of the `width` extended
    positions j, sum the `n_residues` scalar-point products — one
    `pt_msm_pippenger` per position, vmapped over j.  Points carry a Z
    coordinate so the setup tables' infinity lanes pass through (they
    land in buckets but add nothing); zero digits land in bucket 0,
    which the reduction skips."""
    import jax

    def fk20_hext(x, y, z, digits):
        # x/y/z: (n_residues, width, 33); digits: (n_residues, width, W)
        def one(xx, yy, zz, dd):
            return cj.pt_msm_pippenger(cj.F1, (xx, yy, zz), dd,
                                       _FK20_WINDOW)

        return jax.vmap(one, in_axes=(1, 1, 1, 1))(x, y, z, digits)

    return jit(fk20_hext)


# --- host conversions --------------------------------------------------------


def points_to_limbs(points, pad_to: int | None = None):
    """Oracle Jacobian points -> (x, y, z) Montgomery limb stacks with
    infinity SUPPORT (unlike `g1_affine_to_limbs`): infinities map to
    (1, 1, 0), the branchless kernels' canonical encoding.  `pad_to`
    appends infinity lanes up to the rung."""
    n = pad_to if pad_to is not None else len(points)
    one = _fq.to_mont(1)
    xs = np.zeros((n, _fq.N_LIMBS), dtype=np.int32)
    ys = np.zeros((n, _fq.N_LIMBS), dtype=np.int32)
    zs = np.zeros((n, _fq.N_LIMBS), dtype=np.int32)
    xs[:], ys[:] = one, one
    for i, p in enumerate(points):
        aff = _pycurve.g1.to_affine(p)
        if aff is None:
            continue
        xs[i] = _fq.to_mont(aff[0])
        ys[i] = _fq.to_mont(aff[1])
        zs[i] = one
    return xs, ys, zs


def limbs_to_oracle_list(p) -> list:
    """Device Jacobian coord stacks (..., n, 33) -> list of oracle
    Jacobian tuples (leading axes flattened away, n preserved)."""
    X, Y, Z = (np.asarray(c).reshape(-1, _fq.N_LIMBS) for c in p)
    return [(_fq.from_mont(x), _fq.from_mont(y), _fq.from_mont(z))
            for x, y, z in zip(X, Y, Z)]


# --- entry points ------------------------------------------------------------


def g1_fft_device(x, y, z, inverse: bool = False, block: bool = True):
    """Device-level G1 (I)FFT: coords (B, n, 33) int32 in NATURAL
    order, returns device coords (B, n, 33) — the FK20 chain's internal
    hop (points never leave the device between stages).  Host-side
    bit-reversal is an index permutation on the way in."""
    from ..bls_batch import _dispatch

    jnp = _jnp()
    batch, n = int(x.shape[0]), int(x.shape[1])
    perm = np.array(_bitrev_perm(n))
    with telemetry.span("bls.g1_fft_device", n=n, batch=batch,
                        inverse=bool(inverse)):
        telemetry.count("g1fft.device_calls")
        telemetry.count("g1fft.butterfly_rounds", n.bit_length() - 1)
        args = tuple(jnp.asarray(c)[:, perm] for c in (x, y, z))
        tag = "i" if inverse else "f"
        out = _dispatch(
            f"g1_fft@{n}x{batch}{tag}",
            # cst: allow(recompile-unbucketed-dim): n is g1fft_rung-
            # laundered by every caller and batch is the FK20 residue
            # count (64) or a single vector — a handful of compiles
            # per process
            _g1_fft_kernel(n, batch, bool(inverse)),
            args, block=block)
    return out


def g1_fft_async(points, inverse: bool = False, block: bool = True):
    """G1 FFT of an oracle point vector over the order-`g1fft_rung(n)`
    root-of-unity domain (short vectors are zero-padded — i.e. the
    same polynomial evaluated on the rung domain).  Settles to a list
    of oracle Jacobian points.

    The transform matches the field `_fft` shape exactly: out_i =
    sum_j w^(i*j) * P_j with w the rung-order primitive root — parity
    vs naive per-point evaluation is pinned by tests/test_das.py."""
    from ...serve.futures import value_future
    from .. import bls_batch as _bb

    n_live = len(points)
    assert n_live >= 1
    rung = g1fft_rung(n_live)
    with telemetry.span("bls.g1_fft", live=n_live, padded=rung,
                        inverse=bool(inverse)):
        telemetry.count("g1fft.calls")
        _bb._count_lanes(n_live, rung)
        x, y, z = points_to_limbs(points, pad_to=rung)
        out = g1_fft_device(x[None], y[None], z[None],
                            inverse=inverse, block=block)
    return value_future(out, convert=limbs_to_oracle_list)


def g1_fft(points, inverse: bool = False) -> list:
    """Synchronous facade over `g1_fft_async`."""
    return g1_fft_async(points, inverse=inverse).result()


def fk20_hext_device(x, y, z, scalars, block: bool = True):
    """The FK20 'one MSM': device setup-table coords (n_residues,
    width, 33) against host canonical scalar rows (n_residues x width
    ints, the settled field-FFT outputs) -> device coords (width, 33)
    of hext_j = sum_c scalars[c][j] * X[c][j]."""
    from ..bls_batch import _dispatch

    jnp = _jnp()
    n_res, width = int(x.shape[0]), int(x.shape[1])
    flat = [int(s) % R_MODULUS for row in scalars for s in row]
    assert len(flat) == n_res * width
    with telemetry.span("bls.fk20_hext", residues=n_res, width=width):
        telemetry.count("g1fft.hext_calls")
        digits = cj.scalars_to_digits(flat, 255, _FK20_WINDOW).reshape(
            n_res, width, -1)
        out = _dispatch(
            f"fk20_hext@{n_res}x{width}",
            # cst: allow(recompile-unbucketed-dim): (n_residues, width)
            # is the FK20 circulant shape — preset-fixed at (64, 128) —
            # so the kernel compiles once per process
            _fk20_hext_kernel(n_res, width),
            (jnp.asarray(x), jnp.asarray(y), jnp.asarray(z),
             jnp.asarray(digits)), block=block)
    return out
