"""Curve + pairing oracle tests."""

import random

import pytest

from distributed_plonk_tpu import curve as C
from distributed_plonk_tpu import fields as F
from distributed_plonk_tpu.constants import R_MOD, Q_MOD, BLS_X, BLS_X_IS_NEG
from distributed_plonk_tpu.fields import fq12_pow, FQ12_ONE

rng = random.Random(0xC1C1E)


def _msb_mul_affine(p, k):
    t = None
    for b in bin(k)[2:]:
        t = C.g1_add_affine(t, t) if t is not None else None
        if b == "1":
            t = C.g1_add_affine(t, p)
    return t


def test_generators_on_curve_and_order():
    assert C.g1_is_on_curve(C.G1_GEN)
    assert C.g2_is_on_curve(C.G2_GEN)
    # unreduced scalar: r * G == O (g1_mul reduces mod r, so do it manually)
    assert _msb_mul_affine(C.G1_GEN, R_MOD) is None
    assert C.g2_mul(C.G2_GEN, R_MOD - 1) == C.g2_neg(C.G2_GEN)


def test_g1_jacobian_vs_affine():
    p = C.G1_GEN
    for k in [2, 3, 5, 17, 12345, rng.randrange(1 << 64)]:
        assert C.g1_mul(p, k) == _msb_mul_affine(p, k)


def test_g1_add_edge_cases():
    p = C.G1_GEN
    assert C.g1_add_affine(p, None) == p
    assert C.g1_add_affine(None, p) == p
    assert C.g1_add_affine(p, C.g1_neg(p)) is None
    assert C.g1_add_affine(p, p) == C.g1_mul(p, 2)
    j = C.g1_jac_add(C.g1_to_jac(p), (1, 1, 0))
    assert C.g1_from_jac(j) == p


def test_msm_oracle_matches_naive():
    n = 16
    pts = [C.g1_mul(C.G1_GEN, rng.randrange(R_MOD)) for _ in range(n)]
    pts[3] = None  # infinity padding, as the reference's SRS zero-pad
    scalars = [rng.randrange(R_MOD) for _ in range(n)]
    scalars[5] = 0
    naive = None
    for p, s in zip(pts, scalars):
        if p is not None:
            naive = C.g1_add_affine(naive, C.g1_mul(p, s))
    assert C.g1_msm(pts, scalars) == naive


def test_pairing_bilinear():
    a, b = 1234567, 7654321
    e = C.pairing(C.G1_GEN, C.G2_GEN)
    assert e != FQ12_ONE
    assert C.pairing(C.g1_mul(C.G1_GEN, a), C.g2_mul(C.G2_GEN, b)) == fq12_pow(e, a * b % R_MOD)


def test_pairing_check():
    k = 424242
    good = [
        (C.g1_mul(C.G1_GEN, k), C.G2_GEN),
        (C.g1_neg(C.G1_GEN), C.g2_mul(C.G2_GEN, k)),
    ]
    assert C.pairing_check(good)
    bad = [
        (C.g1_mul(C.G1_GEN, k), C.G2_GEN),
        (C.g1_neg(C.G1_GEN), C.g2_mul(C.G2_GEN, k + 1)),
    ]
    assert not C.pairing_check(bad)


# --- the ate pairing's parts, each against a plain oracle ---------------------

# the exponent the package used to raise a Miller value to, bit by bit
FULL_EXP = (Q_MOD ** 12 - 1) // R_MOD


def _rand_fq2(r):
    return (r.randrange(Q_MOD), r.randrange(Q_MOD))


def _rand_fq12(r):
    return (tuple(_rand_fq2(r) for _ in range(3)),
            tuple(_rand_fq2(r) for _ in range(3)))


def _rand_g1(r):
    return C.g1_mul(C.G1_GEN, r.randrange(1, R_MOD))


def _rand_g2(r):
    return C.g2_mul(C.G2_GEN, r.randrange(1, R_MOD))


def test_hard_part_identity_in_the_curve_parameter():
    x = -BLS_X if BLS_X_IS_NEG else BLS_X
    assert R_MOD == x ** 4 - x ** 2 + 1
    assert 3 * ((Q_MOD ** 4 - Q_MOD ** 2 + 1) // R_MOD) == (
        (x - 1) ** 2 * (x + Q_MOD) * (x ** 2 + Q_MOD ** 2 - 1) + 3)
    assert FULL_EXP == (Q_MOD ** 6 - 1) * (Q_MOD ** 2 + 1) * (
        (Q_MOD ** 4 - Q_MOD ** 2 + 1) // R_MOD)


@pytest.mark.parametrize("case", ["miller-%d" % i for i in range(8)]
                         + ["random-%d" % i for i in range(4)])
def test_final_exponentiation_is_the_cube_of_the_full_power(case):
    """Over Miller values AND over arbitrary Fq12 elements, which the easy
    part sends into the cyclotomic subgroup the chain in x relies on."""
    kind, i = case.split("-")
    r = random.Random(0xF1A7 + int(i))
    if kind == "miller":
        f = C.miller_loop([(_rand_g1(r), _rand_g2(r))])
    else:
        f = _rand_fq12(r)
    assert C.final_exponentiation(f) == fq12_pow(fq12_pow(f, FULL_EXP), 3)


@pytest.mark.parametrize("seed", range(3))
def test_fq12_shortcuts_against_the_plain_operations(seed):
    r = random.Random(0xF12 + seed)
    a = _rand_fq12(r)
    assert F.fq12_frobenius(a) == fq12_pow(a, Q_MOD)
    assert F.fq12_sq(a) == F.fq12_mul(a, a)
    assert F.fq12_mul(F.fq12_conj(a), a) == F.fq12_mul(a, F.fq12_conj(a))
    b0, b1, b4 = _rand_fq2(r), _rand_fq2(r), _rand_fq2(r)
    dense = ((b0, b1, F.FQ2_ZERO), (F.FQ2_ZERO, b4, F.FQ2_ZERO))
    assert F.fq12_mul_sparse(a, b0, b1, b4) == F.fq12_mul(a, dense)
    x6 = (_rand_fq2(r), _rand_fq2(r), _rand_fq2(r))
    assert F.fq6_mul_by_01(x6, b0, b1) == F.fq6_mul(x6, (b0, b1, F.FQ2_ZERO))
    assert F.fq6_mul_by_1(x6, b4) == F.fq6_mul(
        x6, (F.FQ2_ZERO, b4, F.FQ2_ZERO))
    z = r.randrange(1, Q_MOD)
    assert F.fq_inv(z) == pow(z, Q_MOD - 2, Q_MOD)
    with pytest.raises(ZeroDivisionError):
        F.fq_inv(0)


def test_pairing_bilinear_in_each_argument_and_nondegenerate():
    r = random.Random(0xB111)
    p, q = _rand_g1(r), _rand_g2(r)
    a = r.randrange(2, 1 << 64)
    e = C.pairing(p, q)
    assert e != FQ12_ONE
    assert fq12_pow(e, R_MOD) == FQ12_ONE          # a value of order r
    assert C.pairing(C.g1_mul(p, a), q) == fq12_pow(e, a)
    assert C.pairing(p, C.g2_mul(q, a)) == fq12_pow(e, a)
    p2 = _rand_g1(r)
    assert C.pairing(C.g1_add_affine(p, p2), q) == F.fq12_mul(
        e, C.pairing(p2, q))
    q2 = _rand_g2(r)
    assert C.pairing(p, C.g2_add(q, q2)) == F.fq12_mul(e, C.pairing(p, q2))
    assert C.pairing(C.g1_neg(p), q) == F.fq12_conj(e)
    assert C.pairing(None, q) == FQ12_ONE
    assert C.pairing(p, None) == FQ12_ONE


def test_miller_loop_shares_one_accumulator_over_the_pairs():
    """The product of the single-pair loops, up to what the final
    exponentiation kills: the two agree after it."""
    r = random.Random(0x5A4E)
    pairs = [(_rand_g1(r), _rand_g2(r)) for _ in range(3)] + [(None, C.G2_GEN)]
    want = FQ12_ONE
    for p, q in pairs:
        want = F.fq12_mul(want, C.pairing(p, q))
    assert C.final_exponentiation(C.miller_loop(pairs)) == want


@pytest.mark.parametrize("seed", range(6))
def test_pairing_check_products_and_each_scalar_off_by_one(seed):
    r = random.Random(0xC4EC + seed)
    p, q = _rand_g1(r), _rand_g2(r)
    a, b = r.randrange(2, R_MOD), r.randrange(2, R_MOD)

    def two(a1, a2):      # e(a1 P, Q) e(-P, a2 Q)
        return [(C.g1_mul(p, a1), q), (C.g1_neg(p), C.g2_mul(q, a2))]

    def three(a1, b1, c1):  # e(a1 P, Q) e(P, b1 Q) e(-c1 P, Q)
        return [(C.g1_mul(p, a1), q), (p, C.g2_mul(q, b1)),
                (C.g1_neg(C.g1_mul(p, c1)), q)]

    C.reset_pairing_counters()
    assert C.pairing_check(two(a, a))
    assert C.PAIRING_COUNTERS == {"checks": 1, "pairs": 2}
    assert not C.pairing_check(two(a + 1, a))
    assert not C.pairing_check(two(a, a + 1))
    assert C.pairing_check(three(a, b, (a + b) % R_MOD))
    assert C.pairing_check(three(a, b, (a + b) % R_MOD) + [(None, q)])
    assert C.PAIRING_COUNTERS == {"checks": 5, "pairs": 12}
    assert not C.pairing_check(three(a + 1, b, (a + b) % R_MOD))
    assert not C.pairing_check(three(a, b + 1, (a + b) % R_MOD))
    assert not C.pairing_check(three(a, b, (a + b + 1) % R_MOD))


@pytest.mark.parametrize("n", [0, 1, 2, 31, 300])
def test_msm_equals_the_naive_sum_at_every_window(n):
    """The window follows the length (1 at two points, 3 at 31, 8 from 256
    up); the value may not. Infinity points and zero scalars among them."""
    r = random.Random(0x35 + n)
    step = _rand_g1(r)
    pts, acc = [], C.G1_GEN
    for _ in range(n):          # distinct points by addition, not n muls
        pts.append(acc)
        acc = C.g1_add_affine(acc, step)
    scalars = [r.randrange(R_MOD) for _ in range(n)]
    for i in range(0, n, 7):
        pts[i] = None
    for i in range(1, n, 5):
        scalars[i] = 0
    if n > 2:
        scalars[2] = 1
        scalars[3 % n] = R_MOD - 1
    naive = None
    for p, s in zip(pts, scalars):
        if p is not None:
            naive = C.g1_add_affine(naive, C.g1_mul(p, s))
    assert C.g1_msm(pts, scalars) == naive
