"""Pure-Python reference BLS12-381 curve arithmetic + pairing (CPU oracle).

Replaces the role of `ark-ec`/`ark-bls12-381` in the reference
(/root/reference/Cargo.toml:31-37, used at src/worker.rs:122 for MSM and in
jf-plonk's verifier). The TPU G1 kernels are tested bit-identical against
these ops; the pairing is only used host-side by the verifier. It is the
optimal ate pairing in the curve's parameter (constants.BLS_X, negative:
BLS_X_IS_NEG), up to a cube: see final_exponentiation.

Point formats:
  G1 affine:   (x, y) ints, or None for the point at infinity.
  G1 jacobian: (X, Y, Z) with x = X/Z^2, y = Y/Z^3; Z == 0 -> infinity.
  G2 affine:   ((x0,x1), (y0,y1)) Fq2 pairs, or None.
"""

from .constants import (
    Q_MOD,
    R_MOD,
    BLS_X,
    BLS_X_IS_NEG,
    G1_GEN_X,
    G1_GEN_Y,
    G2_GEN_X,
    G2_GEN_Y,
)
from .fields import (
    fq_inv,
    fq2_add,
    fq2_sub,
    fq2_mul,
    fq2_sq,
    fq2_scalar,
    fq2_inv,
    fq2_neg,
    fq12_mul,
    fq12_mul_sparse,
    fq12_sq,
    fq12_inv,
    fq12_conj,
    fq12_frobenius,
    FQ12_ONE,
)

G1_GEN = (G1_GEN_X, G1_GEN_Y)
G2_GEN = (G2_GEN_X, G2_GEN_Y)

INF = None


# --- G1 affine / jacobian ----------------------------------------------------

def g1_is_on_curve(p):
    if p is None:
        return True
    x, y = p
    return (y * y - (x * x % Q_MOD * x + 4)) % Q_MOD == 0


def g1_neg(p):
    if p is None:
        return None
    return (p[0], (-p[1]) % Q_MOD)


def g1_add_affine(p, q):
    if p is None:
        return q
    if q is None:
        return p
    x1, y1 = p
    x2, y2 = q
    if x1 == x2:
        if (y1 + y2) % Q_MOD == 0:
            return None
        lam = 3 * x1 * x1 % Q_MOD * fq_inv(2 * y1 % Q_MOD) % Q_MOD
    else:
        lam = (y2 - y1) * fq_inv((x2 - x1) % Q_MOD) % Q_MOD
    x3 = (lam * lam - x1 - x2) % Q_MOD
    y3 = (lam * (x1 - x3) - y1) % Q_MOD
    return (x3, y3)


def g1_to_jac(p):
    if p is None:
        return (1, 1, 0)
    return (p[0], p[1], 1)


def g1_from_jac(j):
    X, Y, Z = j
    if Z == 0:
        return None
    zinv = fq_inv(Z)
    z2 = zinv * zinv % Q_MOD
    return (X * z2 % Q_MOD, Y * z2 % Q_MOD * zinv % Q_MOD)


def g1_jac_double(j):
    X1, Y1, Z1 = j
    if Z1 == 0:
        return j
    return _g1_jac_double_nonzero(X1, Y1, Z1)


def _g1_jac_double_nonzero(X1, Y1, Z1):
    # dbl-2009-l (a = 0)
    A = X1 * X1 % Q_MOD
    B = Y1 * Y1 % Q_MOD
    C = B * B % Q_MOD
    D = 2 * ((X1 + B) * (X1 + B) - A - C) % Q_MOD
    E = 3 * A % Q_MOD
    Fv = E * E % Q_MOD
    X3 = (Fv - 2 * D) % Q_MOD
    Y3 = (E * (D - X3) - 8 * C) % Q_MOD
    Z3 = 2 * Y1 * Z1 % Q_MOD
    return (X3, Y3, Z3)


def g1_jac_add(j1, j2):
    X1, Y1, Z1 = j1
    X2, Y2, Z2 = j2
    if Z1 == 0:
        return j2
    if Z2 == 0:
        return j1
    Z1Z1 = Z1 * Z1 % Q_MOD
    Z2Z2 = Z2 * Z2 % Q_MOD
    U1 = X1 * Z2Z2 % Q_MOD
    U2 = X2 * Z1Z1 % Q_MOD
    S1 = Y1 * Z2 % Q_MOD * Z2Z2 % Q_MOD
    S2 = Y2 * Z1 % Q_MOD * Z1Z1 % Q_MOD
    if U1 == U2:
        if S1 != S2:
            return (1, 1, 0)
        return _g1_jac_double_nonzero(X1, Y1, Z1)
    H = (U2 - U1) % Q_MOD
    I = 4 * H * H % Q_MOD
    J = H * I % Q_MOD
    rr = 2 * (S2 - S1) % Q_MOD
    V = U1 * I % Q_MOD
    X3 = (rr * rr - J - 2 * V) % Q_MOD
    Y3 = (rr * (V - X3) - 2 * S1 * J) % Q_MOD
    Z3 = ((Z1 + Z2) * (Z1 + Z2) - Z1Z1 - Z2Z2) % Q_MOD * H % Q_MOD
    return (X3, Y3, Z3)


def g1_mul(p, k, reduce=True):
    """Scalar multiplication (double-and-add, jacobian).

    reduce=False keeps k unreduced mod r — needed by subgroup checks
    (r·p = O?), where reducing would turn the check into 0·p."""
    if reduce:
        k %= R_MOD
    acc = (1, 1, 0)
    base = g1_to_jac(p)
    while k > 0:
        if k & 1:
            acc = g1_jac_add(acc, base)
        base = g1_jac_double(base)
        k >>= 1
    return g1_from_jac(acc)


def g1_msm(points, scalars):
    """Reference variable-base MSM (Pippenger).

    Oracle for the device MSM (reference behavior: src/worker.rs:159-185).
    Accepts affine points (None = infinity, as produced by the reference's
    zero-padding of the SRS at src/dispatcher2.rs:208).

    The window follows the length: a window's bucket sums cost 2^(c+1)
    additions whatever the length and filling the buckets one a point, so
    the verifier's thirty points want c = 3 where a commit key wants 8.
    From 256 points up it stays 8, as it always was: those are the key
    builds and the oracle's commitments. The value does not depend on it.
    """
    assert len(points) == len(scalars)
    scalars = [s % R_MOD for s in scalars]
    n = len(points)
    c = 8 if n >= 256 else max(1, n.bit_length() - 3)
    num_windows = (R_MOD.bit_length() + c - 1) // c
    window_sums = []
    for w in range(num_windows):
        buckets = [(1, 1, 0)] * ((1 << c) - 1)
        shift = w * c
        for p, s in zip(points, scalars):
            if p is None:
                continue
            digit = (s >> shift) & ((1 << c) - 1)
            if digit != 0:
                buckets[digit - 1] = g1_jac_add(buckets[digit - 1], g1_to_jac(p))
        acc = (1, 1, 0)
        running = (1, 1, 0)
        for b in reversed(buckets):
            running = g1_jac_add(running, b)
            acc = g1_jac_add(acc, running)
        window_sums.append(acc)
    total = (1, 1, 0)
    for ws in reversed(window_sums):
        for _ in range(c):
            total = g1_jac_double(total)
        total = g1_jac_add(total, ws)
    return g1_from_jac(total)


# --- G2 affine ---------------------------------------------------------------

def g2_is_on_curve(p):
    if p is None:
        return True
    x, y = p
    rhs = fq2_add(fq2_mul(fq2_sq(x), x), (4, 4))
    return fq2_sub(fq2_sq(y), rhs) == (0, 0)


def g2_neg(p):
    if p is None:
        return None
    return (p[0], fq2_neg(p[1]))


def g2_add(p, q):
    if p is None:
        return q
    if q is None:
        return p
    x1, y1 = p
    x2, y2 = q
    if x1 == x2:
        if fq2_add(y1, y2) == (0, 0):
            return None
        lam = fq2_mul(fq2_mul((3, 0), fq2_sq(x1)), fq2_inv(fq2_mul((2, 0), y1)))
    else:
        lam = fq2_mul(fq2_sub(y2, y1), fq2_inv(fq2_sub(x2, x1)))
    x3 = fq2_sub(fq2_sub(fq2_sq(lam), x1), x2)
    y3 = fq2_sub(fq2_mul(lam, fq2_sub(x1, x3)), y1)
    return (x3, y3)


def g2_mul(p, k, reduce=True):
    if reduce:
        k %= R_MOD
    acc = None
    base = p
    while k > 0:
        if k & 1:
            acc = g2_add(acc, base)
        base = g2_add(base, base)
        k >>= 1
    return acc


# --- Pairing (optimal ate) ----------------------------------------------------
#
# e(P, Q) = f_{x,Q}(P)^((q^12 - 1)/r), P in G1, Q in G2, x the curve's
# parameter (constants.BLS_X its absolute value, BLS_X_IS_NEG its sign): the
# Miller loop runs over the 64 bits of |x|, not the 255 of r, its running
# point stays on the twist E'/Fq2 (y^2 = x^3 + 4 xi, the M-twist: (x, y) ->
# (x / w^2, y / w^3) lands on E(Fq12) since w^6 = xi), and each line comes
# out with three Fq2 coefficients of twelve. The final exponentiation is
# split by the structure of q^12 - 1 and its hard part runs in x.

_ATE_BITS = [int(b) for b in bin(BLS_X)[3:]]  # below the leading one


def _line_step(t, q, neg_xp, yp):
    """(the line through T and Q, the tangent where Q is T, at P; T + Q),
    by g2_add's slopes. The line's value is taken times w^3, a factor in
    Fq4 that the final exponentiation sends to 1:
    (lam x_T - y_T) - lam x_P v + y_P v w, three coefficients of twelve."""
    (x1, y1), (x2, y2) = t, q
    if t == q:
        lam = fq2_mul(fq2_scalar(fq2_sq(x1), 3), fq2_inv(fq2_add(y1, y1)))
    else:
        lam = fq2_mul(fq2_sub(y2, y1), fq2_inv(fq2_sub(x2, x1)))
    x3 = fq2_sub(fq2_sub(fq2_sq(lam), x1), x2)
    y3 = fq2_sub(fq2_mul(lam, fq2_sub(x1, x3)), y1)
    line = (fq2_sub(fq2_mul(lam, x1), y1), fq2_scalar(lam, neg_xp), yp)
    return line, (x3, y3)


def miller_loop(pairs):
    """prod_i f_{x,Q_i}(P_i) over [(P_i, Q_i)], before the final
    exponentiation: ONE accumulator, squared once a bit for all the pairs.

    P_i is a G1 affine point, Q_i a G2 affine point of order r (a verifying
    key's are; a point of small order ends in a vertical line, whose slope
    raises ZeroDivisionError). A pair with a point at infinity
    contributes 1.
    """
    live = [(-p[0] % Q_MOD, (p[1], 0), q) for p, q in pairs
            if p is not None and q is not None]
    running = [q for _, _, q in live]
    f = FQ12_ONE
    for bit in _ATE_BITS:
        f = fq12_sq(f)
        for i, (neg_xp, yp, q) in enumerate(live):
            t = running[i]
            line, t = _line_step(t, t, neg_xp, yp)
            f = fq12_mul_sparse(f, *line)
            if bit:
                line, t = _line_step(t, q, neg_xp, yp)
                f = fq12_mul_sparse(f, *line)
            running[i] = t
    # f_{-|x|,Q} = 1 / f_{|x|,Q} up to a vertical line, and 1/f is conj(f)
    # up to a factor the final exponentiation kills
    return fq12_conj(f) if BLS_X_IS_NEG else f


def _pow_x(a):
    """a^x for a in the cyclotomic subgroup (where 1/a = conj(a))."""
    result = a
    for bit in _ATE_BITS:
        result = fq12_sq(result)
        if bit:
            result = fq12_mul(result, a)
    return fq12_conj(result) if BLS_X_IS_NEG else result


def final_exponentiation(f):
    """f^(3 (q^12 - 1)/r): the CUBE of the textbook reduced value, so 1
    exactly when that is 1 (3 does not divide r) and as bilinear.

    (q^12 - 1)/r = (q^6 - 1)(q^2 + 1) * (q^4 - q^2 + 1)/r. The easy part is
    a conjugate, an inverse and a Frobenius. It lands in the cyclotomic
    subgroup, where an inverse is a conjugate, and the hard part goes by
        3 (q^4 - q^2 + 1)/r = (x - 1)^2 (x + q)(x^2 + q^2 - 1) + 3
    (Hayashida, Hayasaka, Teruya, eprint 2020/875): five powers by x.
    """
    m = fq12_mul(fq12_conj(f), fq12_inv(f))                    # ^(q^6 - 1)
    m = fq12_mul(fq12_frobenius(fq12_frobenius(m)), m)         # ^(q^2 + 1)
    a = fq12_mul(_pow_x(m), fq12_conj(m))                      # m^(x - 1)
    a = fq12_mul(_pow_x(a), fq12_conj(a))                      # ^(x - 1)
    a = fq12_mul(_pow_x(a), fq12_frobenius(a))                 # ^(x + q)
    a = fq12_mul(fq12_mul(_pow_x(_pow_x(a)),                   # ^(x^2+q^2-1)
                          fq12_frobenius(fq12_frobenius(a))),
                 fq12_conj(a))
    return fq12_mul(a, fq12_mul(fq12_sq(m), m))                # * m^3


# pairing-cost accounting: aggregation's whole value proposition is
# "N proofs, one 2-pair check", so tests pin the claim against these
# counters instead of trusting the docstring (reset_pairing_counters()
# then assert checks == 1 and pairs == 2 after verify_aggregate).
PAIRING_COUNTERS = {"checks": 0, "pairs": 0}


def reset_pairing_counters():
    PAIRING_COUNTERS["checks"] = 0
    PAIRING_COUNTERS["pairs"] = 0


def pairing_check(pairs):
    """Return True iff prod e(P_i, Q_i) == 1.

    Multi-pairing: one Miller loop and one final exponentiation for all
    the pairs. This is all the verifier needs (KZG check at jf-plonk's
    verify, reference src/dispatcher2.rs:1290-1293).
    """
    PAIRING_COUNTERS["checks"] += 1
    PAIRING_COUNTERS["pairs"] += sum(
        1 for p, q in pairs if p is not None and q is not None)
    return final_exponentiation(miller_loop(pairs)) == FQ12_ONE


def pairing(p, q):
    """The pairing's value, e(P, Q)^3 (see final_exponentiation); tests
    use it for bilinearity."""
    return final_exponentiation(miller_loop([(p, q)]))
