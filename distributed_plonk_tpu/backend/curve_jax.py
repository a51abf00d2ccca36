"""Vectorized BLS12-381 G1 arithmetic on device (Jacobian over limb-Fq).

Device replacement for `ark-ec`'s G1 group ops as used by the reference's
MSM workers (/root/reference/src/worker.rs:122). Points are (X, Y, Z)
tuples of (24, *batch) uint32 Montgomery limb arrays; Z == 0 encodes the
point at infinity (matching the oracle's (1, 1, 0) convention, curve.py).

All control flow is branch-free: the add kernel computes the generic sum,
the doubling, and infinity fallbacks unconditionally and `where`-selects —
the TPU-idiomatic shape for data-dependent curve edge cases.
"""

import os

import numpy as np
import jax
import jax.numpy as jnp

from ..constants import FQ_MONT_R, FQ_LIMBS, Q_MOD
from . import field_jax as FJ
from .field_jax import FQ
from .limbs import int_to_limbs, ints_to_limbs, limbs_to_ints

# DPT_CURVE_ADD selects the fused whole-formula Pallas add kernel
# (curve_pallas.py). Default is xla (OFF): measured round 4 on a v5e
# (scripts/add_bench.py, 8192 lanes), the fused kernel ties the staged
# XLA path exactly (131 ms / 32 steps both — the staged path's muls
# already ride the fused Pallas multiplier, and at MSM widths XLA's
# per-op overhead amortizes) while costing ~194 s of Mosaic compile per
# distinct shape. auto/pallas opt back in under the multiplier's gate.
_ADD_MODE = os.environ.get("DPT_CURVE_ADD", "xla")


def _use_fused_add(*shapes):
    if _ADD_MODE == "pallas":        # force, regardless of the mul gate
        return True
    if _ADD_MODE != "auto":          # default "xla": fused add off
        return False
    return FJ._use_pallas(jnp.broadcast_shapes(*shapes))

_MONT_ONE = int_to_limbs(FQ_MONT_R, FQ_LIMBS)  # 1 in Montgomery form
_MONT_R_INV = pow(FQ_MONT_R, Q_MOD - 2, Q_MOD)


def _mont_one_like(x):
    return jnp.broadcast_to(
        jnp.asarray(_MONT_ONE).reshape((FQ_LIMBS,) + (1,) * (x.ndim - 1)), x.shape)


def pt_inf(batch_shape=()):
    """Infinity: (1, 1, 0) in Montgomery form."""
    shape = (FQ_LIMBS,) + tuple(batch_shape)
    one = jnp.broadcast_to(
        jnp.asarray(_MONT_ONE).reshape((FQ_LIMBS,) + (1,) * len(batch_shape)), shape)
    return (one, one, jnp.zeros(shape, dtype=jnp.uint32))


def pt_select(cond, p, q):
    """cond (*batch,) ? p : q, componentwise."""
    return tuple(FJ.select(cond, a, b) for a, b in zip(p, q))


def pt_is_inf(p):
    return FJ.is_zero(FQ, p[2])


def pt_neg(p):
    return (p[0], FJ.neg(FQ, p[1]), p[2])


def from_affine(x, y, inf_mask):
    """(24, *b) coords in Montgomery form + bool inf mask -> Jacobian."""
    one = _mont_one_like(x)
    z = jnp.where(inf_mask[None], jnp.zeros_like(x), one)
    return (x, y, z)


def _dbl(spec, a):
    return FJ.add(spec, a, a)


def _mul_lanes(pairs):
    """Batch k independent Fq products into ONE mont_mul on a stacked lane
    axis: the traced program contains one multiplier instance instead of k
    (k-fold smaller XLA graphs — compile time was the round-1 multichip-gate
    killer), and the device sees one wide op instead of k narrow ones."""
    a = jnp.stack([x for x, _ in pairs], axis=1)
    b = jnp.stack([y for _, y in pairs], axis=1)
    r = FJ.mont_mul(FQ, a, b)
    return [r[:, i] for i in range(len(pairs))]


def _sub_lanes(pairs):
    a = jnp.stack([x for x, _ in pairs], axis=1)
    b = jnp.stack([y for _, y in pairs], axis=1)
    r = FJ.sub(FQ, a, b)
    return [r[:, i] for i in range(len(pairs))]


def jac_double(p):
    """dbl-2009-l (a=0), identical formula to the oracle
    (curve.py _g1_jac_double_nonzero); Z1=0 propagates to Z3=0.
    Independent products run as stacked lanes (4 multiplier instances)."""
    x1, y1, z1 = p
    a, b = _mul_lanes([(x1, x1), (y1, y1)])
    xb = FJ.add(FQ, x1, b)
    c, t = _mul_lanes([(b, b), (xb, xb)])
    d = _dbl(FQ, FJ.sub(FQ, FJ.sub(FQ, t, a), c))
    e = FJ.add(FQ, _dbl(FQ, a), a)
    f, yz = _mul_lanes([(e, e), (y1, z1)])
    x3 = FJ.sub(FQ, f, _dbl(FQ, d))
    c8 = _dbl(FQ, _dbl(FQ, _dbl(FQ, c)))
    (g,) = _mul_lanes([(e, FJ.sub(FQ, d, x3))])
    y3 = FJ.sub(FQ, g, c8)
    z3 = _dbl(FQ, yz)
    return (x3, y3, z3)


def jac_add(p, q):
    """add-2007-bl with branch-free edge handling (P==Q -> double,
    P==-Q -> infinity, either infinite -> other operand).
    Independent products run as stacked lanes (6 multiplier instances for
    the generic sum; plus 4 in the doubling fallback)."""
    x1, y1, z1 = p
    x2, y2, z2 = q
    zz = FJ.add(FQ, z1, z2)
    z1z1, z2z2, zz2 = _mul_lanes([(z1, z1), (z2, z2), (zz, zz)])
    u1, u2, s1a, s2a = _mul_lanes(
        [(x1, z2z2), (x2, z1z1), (y1, z2), (y2, z1)])
    s1, s2 = _mul_lanes([(s1a, z2z2), (s2a, z1z1)])
    h, r0 = _sub_lanes([(u2, u1), (s2, s1)])
    h2 = _dbl(FQ, h)
    rr = _dbl(FQ, r0)
    (i,) = _mul_lanes([(h2, h2)])
    j, v, rr2 = _mul_lanes([(h, i), (u1, i), (rr, rr)])
    xa, za = _sub_lanes([(rr2, j), (zz2, z1z1)])
    x3, zb = _sub_lanes([(xa, _dbl(FQ, v)), (za, z2z2)])
    p1, p2, z3 = _mul_lanes([(rr, FJ.sub(FQ, v, x3)), (s1, j), (zb, h)])
    y3 = FJ.sub(FQ, p1, _dbl(FQ, p2))
    res = (x3, y3, z3)

    p_inf = FJ.is_zero(FQ, z1)
    q_inf = FJ.is_zero(FQ, z2)
    both_fin = ~p_inf & ~q_inf
    h_zero = FJ.eq(FQ, u1, u2) & both_fin
    s_eq = FJ.eq(FQ, s1, s2)

    res = pt_select(h_zero & s_eq, jac_double(p), res)
    res = pt_select(h_zero & ~s_eq, pt_inf(z1.shape[1:]), res)
    res = pt_select(q_inf, p, res)
    res = pt_select(p_inf, q, res)
    return res


# --- complete projective kernels (Renes-Costello-Batina 2015, a=0) -----------
# The bucket pipeline's hot ops: COMPLETE homogeneous-projective addition for
# j-invariant-0 curves (y^2 = x^3 + 4, so b3 = 12). Complete means NO edge
# handling at all — identity (0 : 1 : 0), P == Q, and P == -Q all flow
# through the same straight-line formula (valid on the prime-order subgroup)
# — which on a vector machine beats Jacobian adds twice over: fewer
# multiplies AND none of the branch-free select/fallback machinery.
# Each add stages its multiplies into just TWO stacked-lane mont_mul
# instances (6 independent products each), so compiled programs are small.

def _mul12(a):
    """12*a = 8a + 4a via three doublings and one add (b3 multiply)."""
    a4 = _dbl(FQ, _dbl(FQ, a))
    return FJ.add(FQ, _dbl(FQ, a4), a4)


def proj_inf(batch_shape=()):
    """Identity in homogeneous projective coordinates: (0 : 1 : 0)."""
    shape = (FQ_LIMBS,) + tuple(batch_shape)
    one = jnp.broadcast_to(
        jnp.asarray(_MONT_ONE).reshape((FQ_LIMBS,) + (1,) * len(batch_shape)),
        shape)
    zero = jnp.zeros(shape, dtype=jnp.uint32)
    return (zero, one, zero)


def proj_add(p, q):
    """Complete projective P + Q (RCB15 algorithm 7, a=0): 12 full muls in
    2 stacked-lane instances + 2 cheap b3 multiplies. No special cases.

    Wide shapes on TPU run the whole formula as ONE fused Pallas program
    (curve_pallas.py) — same op sequence, intermediates in VMEM."""
    if _use_fused_add(*[c.shape for c in (*p, *q)]):
        from . import curve_pallas as CP
        return CP.proj_add(p, q)
    x1, y1, z1 = p
    x2, y2, z2 = q
    t0, t1, t2, m3, m4, m5 = _mul_lanes([
        (x1, x2), (y1, y2), (z1, z2),
        (FJ.add(FQ, x1, y1), FJ.add(FQ, x2, y2)),
        (FJ.add(FQ, y1, z1), FJ.add(FQ, y2, z2)),
        (FJ.add(FQ, x1, z1), FJ.add(FQ, x2, z2)),
    ])
    t3 = FJ.sub(FQ, m3, FJ.add(FQ, t0, t1))
    t4 = FJ.sub(FQ, m4, FJ.add(FQ, t1, t2))
    ym = FJ.sub(FQ, m5, FJ.add(FQ, t0, t2))
    t0x3 = FJ.add(FQ, _dbl(FQ, t0), t0)   # 3*t0
    t2b = _mul12(t2)                      # b3*t2
    z3a = FJ.add(FQ, t1, t2b)
    t1a = FJ.sub(FQ, t1, t2b)
    y3b = _mul12(ym)                      # b3*ym
    x3a, t2c, y3c, t1b, t0c, z3b = _mul_lanes([
        (t4, y3b), (t3, t1a), (y3b, t0x3),
        (t1a, z3a), (t0x3, t3), (z3a, t4),
    ])
    return (FJ.sub(FQ, t2c, x3a),
            FJ.add(FQ, t1b, y3c),
            FJ.add(FQ, z3b, t0c))


def proj_add_mixed(p, q_affine, q_inf):
    """Complete projective P + affine Q (RCB15 algorithm 8, a=0): 11 full
    muls in 2 stacked-lane instances. Complete in P; the only mask is for
    Q flagged infinite (padding / zero digit), which returns P.

    Wide shapes on TPU run the whole formula as ONE fused Pallas program
    (curve_pallas.py; the q_inf select stays here in XLA, where it fuses)."""
    if _use_fused_add(*[c.shape for c in (*p, *q_affine)]):
        from . import curve_pallas as CP
        res = CP.proj_add_mixed(p, q_affine)
        return pt_select(q_inf, p, res)
    x1, y1, z1 = p
    x2, y2 = q_affine
    t0, t1, m3, t4a, y3a = _mul_lanes([
        (x1, x2), (y1, y2),
        (FJ.add(FQ, x1, y1), FJ.add(FQ, x2, y2)),
        (y2, z1), (x2, z1),
    ])
    t3 = FJ.sub(FQ, m3, FJ.add(FQ, t0, t1))
    t4 = FJ.add(FQ, t4a, y1)
    ym = FJ.add(FQ, y3a, x1)
    t0x3 = FJ.add(FQ, _dbl(FQ, t0), t0)   # 3*t0
    t2 = _mul12(z1)                       # b3*Z1
    z3a = FJ.add(FQ, t1, t2)
    t1a = FJ.sub(FQ, t1, t2)
    y3b = _mul12(ym)                      # b3*ym
    x3a, t2c, y3c, t1b, t0c, z3b = _mul_lanes([
        (t4, y3b), (t3, t1a), (y3b, t0x3),
        (t1a, z3a), (t0x3, t3), (z3a, t4),
    ])
    res = (FJ.sub(FQ, t2c, x3a),
           FJ.add(FQ, t1b, y3c),
           FJ.add(FQ, z3b, t0c))
    return pt_select(q_inf, p, res)


def _mm(a, b):
    return FJ.mont_mul(FQ, a, b)


@jax.jit
def _prefix_suffix(z):
    # single-width Hillis-Steele ladders, NOT associative_scan: the
    # multi-width lowering wedged the remote TPU compile at SRS scale
    # (round 4) — rationale at field_jax.cumprod_mont
    return FJ.cumprod_mont(FQ, z), FJ.cumprod_mont(FQ, z, reverse=True)


@jax.jit
def _normalize(px, py, pz, pre, suf, tinv, inf):
    one_col = jnp.asarray(_MONT_ONE).reshape(FQ_LIMBS, 1)
    pre_im1 = jnp.concatenate([one_col, pre[:, :-1]], axis=1)
    suf_ip1 = jnp.concatenate([suf[:, 1:], one_col], axis=1)
    # z_i^-1 (Montgomery) = pre_{i-1} * suf_{i+1} * (T^-1 R)
    zinv = _mm(_mm(pre_im1, suf_ip1), jnp.broadcast_to(tinv, pz.shape))
    zinv2 = _mm(zinv, zinv)
    zinv3 = _mm(zinv2, zinv)
    ax = _mm(px, zinv2)
    ay = _mm(py, zinv3)
    zero = jnp.zeros_like(ax)
    return (FJ.select(inf, zero, ax), FJ.select(inf, zero, ay))


def batch_to_affine(p):
    """Jacobian (24, n) Montgomery -> (x_affine, y_affine, inf_mask), all on
    device: Montgomery batch inversion of the Z column via two log-depth
    prefix/suffix product scans and ONE field inverse, which crosses to the
    host as a single element (pow(z, q-2) there costs nothing). Used to
    normalize a device-built SRS (fixed_base output has arbitrary Z) into
    the affine form the mixed-add bucket scan consumes, and each window of
    msm_jax's pre-weighted table: the two programs are the module's, so a
    second call at a width traces and compiles nothing."""
    px, py, pz = p
    inf = FJ.is_zero(FQ, pz)
    z = FJ.select(inf, _mont_one_like(pz), pz)
    pre, suf = _prefix_suffix(z)
    total = np.asarray(pre[:, -1])  # ONE element to host
    total_int = 0
    for k, limb in enumerate(total):
        total_int |= int(limb) << (16 * k)
    # total is Montgomery form of T: T*R. modinv(T*R) = T^-1 * R^-1, so
    # R^2 * that = T^-1 * R, the inverse in Montgomery form
    inv_int = (FQ_MONT_R * FQ_MONT_R % Q_MOD) * pow(total_int, Q_MOD - 2, Q_MOD) % Q_MOD
    tinv = jnp.asarray(int_to_limbs(inv_int, FQ_LIMBS)).reshape(FQ_LIMBS, 1)
    ax, ay = _normalize(px, py, pz, pre, suf, tinv, inf)
    return ax, ay, inf


def batch_to_host_affine(p, width):
    """Jacobian (24, n) Montgomery on the device -> list[(x, y) | None] of
    n host points, normalized by `batch_to_affine` (one field inverse
    crosses to the host, not one a point). The points are padded with the
    identity to `width` >= n first, so that a caller runs the two programs
    at a width they are compiled for already."""
    n = p[0].shape[1]
    assert width >= n
    if width > n:
        p = tuple(jnp.pad(c, ((0, 0), (0, width - n))) for c in p)
    ax, ay, inf = batch_to_affine(p)
    xs = limbs_to_ints(np.asarray(ax)[:, :n])
    ys = limbs_to_ints(np.asarray(ay)[:, :n])
    return [None if i else (x * _MONT_R_INV % Q_MOD, y * _MONT_R_INV % Q_MOD)
            for x, y, i in zip(xs, ys, np.asarray(inf)[:n].tolist())]


# --- host boundary helpers (tests / debugging; oracle-grade, not hot) --------

def affine_to_device(points):
    """list[(x, y) | None] -> Jacobian tuple of (24, n) Montgomery arrays."""
    xs = [(p[0] * FQ_MONT_R % Q_MOD) if p else 0 for p in points]
    ys = [(p[1] * FQ_MONT_R % Q_MOD) if p else 0 for p in points]
    inf = np.array([p is None for p in points])
    return from_affine(jnp.asarray(ints_to_limbs(xs, FQ_LIMBS)),
                       jnp.asarray(ints_to_limbs(ys, FQ_LIMBS)),
                       jnp.asarray(inf))


def device_to_affine(p):
    """Jacobian tuple of (24, n) Montgomery arrays -> list[(x, y) | None]."""
    from .. import curve as C

    cols = [limbs_to_ints(np.asarray(c)) for c in p]
    out = []
    for X, Y, Z in zip(*cols):
        jac = tuple(v * _MONT_R_INV % Q_MOD for v in (X, Y, Z))
        out.append(C.g1_from_jac(jac))
    return out
