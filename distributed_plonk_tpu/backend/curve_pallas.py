"""Pallas fused complete projective add: the whole RCB15 formula in VMEM.

WHY (measured on a v5e, BASELINE.md round 4): after the fused Montgomery
multiplier landed, the MSM bucket scan still ran at ~510k lane-adds/s
against a ~12M lane-muls/s multiplier — the projective add is a ~12-deep
dependent chain of muls/adds/subs, and issuing it as ~24 separate XLA
ops per scan step pays the per-op dispatch + VPU/MXU layout-transition
cost ~24 times and round-trips every intermediate through HBM (~300 B
per lane per op). This kernel runs the ENTIRE complete-add formula
(RCB15 algorithms 7/8 for a=0, b3=12 — the same straight-line sequence
as curve_jax.proj_add / proj_add_mixed) in one Pallas program: the 11/12
full Montgomery products execute as TWO wide banded group-products (the
independent muls concatenate along lanes, exactly like curve_jax's
stacked-lane staging, but inside VMEM), and all modular adds/subs reuse
the same in-register Kogge-Stone sweeps. HBM traffic per lane-add drops
from ~24 round-trips to: read 5 (mixed) or 6 (full) coordinates, write 3.

Bit-identity: every intermediate is fully reduced mod p by the same
paired-sweep rule as field_jax.add/sub/mont_mul, so outputs are
limb-identical to the XLA path (oracle-tested in
tests/test_curve_pallas.py; the MSM consuming it stays byte-identical).

Dispatch: curve_jax.proj_add{,_mixed} route wide TPU shapes here under
the same gate as the fused multiplier (DPT_FIELD_MUL=auto + lane
threshold; DPT_CURVE_ADD=xla opts just the add kernel out). The q_inf /
sign selects of the callers stay in XLA where they fuse for free.

Reference parity: this is the device replacement for the per-bucket
point additions inside ark-ec's VariableBaseMSM as driven by the MSM
workers (/root/reference/src/worker.rs:122,159-185).
"""

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .field_pallas import (LIMB_BITS, LIMB_MASK, _carry_sweep_val,
                           _to_bytes_f32, _cols_to_limbs, _const_bytes,
                           int_from_limbs)

# lanes of each coordinate per grid step. The group products run 5-6x
# this wide; 256 keeps the f32 column scratch at 96*6*256*4 = 590 KB for
# Fq and the whole working set low single-digit MB of VMEM.
LANE_TILE = 256


def _col_const(limbs):
    """Python limb ints -> (L, 1) i32 column built from inlined scalars
    (pallas kernels cannot capture array constants)."""
    return jnp.concatenate(
        [jnp.full((1, 1), int(v), jnp.int32) for v in limbs], axis=0)


# --- in-kernel modular primitives (i32 limbs in [0, 2^16), width-generic) ----

def _mod_add(a, b, n_limbs, negp):
    """a + b mod p, mirroring field_jax.add: sweep the raw sum and the
    sum + (2^(16L) - p); the second's carry-out flags sum >= p."""
    s = a + b
    r1, _ = _carry_sweep_val(s, n_limbs)
    r2, c2 = _carry_sweep_val(s + negp, n_limbs)
    return jnp.where((c2 != 0)[None], r2, r1)


def _row0_mask(shape):
    """(rows, w) i32 that is 1 on row 0, else 0 — the concat-free way to
    adjust the head row (a row-concatenate gives the result an offset
    vector layout that Mosaic then cannot lane-concatenate)."""
    import jax.lax as lax
    return (lax.broadcasted_iota(jnp.int32, shape, 0) == 0).astype(jnp.int32)


def _mod_sub(a, b, n_limbs, p_col):
    """a - b mod p, mirroring field_jax.sub: a + ~b + 1 carries iff
    a >= b; otherwise take the + p wrap-around lane."""
    base = a + (b ^ LIMB_MASK)
    base = base + _row0_mask(base.shape)
    r1, c1 = _carry_sweep_val(base, n_limbs)
    r2, _ = _carry_sweep_val(base + p_col, n_limbs)
    return jnp.where((c1 != 0)[None], r1, r2)


def _band_mul_w(t_ref, a_bytes, b_bytes, w):
    """field_pallas._band_mul on the leading `w` lanes of the scratch.

    The zeroing covers the FULL scratch, not just [:, :w]: a partial
    zero is a weak update to the static verifier's per-ref interval cell
    (analysis/bounds.py), so stale bounds from a wider prior product
    would compound across the ~12 products of a fused add and trip the
    f32-exactness check; the extra lanes cost ~1% of the band FMAs."""
    nb = a_bytes.shape[0]
    t_ref[...] = jnp.zeros(t_ref.shape, jnp.float32)
    for i in range(nb):
        t_ref[i:i + nb, :w] += a_bytes[i][None, :] * b_bytes
    return t_ref[:, :w]


def _band_mul_const_w(t_ref, c_bytes, b_bytes, w):
    nb = b_bytes.shape[0]
    t_ref[...] = jnp.zeros(t_ref.shape, jnp.float32)
    for i, c in enumerate(c_bytes):
        if c == 0:
            continue
        t_ref[i:i + nb, :w] += np.float32(c) * b_bytes
    return t_ref[:, :w]


def _mont_mul_val(t_ref, a, b, k):
    """Full Montgomery SOS product on in-register (L, w) i32 values —
    the body of field_pallas._mont_mul_kernel, reusing one (4L, Wmax)
    f32 scratch. k carries the per-field constants."""
    L = k["n_limbs"]
    w = a.shape[1]
    a_by = _to_bytes_f32(a)
    b_by = _to_bytes_f32(b)
    t_cols = _band_mul_w(t_ref, a_by, b_by, w)
    t_limbs = _cols_to_limbs(t_cols)
    t_lo, c_t = _carry_sweep_val(t_limbs[:L], L)
    tlo_by = _to_bytes_f32(t_lo)
    m_cols = _band_mul_const_w(t_ref, k["ninv_bytes"], tlo_by, w)[:2 * L]
    m, _ = _carry_sweep_val(_cols_to_limbs(m_cols), L)
    m_by = _to_bytes_f32(m)
    mp_cols = _band_mul_const_w(t_ref, k["mod_bytes"], m_by, w)
    mp_limbs = _cols_to_limbs(mp_cols)
    _, c_low = _carry_sweep_val(t_lo + mp_limbs[:L], L)
    hi = t_limbs[L:] + mp_limbs[L:]
    hi = hi + _row0_mask(hi.shape) * (c_t + c_low)[None]
    r1, _ = _carry_sweep_val(hi, L)
    r2, c2 = _carry_sweep_val(hi + k["negp"], L)
    return jnp.where((c2 != 0)[None], r2, r1)


def _mm_group(t_ref, pairs, k):
    """Stacked-lane group product: the independent muls concatenate along
    lanes into ONE banded product (the in-VMEM analog of
    curve_jax._mul_lanes — same batching idea, zero HBM round-trips)."""
    T = pairs[0][0].shape[1]
    a = jnp.concatenate([p[0] for p in pairs], axis=1)
    b = jnp.concatenate([p[1] for p in pairs], axis=1)
    r = _mont_mul_val(t_ref, a, b, k)
    return [r[:, i * T:(i + 1) * T] for i in range(len(pairs))]


def _mul12(a, k):
    """12*a = 8a + 4a (the b3 = 3*4 multiply for y^2 = x^3 + 4), via the
    same dbl/add chain as curve_jax._mul12 (fully reduced at each step)."""
    L, negp = k["n_limbs"], k["negp"]
    a2 = _mod_add(a, a, L, negp)
    a4 = _mod_add(a2, a2, L, negp)
    a8 = _mod_add(a4, a4, L, negp)
    return _mod_add(a8, a4, L, negp)


# --- the fused kernels -------------------------------------------------------

def consts_env(kc):
    """Hashable const tuple (from _fq_consts / fq_consts) -> the dict the
    value-level helpers consume, with the modulus columns materialized.
    Exported for kernels that embed these primitives (msm_pallas)."""
    k = dict(kc)
    k["negp"] = _col_const(k.pop("negmod_limbs"))
    k["p_col"] = _col_const(k.pop("mod_limbs"))
    return k


def _rcb15_tail(t_ref, k, t0, t1, t3, t4, ym, t2):
    """Shared tail of RCB15 algorithms 7/8 once (t0, t1, t3, t4, ym) and
    the b3-scaled t2 are in hand; returns (x3, y3, z3) i32 values."""
    L, negp, p_col = k["n_limbs"], k["negp"], k["p_col"]
    t0x3 = _mod_add(_mod_add(t0, t0, L, negp), t0, L, negp)
    z3a = _mod_add(t1, t2, L, negp)
    t1a = _mod_sub(t1, t2, L, p_col)
    y3b = _mul12(ym, k)
    x3a, t2c, y3c, t1b, t0c, z3b = _mm_group(
        t_ref,
        [(t4, y3b), (t3, t1a), (y3b, t0x3),
         (t1a, z3a), (t0x3, t3), (z3a, t4)], k)
    return (_mod_sub(t2c, x3a, L, p_col),
            _mod_add(t1b, y3c, L, negp),
            _mod_add(z3b, t0c, L, negp))


def add_mixed_val(t_ref, k, p, q):
    """Complete projective P + affine Q (RCB15 algorithm 8, a=0) on
    in-VMEM (L, w) i32 VALUES — the exact op sequence of
    curve_jax.proj_add_mixed, width-generic (w is whatever the caller's
    lane count is; t_ref must be at least 6*w lanes wide). The q_inf /
    skip select stays with the caller. Returns (x3, y3, z3) values."""
    L, negp, p_col = k["n_limbs"], k["negp"], k["p_col"]
    x1, y1, z1 = p
    x2, y2 = q
    a1 = _mod_add(x1, y1, L, negp)
    a2 = _mod_add(x2, y2, L, negp)
    t0, t1, m3, t4a, y3a = _mm_group(
        t_ref, [(x1, x2), (y1, y2), (a1, a2), (y2, z1), (x2, z1)], k)
    t3 = _mod_sub(m3, _mod_add(t0, t1, L, negp), L, p_col)
    t4 = _mod_add(t4a, y1, L, negp)
    ym = _mod_add(y3a, x1, L, negp)
    t2 = _mul12(z1, k)
    return _rcb15_tail(t_ref, k, t0, t1, t3, t4, ym, t2)


def add_full_val(t_ref, k, p, q):
    """Complete projective P + Q (RCB15 algorithm 7, a=0) on in-VMEM
    (L, w) i32 values — the exact op sequence of curve_jax.proj_add."""
    L, negp, p_col = k["n_limbs"], k["negp"], k["p_col"]
    x1, y1, z1 = p
    x2, y2, z2 = q
    t0, t1, t2r, m3, m4, m5 = _mm_group(
        t_ref,
        [(x1, x2), (y1, y2), (z1, z2),
         (_mod_add(x1, y1, L, negp), _mod_add(x2, y2, L, negp)),
         (_mod_add(y1, z1, L, negp), _mod_add(y2, z2, L, negp)),
         (_mod_add(x1, z1, L, negp), _mod_add(x2, z2, L, negp))], k)
    t3 = _mod_sub(m3, _mod_add(t0, t1, L, negp), L, p_col)
    t4 = _mod_sub(m4, _mod_add(t1, t2r, L, negp), L, p_col)
    ym = _mod_sub(m5, _mod_add(t0, t2r, L, negp), L, p_col)
    t2 = _mul12(t2r, k)
    return _rcb15_tail(t_ref, k, t0, t1, t3, t4, ym, t2)


def _add_mixed_kernel(x1_ref, y1_ref, z1_ref, x2_ref, y2_ref,
                      ox_ref, oy_ref, oz_ref, t_ref, *, kc):
    """Complete projective P + affine Q (RCB15 algorithm 8, a=0): the
    exact op sequence of curve_jax.proj_add_mixed, in one program."""
    k = consts_env(kc)
    p = tuple(r[...].astype(jnp.int32) for r in (x1_ref, y1_ref, z1_ref))
    q = tuple(r[...].astype(jnp.int32) for r in (x2_ref, y2_ref))
    x3, y3, z3 = add_mixed_val(t_ref, k, p, q)
    ox_ref[...] = x3.astype(jnp.uint32)
    oy_ref[...] = y3.astype(jnp.uint32)
    oz_ref[...] = z3.astype(jnp.uint32)


def _add_full_kernel(x1_ref, y1_ref, z1_ref, x2_ref, y2_ref, z2_ref,
                     ox_ref, oy_ref, oz_ref, t_ref, *, kc):
    """Complete projective P + Q (RCB15 algorithm 7, a=0): the exact op
    sequence of curve_jax.proj_add, in one program."""
    k = consts_env(kc)
    p = tuple(r[...].astype(jnp.int32) for r in (x1_ref, y1_ref, z1_ref))
    q = tuple(r[...].astype(jnp.int32) for r in (x2_ref, y2_ref, z2_ref))
    x3, y3, z3 = add_full_val(t_ref, k, p, q)
    ox_ref[...] = x3.astype(jnp.uint32)
    oy_ref[...] = y3.astype(jnp.uint32)
    oz_ref[...] = z3.astype(jnp.uint32)


def field_consts(spec):
    """Hashable per-field constant tuple for kernels embedding these
    primitives (jit-static; feed through consts_env inside the kernel
    body). Width-generic over the field spec; the curve/MSM kernels pass
    Fq."""
    L = spec.n_limbs
    return (("n_limbs", L),
            ("ninv_bytes",
             tuple(_const_bytes(int_from_limbs(spec.ninv_limbs), 2 * L))),
            ("mod_bytes",
             tuple(_const_bytes(int_from_limbs(spec.mod_limbs), 2 * L))),
            ("negmod_limbs", tuple(int(v) for v in spec.negmod_limbs)),
            ("mod_limbs", tuple(int(v) for v in spec.mod_limbs)))


def fq_consts():
    """field_consts(Fq) — the constant set of the curve/MSM kernels."""
    from .field_jax import FQ

    return field_consts(FQ)


_fq_consts = fq_consts  # internal spelling kept for the add kernels below


@functools.partial(jax.jit, static_argnums=(0, 1))
def _add_flat(mixed, interpret, *coords):
    """(L, N) coordinate arrays (5 mixed / 6 full), N a LANE_TILE
    multiple -> three (L, N) outputs."""
    from jax.experimental.pallas import tpu as pltpu
    from .field_jax import FQ

    L = FQ.n_limbs
    kern = _add_mixed_kernel if mixed else _add_full_kernel
    kernel = functools.partial(kern, kc=_fq_consts())
    n = coords[0].shape[1]
    grid = n // LANE_TILE
    spec = pl.BlockSpec((L, LANE_TILE), lambda i: (0, i))
    return pl.pallas_call(
        kernel,
        out_shape=[jax.ShapeDtypeStruct((L, n), jnp.uint32)] * 3,
        grid=(grid,),
        in_specs=[spec] * len(coords),
        out_specs=[spec] * 3,
        scratch_shapes=[pltpu.VMEM((4 * L, 6 * LANE_TILE), jnp.float32)],
        interpret=interpret,
    )(*coords)


def _dispatch(mixed, parts):
    from .field_jax import FQ, pallas_interpret

    L = FQ.n_limbs
    shape = jnp.broadcast_shapes(*[p.shape for p in parts])
    lanes = 1
    for d in shape[1:]:
        lanes *= d
    pad = (-lanes) % LANE_TILE
    flat = []
    for p in parts:
        f = jnp.broadcast_to(p, shape).reshape(L, lanes)
        flat.append(jnp.pad(f, ((0, 0), (0, pad))) if pad else f)
    out = _add_flat(mixed, pallas_interpret(), *flat)
    if pad:
        out = [o[:, :lanes] for o in out]
    return tuple(o.reshape(shape) for o in out)


def proj_add_mixed(p, q_affine):
    """Fused-kernel counterpart of curve_jax.proj_add_mixed WITHOUT the
    q_inf select (the caller applies it in XLA, where it fuses)."""
    return _dispatch(True, [*p, *q_affine])


def proj_add(p, q):
    """Fused-kernel counterpart of curve_jax.proj_add."""
    return _dispatch(False, [*p, *q])
