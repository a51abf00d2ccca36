"""Pallas fused Montgomery multiplier: the whole SOS product in VMEM.

WHY (measured on a v5e through scripts/msm_ab.py + BASELINE.md round 4):
the XLA-level f32 mont_mul materializes its byte-product column tensor to
HBM (~18 KB per lane per multiply — a 2^18-lane call allocates 24 GB and
OOMs the chip), which makes every projective add ~12 x 18 KB of HBM
traffic. The measured MSM ceiling (~370-620k lane-adds/s regardless of
width) is exactly that traffic bound. This kernel keeps ALL intermediates
(byte rows, product columns, carry sweeps) in VMEM scratch: HBM traffic
per multiply drops to the operands + result (~300 B/lane), a ~60x cut.

HOW: one grid step processes a (n_limbs, LANE_TILE) block of each
operand. The schoolbook byte product is NOT an unrolled i x j loop
(2L x 2L = 2304 FMAs traced) but a BANDED accumulation — for each of the
2L bytes of `a`, one (2L, T)-shaped FMA adds a_i * b_bytes into the
column window [i, i + 2L) of a (4L, T) f32 scratch:

    for i in 0..2L-1:  t[i : i+2L, :] += a_byte[i] * b_bytes

f32 accumulation is exact: products <= 255^2, column sums <= 2L terms
=> < 2^22 < 2^24. The three SOS phases (t = a*b; m = t_lo * (-p^-1) mod R;
m*p) all use the same band loop — the constant products use Python-float
byte constants, costing a scalar*tensor FMA per band row. Carries run as
the same log-depth Kogge-Stone sweep as field_jax._carry_sweep, on VMEM
values. The algorithm is bit-identical to field_jax.mont_mul (same SOS
reduction; oracle-tested in tests/test_field_pallas.py, and statically
proven like the XLA paths: the field/*_mont_mul_pallas_* registry
entries interval-check the kernel jaxpr at the real lane tile AND
exactly evaluate the grid walk against the a*b*R^-1 mod p value
contract — both variants, both fields).

Select with DPT_FIELD_MUL=pallas (TPU; other platforms fall back to the
f32 XLA path automatically, and tests exercise the kernel via
interpret mode).
"""

import functools
import os

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LIMB_BITS = 16
LIMB_MASK = (1 << LIMB_BITS) - 1

# lanes per grid step: f32 tiling wants multiples of (8, 128); 512 lanes
# keeps the (4L, T) f32 scratch at 96*512*4 = 196 KB for Fq — far under
# VMEM — while giving the VPU full rows. DPT_PALLAS_LANE_TILE widens the
# tile (fewer sequential grid steps at NTT widths — a 2^22-lane stage mul
# is 8192 steps at 512 — trading VMEM for per-step overhead).
LANE_TILE = int(os.environ.get("DPT_PALLAS_LANE_TILE", "512"))


def _const_bytes(value, n_bytes):
    """Python int -> list of n_bytes byte values (little-endian)."""
    return [(value >> (8 * k)) & 0xFF for k in range(n_bytes)]


def _carry_sweep_val(cols, n_limbs):
    """Kogge-Stone carry propagation on an in-register (K, T) i32 value
    (entries any u32; see field_jax._carry_sweep for the bound argument).
    Returns (limbs (K, T) in [0, 2^16), carry_out (T,) i32)."""
    lo = cols & LIMB_MASK
    hi = jnp.right_shift(cols, LIMB_BITS)
    zero_row = jnp.zeros_like(hi[:1])
    s = lo + jnp.concatenate([zero_row, hi[:-1]], axis=0)

    def shift_down(x, k):
        return jnp.concatenate([jnp.zeros_like(x[:k]), x[:-k]], axis=0)

    # carry masks as 0/1 i32, not bool: Mosaic cannot concatenate i1
    # vector registers (shift_down is a concat)
    gen = (s > LIMB_MASK).astype(jnp.int32)
    prop = (s == LIMB_MASK).astype(jnp.int32)
    k = 1
    while k < n_limbs:
        gen = gen | (prop & shift_down(gen, k))
        prop = prop & shift_down(prop, k)
        k *= 2
    b_in = shift_down(gen, 1)
    limbs = (s + b_in) & LIMB_MASK
    # top-row extraction WITHOUT a row slice: x[-1] lowers via
    # dynamic_slice (unimplemented in the Mosaic TC pipeline), and a
    # static x[top] of row 23 gives the result an offset-7 vector layout
    # that poisons any later lane-concatenate (the fused add's group
    # stacking). A masked row reduction yields a clean-layout vector.
    top_mask = (jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
                == s.shape[0] - 1).astype(jnp.int32)
    carry = jnp.sum((hi + gen) * top_mask, axis=0)
    return limbs, carry


def _to_bytes_f32(limbs):
    """(L, *t) i32 16-bit limbs -> (2L, *t) f32 byte rows (little-endian:
    row 2k = limb k low byte, row 2k+1 = high byte). Trailing-dims
    generic: the fused NTT kernel runs it on (L, rows, T) blocks, the 2D
    callers are unchanged."""
    L = limbs.shape[0]
    ev = (limbs & 0xFF).astype(jnp.float32)
    od = jnp.right_shift(limbs, 8).astype(jnp.float32)
    # interleave via stack + reshape on the major axis
    return jnp.stack([ev, od], axis=1).reshape((2 * L,) + limbs.shape[1:])


def _band_mul(t_ref, a_bytes, b_bytes):
    """Banded accumulation: out[k] = sum_{i+j=k} a_i * b_j, computed as
    2L shifted full-width (2L, T) FMAs accumulated IN PLACE into the
    (4L, T) f32 VMEM scratch t_ref (a concat- or .at[]-based functional
    accumulation copies the whole column buffer every iteration — 144
    buffer copies per product — and .at[].add's scatter lowering is
    rejected by pallas anyway). Returns the scratch value."""
    nb, T = a_bytes.shape
    t_ref[...] = jnp.zeros((2 * nb, T), jnp.float32)
    for i in range(nb):
        t_ref[i:i + nb] += a_bytes[i][None, :] * b_bytes
    return t_ref[...]


def _band_mul_const(t_ref, c_bytes, b_bytes):
    """Same in-place band accumulation with a compile-time constant
    multiplicand: out[k] = sum_{i+j=k} c_i * b_j, c_i Python scalars."""
    nb, T = b_bytes.shape
    t_ref[...] = jnp.zeros((2 * nb, T), jnp.float32)
    for i, c in enumerate(c_bytes):
        if c == 0:
            continue
        t_ref[i:i + nb] += np.float32(c) * b_bytes
    return t_ref[...]


def _cols_to_limbs(cols_f32):
    """(2K, *t) f32 byte columns -> (K, *t) i32 combined limb columns
    (ev + od*256, any u32 — fed to the carry sweep). Trailing-dims
    generic like _to_bytes_f32."""
    twoK = cols_f32.shape[0]
    v = cols_f32.reshape((twoK // 2, 2) + cols_f32.shape[1:])
    ev = v[:, 0].astype(jnp.int32)
    od = v[:, 1].astype(jnp.int32)
    return ev + jnp.left_shift(od, 8)


def _local_round(cols):
    """One base-256 local carry round on f32 digit columns (rows, T):
    each column keeps its low byte and pushes floor(col/256) one row up
    (the top row's carry-out is the CALLER's bound obligation). All
    arithmetic exact in f32 for columns < 2^24. Two rounds bring columns
    < 2^24 down to digits < 513; a third round to < 258."""
    hi = jnp.floor(cols * np.float32(1.0 / 256.0))
    dig = cols - hi * np.float32(256.0)
    shifted = jnp.concatenate([jnp.zeros_like(hi[:1]), hi[:-1]], axis=0)
    return dig + shifted


def _pairs_to_u32(cols_f32):
    """(2K, T) f32 digit columns -> (K, T) i32 rows ev + 256*od (entries
    < 2^31 for digit columns < 2^22 — fed to the exact carry sweep)."""
    twoK, T = cols_f32.shape
    v = cols_f32.reshape(twoK // 2, 2, T)
    return v[:, 0].astype(jnp.int32) + jnp.left_shift(
        v[:, 1].astype(jnp.int32), 8)


def _mont_mul_kernel_lazy(a_ref, b_ref, o_ref, t_ref, *, n_limbs,
                          mod_limbs, ninv_bytes, mod_bytes, negmod_limbs):
    """Lazy-carry Montgomery SOS: semi-normalized DIGIT columns flow
    between the three bands; exact Kogge-Stone sweeps only where a VALUE
    must be exact (the low-half carry-out and the final reduce) — 3
    sweeps instead of 5, and no byte re-conversions after the first.

    Soundness sketch (all f32 column values exact, < 2^24):
      - t = a*b band columns < 2L*255^2 < 2^22; two local rounds give
        digits < 513 with NO top-row loss (t < p^2 keeps the top column
        < 2^5). value(t) splits exactly at the R boundary.
      - m-band = ninv_bytes (<=255) x t_digits (<513): column sums
        < 2L*255*513 < 2^23 — exact; truncated at 2L columns the value
        is t*ninv mod R up to multiples of R, which divisibility by R
        tolerates. THREE local rounds bound m's digits < 258, so
        value(m') < 1.012*R and the final quotient stays < 1.52p — one
        conditional subtract reaches the canonical [0, p) result,
        BIT-IDENTICAL to the strict kernel.
      - mp-band = mod_bytes x m_digits (<258): sums < 2^22 — exact.
      - exact sweeps: low-half carry-out of t+m*p (pair-combined rows
        < 2^31), final reduce r1/r2 pair.
    """
    def m_band(t_dig2L):
        m_cols = _band_mul_const(t_ref, ninv_bytes, t_dig2L)[:2 * n_limbs]
        return _local_round(_local_round(_local_round(m_cols)))  # < 258

    def mp_band(m_dig):
        return _band_mul_const(t_ref, mod_bytes, m_dig)  # (4L, T), < 2^22

    _lazy_sos(a_ref, b_ref, o_ref, t_ref, n_limbs=n_limbs,
              negmod_limbs=negmod_limbs, t_rounds=2,
              m_band=m_band, mp_band=mp_band)


def _lazy_sos(a_ref, b_ref, o_ref, t_ref, *, n_limbs, negmod_limbs,
              t_rounds, m_band, mp_band):
    """Shared lazy-carry SOS skeleton: VPU a*b band -> t digit rounds ->
    m_band -> mp_band -> the exact finalize (low-half carry-out sweep +
    conditional subtract). The two kernel variants differ ONLY in how
    the constant bands run (VPU byte bands vs MXU Toeplitz matmuls) and
    in how many local rounds t needs before its band (the MXU band wants
    digits <= 256 for bf16 exactness; the VPU band tolerates < 513)."""
    L = n_limbs
    a = a_ref[...].astype(jnp.int32)
    b = b_ref[...].astype(jnp.int32)
    a_by = _to_bytes_f32(a)
    b_by = _to_bytes_f32(b)

    t_cols = _band_mul(t_ref, a_by, b_by)          # (4L, T) f32, < 2^22
    t_dig = t_cols                                 # exact split at R boundary
    for _ in range(t_rounds):
        t_dig = _local_round(t_dig)

    m_dig = m_band(t_dig[:2 * L])
    mp_cols = mp_band(m_dig)

    lo = _pairs_to_u32(t_dig[:2 * L] + mp_cols[:2 * L])
    _, c_low = _carry_sweep_val(lo, L)             # low half == 0 mod R

    hi = _pairs_to_u32(t_dig[2 * L:] + mp_cols[2 * L:])
    hi = hi + _row0_mask_i32(hi.shape) * c_low[None]
    negp = jnp.concatenate(
        [jnp.full((1, 1), int(v), jnp.int32) for v in negmod_limbs], axis=0)
    r1, _ = _carry_sweep_val(hi, L)
    r2, c2 = _carry_sweep_val(hi + negp, L)
    o_ref[...] = jnp.where((c2 != 0)[None], r2, r1).astype(jnp.uint32)


def _mont_mul_kernel_mxu(a_ref, b_ref, cn_ref, cp_ref, o_ref, t_ref, *,
                         n_limbs, mod_limbs, ninv_bytes, mod_bytes,
                         negmod_limbs):
    """Lazy-carry SOS with the two CONSTANT bands on the MXU.

    The m-band (ninv x t) and mp-band (p x m) are Toeplitz products by
    compile-time constants; as (out, 2L) @ (2L, T) bf16 matmuls with f32
    accumulation they run on the systolic array instead of burning 2/3 of
    the kernel's VPU FMAs (the measured round-5 multiplier ceiling —
    BASELINE.md round-6 roadmap #1a). Only the variable a x b band stays
    on the VPU (per-lane varying operands cannot share MXU weights).

    Exactness: bf16 has 8 significant bits, so integers <= 256 are exact.
    THREE local rounds after each accumulation bound digits <= 256:
      t band cols <= 2L*255^2 < 3.13e6 -> r1 <= 255+12192, r2 <= 303,
      r3 <= 256. The matmul products are <= 255*256 and every f32
      accumulator sum <= 2L*255*256 < 2^23 < 2^24 — exact. value(m') <=
      256*(R-1)/255 < 1.004*R, tighter than the VPU lazy kernel's 1.012*R
      bound, so the same single conditional subtract yields the canonical
      [0, p) result, BIT-IDENTICAL to the strict kernel.
    """
    dot = functools.partial(
        jax.lax.dot_general,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    def m_band(t_dig2L):
        m_cols = dot(cn_ref[...], t_dig2L.astype(jnp.bfloat16))
        return _local_round(_local_round(_local_round(m_cols)))  # <= 256

    def mp_band(m_dig):
        return dot(cp_ref[...], m_dig.astype(jnp.bfloat16))  # (4L, T) < 2^23

    _lazy_sos(a_ref, b_ref, o_ref, t_ref, n_limbs=n_limbs,
              negmod_limbs=negmod_limbs, t_rounds=3,
              m_band=m_band, mp_band=mp_band)


def _row0_mask_i32(shape):
    """1 on row 0 else 0 (concat-free head-row adjustment — a row concat
    would give the result an offset vector layout; see curve_pallas)."""
    return (jax.lax.broadcasted_iota(jnp.int32, shape, 0) == 0).astype(
        jnp.int32)


def _mont_mul_kernel(a_ref, b_ref, o_ref, t_ref, *, n_limbs, mod_limbs,
                     ninv_bytes, mod_bytes, negmod_limbs):
    """One (n_limbs, LANE_TILE) block: full Montgomery SOS product.

    Mirrors field_jax.mont_mul phase for phase; all intermediates live in
    registers/VMEM (t_ref: one reused (4L, T) f32 column scratch)."""
    L = n_limbs
    a = a_ref[...].astype(jnp.int32)
    b = b_ref[...].astype(jnp.int32)

    a_by = _to_bytes_f32(a)            # (2L, T)
    b_by = _to_bytes_f32(b)

    # t = a * b: 4L byte columns -> 2L limb columns, carry the low half
    t_cols = _band_mul(t_ref, a_by, b_by)
    t_limbs = _cols_to_limbs(t_cols)   # (2L, T) i32
    t_lo, c_t = _carry_sweep_val(t_limbs[:L], L)

    # m = t_lo * (-p^-1) mod R (constant product, low half kept)
    tlo_by = _to_bytes_f32(t_lo)
    m_cols = _band_mul_const(t_ref, ninv_bytes, tlo_by)[:2 * L]
    m, _ = _carry_sweep_val(_cols_to_limbs(m_cols), L)

    # m * p (constant product, full width)
    m_by = _to_bytes_f32(m)
    mp_cols = _band_mul_const(t_ref, mod_bytes, m_by)
    mp_limbs = _cols_to_limbs(mp_cols)  # (2L, T)

    # low half of t + m*p is 0 mod R; only its carry-out survives
    _, c_low = _carry_sweep_val(t_lo + mp_limbs[:L], L)

    # high half: (t + m*p) / R, then one conditional subtract of p
    hi = t_limbs[L:] + mp_limbs[L:]
    hi = jnp.concatenate([hi[:1] + (c_t + c_low)[None], hi[1:]], axis=0)
    # 2^(16L) - p as a (L, 1) column built from inlined scalar constants
    # (pallas kernels cannot capture array constants)
    negp = jnp.concatenate(
        [jnp.full((1, 1), int(v), jnp.int32) for v in negmod_limbs], axis=0)
    r1, c1 = _carry_sweep_val(hi, L)
    r2, c2 = _carry_sweep_val(hi + negp, L)
    take2 = (c2 != 0)[None, :]
    o_ref[...] = jnp.where(take2, r2, r1).astype(jnp.uint32)


# Kernel variant (bit-identical outputs in every case):
#   DPT_MUL_MXU=1 -> lazy-carry with the constant bands as bf16 Toeplitz
#     matmuls on the MXU (opt-in: the chip A/B measured parity with the
#     lazy kernel within run-to-run noise at the default tile —
#     BASELINE.md);
#   DPT_MUL_LAZY=1 -> all-VPU lazy-carry (round-5 default: the chip A/B
#     mul_tile_ab_r05.json measured it ~13-14% over strict at every tile
#     width — Fr 17.6->15.2 ns, Fq 45.7->39.7 ns at tile 512);
#   else the strict kernel.
if os.environ.get("DPT_MUL_MXU", "0") != "0":
    _VARIANT = "mxu"
elif os.environ.get("DPT_MUL_LAZY", "1") != "0":
    _VARIANT = "lazy"
else:
    _VARIANT = "strict"

_KERNELS = {"mxu": _mont_mul_kernel_mxu, "lazy": _mont_mul_kernel_lazy,
            "strict": _mont_mul_kernel}


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _mont_mul_flat(spec_key, interpret, variant, tile, a, b):
    """(L, N) x (L, N) -> (L, N), N a multiple of `tile` (the resolved
    lane tile — a static jit arg, so plan-tuned and knob-tuned tiles
    compile distinct programs instead of sharing one)."""
    from .field_jax import FR, FQ

    spec = FR if spec_key == "fr" else FQ
    L = spec.n_limbs
    kernel = functools.partial(
        _KERNELS[variant], n_limbs=L,
        mod_limbs=tuple(int(x) for x in spec.mod_limbs),
        ninv_bytes=tuple(_const_bytes(int_from_limbs(spec.ninv_limbs), 2 * L)),
        mod_bytes=tuple(_const_bytes(int_from_limbs(spec.mod_limbs), 2 * L)),
        negmod_limbs=tuple(int(x) for x in spec.negmod_limbs),
    )
    from jax.experimental.pallas import tpu as pltpu

    n = a.shape[1]
    grid = n // tile
    scratch = [pltpu.VMEM((4 * L, tile), jnp.float32)]
    in_specs = [pl.BlockSpec((L, tile), lambda i: (0, i)),
                pl.BlockSpec((L, tile), lambda i: (0, i))]
    operands = [a, b]
    if variant == "mxu":
        # broadcast constant Toeplitz operands: same block every grid step
        cn = jnp.asarray(spec.ninv_toeplitz, jnp.bfloat16)
        cp = jnp.asarray(spec.mod_toeplitz, jnp.bfloat16)
        in_specs += [pl.BlockSpec(cn.shape, lambda i: (0, 0)),
                     pl.BlockSpec(cp.shape, lambda i: (0, 0))]
        operands += [cn, cp]
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((L, n), jnp.uint32),
        grid=(grid,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((L, tile), lambda i: (0, i)),
        scratch_shapes=scratch,
        interpret=interpret,
    )(*operands)


def int_from_limbs(limbs):
    v = 0
    for i, x in enumerate(limbs):
        v |= int(x) << (LIMB_BITS * i)
    return v


def mont_mul(spec, a, b):
    """Drop-in replacement for field_jax.mont_mul (same semantics):
    broadcasts b against a, flattens batch dims to lanes, pads to the
    lane tile, dispatches the fused kernel."""
    from .field_jax import pallas_interpret

    L = spec.n_limbs
    shape = jnp.broadcast_shapes(a.shape, b.shape)
    a = jnp.broadcast_to(a, shape)
    b = jnp.broadcast_to(b, shape)
    lanes = 1
    for d in shape[1:]:
        lanes *= d
    af = a.reshape(L, lanes)
    bf = b.reshape(L, lanes)
    pad = (-lanes) % LANE_TILE
    if pad:
        af = jnp.pad(af, ((0, 0), (0, pad)))
        bf = jnp.pad(bf, ((0, 0), (0, pad)))
    out = _mont_mul_flat(spec.name.lower(), pallas_interpret(), _VARIANT,
                         LANE_TILE, af, bf)
    if pad:
        out = out[:, :lanes]
    return out.reshape(shape)
