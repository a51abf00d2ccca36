"""Pallas fused MSM bucket accumulation: VMEM-resident bucket planes.

WHY (round-5 bench line + scripts/scatter_ab.py round 4): after the
radix-4 NTT landed, the variable-base MSM is the prover's dominant kernel by an
order of magnitude (2^20 MSM 49.2 s vs 2^20 NTT 5.6 s), and it runs at
`mfu_msm_pct` 19.4 against a 63.7% multiplier — ~3x headroom that the
scatter A/B already attributed to bucket-plane MEMORY TRAFFIC, not the
RCB15 add: every `lax.scan` step of msm_jax._bucket_scan* issues the
one-hot gather/update as XLA ops, so the full (G, M, B) plane
round-trips HBM once per step (the measured 3.5 ms/step floor at
G=256, M=32, B=128).

THIS kernel fuses the whole per-step pipeline — digit decode, bucket
gather, complete projective mixed add (RCB15 algorithm 8), bucket
update — into one Pallas program whose bucket planes live in VMEM
scratch for the entire point stream:

  grid = (window_tiles, steps), steps innermost. For one tile of Mt
  window lanes, the (rows, B, G*Mt) plane scratch persists across all
  n/G point steps (packed limb pairs by default: 12 rows of u32 — a
  (G=8, B=128) per-window plane is ~150 KB, so ~256 resident lanes fit
  in ~4.7 MB of VMEM); each step streams one (24, G) point tile plus a
  (G*Mt,) op word tile from HBM and performs the gather + add + update
  entirely in registers/VMEM, reusing curve_pallas.add_mixed_val (the
  same straight-line RCB15 sequence, bit-identical to the XLA path)
  and field_pallas' carry sweeps.

HBM traffic model: the XLA scan moves 3 coords x rows x G x M x B x 4 B
of plane per step (n/G steps); this kernel reads each point tile
ceil(M/Mt) times, reads the op words once, and writes the planes ONCE
at the end — per-step HBM traffic drops from the full plane round trip
to 'read points + ops once-ish', leaving the RCB15 multiplier as the
bound (the whole reason the fused multiplier's 3x headroom is
recoverable).

Bit-identity: digits, skip/sign derivation, gather, RCB15 add, and
update replicate the EXACT op sequence of msm_jax._bucket_scan /
_bucket_scan_signed with fully-reduced canonical intermediates, so the
output planes are limb-identical to the XLA path at the same group
width (tests/test_msm_pallas.py in interpret mode; on the v5e in PR 21's
chip run at the 2^13 commit shape), and everything downstream (fold /
finish / proof bytes) is unchanged. Select DPT_MSM_KERNEL=pallas; `auto`
stays on the XLA scan because one shape takes Mosaic 387 s to compile
(msm_jax._use_pallas_kernel).
"""

import functools
import os

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from ..constants import FQ_LIMBS
from .curve_pallas import add_mixed_val, consts_env, fq_consts, _mod_sub
from .field_jax import pallas_interpret, unpack_limb_pairs

# op-word encoding shared by the wrapper (XLA side) and the kernel:
# bits [0, 8) bucket index, bit 8 negate-y, bit 9 skip (zero digit /
# infinity / lane padding)
_NEG_BIT = 8
_SKIP_BIT = 9

# peak VMEM the resident bucket planes may occupy (3 coords x rows x
# B x lanes x 4 B); the lane tile shrinks to fit
_VMEM_MB = int(os.environ.get("DPT_MSM_PALLAS_VMEM_MB", "6"))


def plane_lanes_cap(n_buckets, packed):
    """Largest power-of-two G*Mt lane count whose PER-LANE VMEM footprint
    fits the budget (>= 8 so degenerate budgets still run). Charged per
    lane: the three resident bucket-plane scratches plus their
    same-shaped output windows (revisited across the step grid axis, so
    they occupy VMEM alongside the scratch), the f32 multiplier scratch
    (4*L x 6*lanes), and the op-word block; the per-group point tile is
    amortized over Mt lanes and left out."""
    rows = FQ_LIMBS // 2 if packed else FQ_LIMBS
    per_lane = (6 * rows * n_buckets * 4   # planes: scratch + out window
                + 4 * FQ_LIMBS * 6 * 4     # mul scratch t_ref
                + 4)                       # op words
    cap = (_VMEM_MB << 20) // per_lane
    return max(8, 1 << max(3, cap.bit_length() - 1))


def _pack_rows(v):
    """field_jax.pack_limb_pairs for in-kernel values: the row pairs come
    from a major-axis reshape (the same split field_pallas._cols_to_limbs
    lowers with) because the strided v[0::2] lowers to a gather Mosaic
    refuses. Same words bit for bit."""
    pairs = v.reshape((v.shape[0] // 2, 2) + v.shape[1:])
    return pairs[:, 0] | jnp.left_shift(pairs[:, 1], 16)


def _lane_repeat(v, mt):
    """(rows, G) -> (rows, G*mt) with out[:, g*mt + ml] = v[:, g]
    (jnp.repeat on the lane axis). Built from G lane-broadcast selects:
    jnp.repeat lowers to a lane reshape Mosaic refuses, while a static
    one-lane slice broadcast against a lane-range mask is plain VPU work,
    G (<= 32) cheap passes beside the RCB15 add's ~12 multiplies."""
    rows, group = v.shape
    lane = lax.broadcasted_iota(jnp.int32, (1, group * mt), 1)
    out = jnp.zeros((rows, group * mt), v.dtype)
    for g in range(group):
        sel = (lane >= g * mt) & (lane < (g + 1) * mt)
        out = jnp.where(sel, v[:, g:g + 1], out)
    return out


def _bucket_kernel(sx_ref, sy_ref, ops_ref, ox_ref, oy_ref, oz_ref,
                   px_ref, py_ref, pz_ref, t_ref, *, kc, n_buckets,
                   signed, packed, steps, mt, one_rows):
    """One (window-tile, step) grid cell: gather + RCB15 mixed add +
    update on the VMEM-resident planes.

    px/py/pz scratch: (rows, B, L) u32 bucket planes, L = G*Mt lanes
    (lane l = g*Mt + ml), persisted across the `steps` grid axis.
    sx/sy: one (24, G) affine Montgomery point tile. ops: (L,) op words.
    ox/oy/oz: (rows, B, L) plane outputs, written on the last step.
    """
    k = consts_env(kc)
    L = k["n_limbs"]
    s = pl.program_id(1)
    plane_shape = px_ref.shape

    @pl.when(s == 0)
    def _init():
        # projective identity (0 : 1 : 0), row-packed like the carries
        # each row a full-shape splat: broadcasting a (rows, 1, 1) column
        # over buckets AND lanes at once is not implemented in Mosaic
        zero = jnp.zeros(plane_shape, jnp.uint32)
        px_ref[...] = zero
        py_ref[...] = jnp.concatenate(
            [jnp.full((1,) + plane_shape[1:], int(v), jnp.uint32)
             for v in one_rows], axis=0)
        pz_ref[...] = zero

    ops = ops_ref[...].reshape(1, ops_ref.shape[-1])      # (1, lanes)
    idx = ops & (n_buckets - 1)
    negb = ((ops >> _NEG_BIT) & 1) != 0
    skipb = ((ops >> _SKIP_BIT) & 1) != 0

    # one-hot bucket gather: at most one hit per lane along the bucket
    # (sublane) axis, so the masked sum IS the per-lane bucket value.
    # The mask is built at FULL rank (iota directly over (1, B, L), the
    # compare against a trailing-1 reshape) — the same structural shape
    # as the XLA onehot path, which analysis/bounds.py recognizes; a
    # reshape AFTER the eq would drop the one-hot tag and the verifier
    # would multiply the sum bound by B
    hit = (lax.broadcasted_iota(jnp.uint32, (1,) + plane_shape[1:], 1)
           == idx[:, None, :])

    def gather(word):
        # summed as i32: Mosaic has no unsigned reduction. Every operand
        # is a 16-bit limb, so the conversion is exact, and it comes
        # BEFORE the select so the masked sum keeps its one-hot shape
        return jnp.sum(jnp.where(hit, word.astype(jnp.int32), 0), axis=1)

    planes = tuple(r[...] for r in (px_ref, py_ref, pz_ref))
    if packed:
        # the two limbs of each packed word are gathered apart (a packed
        # word can exceed 2^31) and interleaved as unpack_limb_pairs does
        cur = tuple(
            jnp.stack([gather(w & 0xFFFF), gather(jnp.right_shift(w, 16))],
                      axis=1).reshape(2 * plane_shape[0], plane_shape[2])
            for w in planes)
    else:
        cur = tuple(gather(w) for w in planes)

    sx = sx_ref[...].reshape(FQ_LIMBS, sx_ref.shape[-1]).astype(jnp.int32)
    sy = sy_ref[...].reshape(FQ_LIMBS, sy_ref.shape[-1]).astype(jnp.int32)
    if signed:
        # negate once per point tile (the XLA scan's FJ.neg), select per
        # lane after the window broadcast
        nsy = _mod_sub(jnp.zeros_like(sy), sy, L, k["p_col"])
        qy = jnp.where(negb, _lane_repeat(nsy, mt), _lane_repeat(sy, mt))
    else:
        qy = _lane_repeat(sy, mt)
    sxb = _lane_repeat(sx, mt)

    res = add_mixed_val(t_ref, k, cur, (sxb, qy))
    nv = tuple(jnp.where(skipb, c, r).astype(jnp.uint32)
               for c, r in zip(cur, res))
    if packed:
        nv = tuple(_pack_rows(v) for v in nv)
    for r, v in zip((px_ref, py_ref, pz_ref), nv):
        r[...] = jnp.where(hit, v[:, None, :], r[...])

    @pl.when(s == steps - 1)
    def _flush():
        ox_ref[0] = px_ref[...]
        oy_ref[0] = py_ref[...]
        oz_ref[0] = pz_ref[...]


@functools.partial(jax.jit,
                   static_argnums=(0, 1, 2, 3, 4, 5, 6))
def _bucket_call(interpret, group, n_buckets, signed, packed, mt, wt,
                 sx, sy, ops):
    """(steps, 24, G) points + (Wt, steps, G*Mt) op words -> 3 x
    (Wt, rows, B, G*Mt) u32 planes."""
    from jax.experimental.pallas import tpu as pltpu
    from .field_jax import FQ
    from .limbs import int_to_limbs
    from ..constants import FQ_MONT_R, Q_MOD

    steps = sx.shape[0]
    lanes = group * mt
    rows = FQ_LIMBS // 2 if packed else FQ_LIMBS
    one = int_to_limbs(FQ_MONT_R % Q_MOD, FQ_LIMBS)
    if packed:
        one_rows = tuple(int(one[2 * i]) | (int(one[2 * i + 1]) << 16)
                         for i in range(FQ_LIMBS // 2))
    else:
        one_rows = tuple(int(v) for v in one)
    kernel = functools.partial(
        _bucket_kernel, kc=fq_consts(), n_buckets=n_buckets,
        signed=signed, packed=packed, steps=steps, mt=mt,
        one_rows=one_rows)
    pt_spec = pl.BlockSpec((1, FQ_LIMBS, group), lambda w, s: (s, 0, 0))
    plane_spec = pl.BlockSpec((1, rows, n_buckets, lanes),
                              lambda w, s: (w, 0, 0, 0))
    return pl.pallas_call(
        kernel,
        out_shape=[jax.ShapeDtypeStruct((wt, rows, n_buckets, lanes),
                                        jnp.uint32)] * 3,
        grid=(wt, steps),
        in_specs=[pt_spec, pt_spec,
                  # (wt, steps, 1, lanes): a unit sublane axis makes the
                  # block's last two dims equal the array's, which is
                  # what the Mosaic block-shape rule asks of a 1-row block
                  pl.BlockSpec((1, 1, 1, lanes),
                               lambda w, s: (w, s, 0, 0))],
        out_specs=[plane_spec] * 3,
        scratch_shapes=[pltpu.VMEM((rows, n_buckets, lanes), jnp.uint32)
                        for _ in range(3)]
        + [pltpu.VMEM((4 * FQ.n_limbs, 6 * lanes), jnp.float32)],
        interpret=interpret,
    )(sx, sy, ops[:, :, None, :])


def _scan_pallas(ax, ay, ops, group, n_buckets, signed, packed):
    """Shared wrapper: (24, n) points + (M, n) op words ->
    ((24, G, M, B),)*3 planes, laid out exactly like the XLA scans."""
    from .msm_jax import _scan_layout, _to_scan_m

    M, n = ops.shape
    steps = n // group
    sx, sy = _scan_layout(ax, ay, group)
    sops = _to_scan_m(ops, group)                    # (steps, G, M)

    cap = plane_lanes_cap(n_buckets, packed)
    mt = max(1, min(M, cap // group))
    wt = -(-M // mt)
    pad = wt * mt - M
    if pad:
        sops = jnp.pad(sops, ((0, 0), (0, 0), (0, pad)),
                       constant_values=np.uint32(1 << _SKIP_BIT))
    # (steps, G, Wt, Mt) -> (Wt, steps, G*Mt): lane l = g*Mt + ml
    sops = sops.reshape(steps, group, wt, mt).transpose(2, 0, 1, 3)
    sops = sops.reshape(wt, steps, group * mt)

    outs = _bucket_call(pallas_interpret(), group, n_buckets, signed,
                        packed, mt, wt, sx, sy, sops)
    planes = []
    for o in outs:
        rows = o.shape[1]
        o = o.reshape(wt, rows, n_buckets, group, mt)
        # (w, r, b, g, ml) -> (r, g, w, ml, b) -> (r, g, M, b)
        o = o.transpose(1, 3, 0, 4, 2).reshape(
            rows, group, wt * mt, n_buckets)[:, :, :M]
        if packed:
            o = unpack_limb_pairs(o)
        planes.append(o)
    return tuple(planes)


def bucket_scan(ax, ay, ainf, digits, group, n_buckets, packed=True):
    """Fused-kernel counterpart of msm_jax._bucket_scan (unsigned):
    identical signature and bit-identical ((24, G, M, B),)*3 planes."""
    ops = digits | (ainf[None].astype(jnp.uint32) << _SKIP_BIT)
    return _scan_pallas(ax, ay, ops, group, n_buckets,
                        signed=False, packed=packed)


def bucket_scan_signed(ax, ay, ainf, packed_digits, group,
                       n_buckets=128, packed=True):
    """Fused-kernel counterpart of msm_jax._bucket_scan_signed: the
    sign/skip/index derivation matches the XLA scan step for step."""
    off = packed_digits.astype(jnp.int32) - n_buckets
    neg = off < 0
    mag = jnp.abs(off)
    skip = (mag == 0) | ainf[None]
    idx = jnp.maximum(mag, 1).astype(jnp.uint32) - 1
    ops = (idx
           | (neg.astype(jnp.uint32) << _NEG_BIT)
           | (skip.astype(jnp.uint32) << _SKIP_BIT))
    return _scan_pallas(ax, ay, ops, group, n_buckets,
                        signed=True, packed=packed)
