"""Pallas fused multi-stage NTT: radix-16/64 worth of butterflies per
HBM round trip.

WHY (round-5 bench line + ROADMAP direction 3): after the fused MSM landed, the
NTT is the prover's dominant non-MSM kernel and it is pure
HBM-bandwidth-bound — `mfu_ntt_pct` ~2.15 against a ~64% Fq multiplier,
because every butterfly stage of the constant-geometry core round-trips
the full (16, n) vector through HBM and radix-4 (PR 3) only halved the
stage count. This kernel applies the exact msm_pallas playbook: keep
the working set VMEM-resident across MANY stages, so one HBM round trip
retires R = log2(rows) radix-2 stages (rows = 16..64, i.e. radix-16/64)
instead of two.

THE TILING (why a column tile can run R stages locally): one
constant-geometry radix-2 stage maps v[p], v[p + n/2] -> out[2p],
out[2p+1] — the TOP index bit is consumed and a new BOTTOM bit is
produced. Composing R consecutive stages therefore consumes the top R
bits and emits R bottom bits: with the input viewed as a (2^R, M)
matrix (row r = top bits, column c = low bits, M = n/2^R), the final
outputs out[(c << R) | b] for one column c depend ONLY on the 2^R input
rows of that same column. Columns never mix inside a group — so a
(16, 2^R, T) column tile runs all R stages in VMEM. Better: tracking
the index algebra shows the WITHIN-TILE dataflow is itself constant
geometry on the row axis (butterfly row r with row r + 2^(R-1), write
rows 2r, 2r+1), and the stage-τ twiddle for pair row r depends only on
(r mod 2^τ, c) — so per fused stage the kernel streams a small
(16, 2^τ, T) table of PRECOMPUTED twiddle values and broadcasts it
along the repeat axis. Total twiddle traffic per group is < n lanes
(sum_τ 2^τ · M), comparable to one radix-4 pair's gather volume, while
the DATA makes ceil(log2(n)/R) round trips instead of log2(n)/2.

Traffic model at n = 2^20, rows = 64 (R = 6): radix-4 moves the
(16, n) vector through HBM 10 times (plus twiddle gathers); the fused
kernel moves it ceil(20/6) = 4 times plus one output-permutation pass
— ~2.2x less stage traffic, approaching the 2-pass floor of a
bandwidth-bound transform. The butterfly math itself reuses the
bit-identical in-VMEM Montgomery primitives shared with
curve_pallas/field_pallas (strict SOS multiply, paired Kogge-Stone
carry sweeps), so outputs are limb-identical to the XLA stage cores.

BOUNDARY FUSION (mirrors PR 3's peeled stages): the forward-coset g^j
pre-scale rides the first group as a per-block multiply (group 0's
first stage has trivial twiddles, exactly like _stage4_coset_first);
the iNTT 1/n and inverse-coset g^-i post-scales ride the LAST group,
applied pre-permutation through a bit-reverse-reordered table. The
output bit-reversal itself stays an XLA gather on the kernel result (a
rectangular-block write of a bit-reversed tile is not expressible as a
BlockSpec; the gather is pure data movement and fuses with whatever
consumes the output — e.g. the round-3 pointwise epilogues). Consumer-
side fusion LANDED (DPT_R3_BITREV, jax_backend): the fused round-3
pipeline skips this gather entirely on every producer launch
(NttPlan kernel defer_perm — accumulators stay in constant-geometry
order) and pays ONE input gather at the consuming coset-iNTT instead.

Select with DPT_NTT_KERNEL=pallas (interpret mode is test-only:
DPT_PALLAS_INTERPRET=1). `auto` resolves to the radix-4 XLA core on every
platform, because this kernel has never compiled on the v5e: Mosaic
(PR 21, libtpu 0.0.34) refuses it with "Not implemented: Broadcast in
both sublanes and lanes" at the (L, 1, 1) modulus columns of _col3, which
is what asking for it by name raises there. An experiment in that PR
(lane-splat columns, twiddle tables stored repeated to 8 sublanes; not
kept, it was only ever verified interpreted) got past that refusal, and
then the compile of one 2^13 group program did not return within 225 s,
nor a 64-point one within 640 s — CHANGES.md PR 21. Tiles are sized against
DPT_NTT_PALLAS_VMEM_MB; DPT_NTT_PALLAS_ROWS caps the per-group row
count (the analog of msm's group cap).
"""

import functools
import os

import numpy as np
import jax
import jax.numpy as jnp

from . import autotune
from .curve_pallas import _mod_add, _mod_sub, _row0_mask, field_consts
from .field_jax import pallas_interpret
from .field_pallas import _carry_sweep_val, _cols_to_limbs, _to_bytes_f32

# peak VMEM one grid cell may occupy; the lane tile (and then the fused
# row count) shrink to fit. Per (row, lane) the cell charges: in + out
# blocks (2 x 4 B x 16 limbs), the stage twiddle blocks (sum_τ 2^τ ~ one
# more 16-limb row set), a boundary-scale block, and the (4L, rows, T)
# f32 multiplier scratch (64 rows x 4 B) -> ~512 B.
_VMEM_MB_DEFAULT = 6
_VMEM_MB = int(os.environ.get("DPT_NTT_PALLAS_VMEM_MB",
                              str(_VMEM_MB_DEFAULT)))
_PER_ROW_LANE_BYTES = 512

# group cap: largest fused row count 2^R per HBM round trip (the analog
# of msm_jax's DPT_MSM_GROUP_MAX plane cap); 64 = radix-64
_ROWS_CAP_DEFAULT = 64
_ROWS_CAP = int(os.environ.get("DPT_NTT_PALLAS_ROWS",
                               str(_ROWS_CAP_DEFAULT)))


def _vmem_mb(n=None):
    """Per-call VMEM budget: the env/patched module attr wins, else the
    autotune plan's winner near domain size n, else the default."""
    return int(autotune.attr_or_plan(
        _VMEM_MB, _VMEM_MB_DEFAULT, "DPT_NTT_PALLAS_VMEM_MB",
        "ntt", "vmem_mb", n, cast=int))


def _rows_knob(n=None):
    """Per-call fused-row cap knob (same precedence as _vmem_mb)."""
    return int(autotune.attr_or_plan(
        _ROWS_CAP, _ROWS_CAP_DEFAULT, "DPT_NTT_PALLAS_ROWS",
        "ntt", "rows", n, cast=int))


def fused_rows_cap(n=None):
    """Largest power-of-two fused row count whose working set keeps a
    full 128-lane tile inside the VMEM budget (>= 4 so tiny budgets
    still fuse two stages; capped by the group knob)."""
    cap = (_vmem_mb(n) << 20) // (_PER_ROW_LANE_BYTES * 128)
    cap = 1 << max(2, cap.bit_length() - 1)
    knob = max(4, _rows_knob(n))
    knob = 1 << (knob.bit_length() - 1)
    return min(cap, knob)


def _lane_tile(m_cols, rows, n=None):
    """Columns per grid cell: widest power-of-two tile within budget
    (>= 1; 256 lanes is plenty to feed the VPU)."""
    t = (_vmem_mb(n) << 20) // (_PER_ROW_LANE_BYTES * rows)
    t = 1 << max(0, t.bit_length() - 1)
    return max(1, min(m_cols, t, 256))


def plan_schedule(log_n):
    """Balanced partition of the log2(n) radix-2 stages into
    ceil(log_n / R_max) fused groups: tuple of (s0, R) with s0 the first
    global stage of the group. () for log_n < 2 (no fusion win; the XLA
    core covers those widths — same fallback as radix-4's n <= 2)."""
    if log_n < 2:
        return ()
    r_max = fused_rows_cap(1 << log_n).bit_length() - 1
    n_groups = -(-log_n // r_max)
    base, extra = divmod(log_n, n_groups)
    sizes = [base + 1] * extra + [base] * (n_groups - extra)
    out, s0 = [], 0
    for r in sizes:
        out.append((s0, r))
        s0 += r
    return tuple(out)


def group_tables(log_n, exps, pow_tab, schedule):
    """Host twiddle-VALUE tables for every fused stage, as a FLAT dict
    (flat so mesh shard_map const specs and jit args treat them like any
    other stage-core table): key 'pg{g}s{t}' -> (16, 2^t, M_g) Montgomery
    values, M_g = n >> R_g.

    Stage t of group (s0, R) butterflies pair row r of column c with
    twiddle w^e(s0+t, (c << t) | (r mod 2^t)) — the global pair index is
    (c << t) | h + q*2^(k-R+t) and e(s, p) depends on p mod 2^s only, so
    the repeat coordinate q drops out and the table is (2^t, M) instead
    of (2^(R-1), M). Group 0's stage 0 is the trivial w^0 stage (no
    table, no multiply — the peeled-first-stage identity of PR 3)."""
    n = 1 << log_n
    out = {}
    for g, (s0, r) in enumerate(schedule):
        m_cols = n >> r
        c = np.arange(m_cols, dtype=np.int64)[None, :]
        for t in range(r):
            if s0 + t == 0:
                continue  # trivial stage: every twiddle is w^0 = 1
            h = np.arange(1 << t, dtype=np.int64)[:, None]
            e = exps[s0 + t, (c << t) | h]  # (2^t, M)
            out[f"pg{g}s{t}"] = pow_tab[:, e]
    return out


def schedule_from_consts(log_n, consts):
    """Recover the group schedule from the table keys/shapes, so the
    traced program always agrees with the consts it was handed (the env
    knobs may have moved between consts build and trace)."""
    rows = {}
    for key, v in consts.items():
        if not key.startswith("pg"):
            continue
        g = int(key[2:key.index("s")])
        m_cols = v.shape[-1]
        rows[g] = log_n - (m_cols.bit_length() - 1)
    if not rows:
        return ()
    out, s0 = [], 0
    for g in range(max(rows) + 1):
        if g not in rows:
            raise ValueError(f"pallas NTT consts missing group {g} tables")
        out.append((s0, rows[g]))
        s0 += rows[g]
    if s0 != log_n:
        raise ValueError(
            f"pallas NTT schedule covers {s0} stages, expected {log_n}")
    return tuple(out)


def _col3(limbs):
    """Python limb ints -> (L, 1, 1) i32 column broadcastable against the
    kernel's (L, rows, T) blocks (pallas kernels cannot capture array
    constants; see curve_pallas._col_const)."""
    return jnp.concatenate(
        [jnp.full((1, 1, 1), int(v), jnp.int32) for v in limbs], axis=0)


def fr_consts():
    """Hashable Fr constant tuple (jit-static kernel parameter)."""
    from .field_jax import FR

    return field_consts(FR)


def _env3(kc):
    """Constant tuple -> the dict the block-shaped helpers consume, with
    the modulus columns at rank 3 (curve_pallas.consts_env is the rank-2
    spelling for the lane-flat curve kernels)."""
    k = dict(kc)
    k["negp"] = _col3(k.pop("negmod_limbs"))
    k["p_col"] = _col3(k.pop("mod_limbs"))
    return k


def _band3(t_ref, a_bytes, b_bytes):
    """Banded byte-product accumulation on (2L, rh, T) blocks into the
    (4L, rows, T) f32 VMEM scratch (field_pallas._band_mul one rank up;
    the zeroing covers the FULL scratch so the write is strong for the
    static verifier's ref cells — see curve_pallas._band_mul_w)."""
    nb, rh = a_bytes.shape[0], a_bytes.shape[1]
    t_ref[...] = jnp.zeros(t_ref.shape, jnp.float32)
    for i in range(nb):
        t_ref[i:i + nb, :rh] += a_bytes[i][None] * b_bytes
    return t_ref[:, :rh]


def _band3_const(t_ref, c_bytes, b_bytes):
    """Same accumulation with a compile-time constant multiplicand."""
    nb, rh = b_bytes.shape[0], b_bytes.shape[1]
    t_ref[...] = jnp.zeros(t_ref.shape, jnp.float32)
    for i, c in enumerate(c_bytes):
        if c == 0:
            continue
        t_ref[i:i + nb, :rh] += np.float32(c) * b_bytes
    return t_ref[:, :rh]


def _mont3(t_ref, a, b, k):
    """Full strict Montgomery SOS product on (L, rh, T) i32 blocks —
    curve_pallas._mont_mul_val one rank up (same phase sequence as
    field_jax.mont_mul, so results are fully reduced and limb-identical
    to the XLA stage cores' multiplies)."""
    L = k["n_limbs"]
    a_by = _to_bytes_f32(a)
    b_by = _to_bytes_f32(b)
    t_cols = _band3(t_ref, a_by, b_by)
    t_limbs = _cols_to_limbs(t_cols)
    t_lo, c_t = _carry_sweep_val(t_limbs[:L], L)
    tlo_by = _to_bytes_f32(t_lo)
    m_cols = _band3_const(t_ref, k["ninv_bytes"], tlo_by)[:2 * L]
    m, _ = _carry_sweep_val(_cols_to_limbs(m_cols), L)
    m_by = _to_bytes_f32(m)
    mp_cols = _band3_const(t_ref, k["mod_bytes"], m_by)
    mp_limbs = _cols_to_limbs(mp_cols)
    _, c_low = _carry_sweep_val(t_lo + mp_limbs[:L], L)
    hi = t_limbs[L:] + mp_limbs[L:]
    hi = hi + _row0_mask(hi.shape) * (c_t + c_low)[None]
    r1, _ = _carry_sweep_val(hi, L)
    r2, c2 = _carry_sweep_val(hi + k["negp"], L)
    return jnp.where((c2 != 0)[None], r2, r1)


def _ntt_group_kernel(x_ref, *refs, kc, rows, tile, stage_tabs, has_pre,
                      has_post):
    """One (batch, column-tile) grid cell: R = log2(rows) fused
    constant-geometry stages entirely in VMEM.

    x_ref: (16, 1, rows, T) input block (rows = top index bits). refs:
    [pre block] + one (16, 2^t, T) twiddle block per non-trivial stage +
    [post block], then the (16, 1, T, rows) output block and the
    (4*16, rows, T) f32 multiplier scratch. stage_tabs[t] says whether
    stage t has a table (False only for the trivial global stage 0)."""
    refs = list(refs)
    t_ref = refs.pop()
    o_ref = refs.pop()
    k = _env3(kc)
    L = k["n_limbs"]
    cur = x_ref[...].reshape(L, rows, tile).astype(jnp.int32)
    if has_pre:
        # forward-coset g^j pre-scale fused into the first load (the
        # quarters-of-the-coset-table trick of _stage4_coset_first,
        # generalized to 2^R rows)
        cur = _mont3(t_ref, cur, refs.pop(0)[...].astype(jnp.int32), k)
    half = rows // 2
    for t, has_tab in enumerate(stage_tabs):
        u = cur[:, :half]
        w = cur[:, half:]
        if has_tab:
            tw = refs.pop(0)[...].astype(jnp.int32)  # (L, 2^t, T)
            reps = half >> t
            twb = jnp.broadcast_to(
                tw[:, None], (L, reps, 1 << t, tile)).reshape(L, half, tile)
            w = _mont3(t_ref, w, twb, k)
        hi = _mod_add(u, w, L, k["negp"])
        lo = _mod_sub(u, w, L, k["p_col"])
        # constant-geometry interleave on the row axis: out[2r] = hi_r,
        # out[2r+1] = lo_r (stack + major-axis reshape, the Mosaic-safe
        # interleave of field_pallas._to_bytes_f32)
        cur = jnp.stack([hi, lo], axis=2).reshape(L, rows, tile)
    if has_post:
        # iNTT 1/n / inverse-coset scales, bit-reverse-reordered so they
        # apply pre-permutation (see NttPlan._kernel_consts)
        cur = _mont3(t_ref, cur, refs.pop(0)[...].astype(jnp.int32), k)
    out = cur.swapaxes(1, 2).astype(jnp.uint32)  # (L, T, rows)
    o_ref[...] = out.reshape(o_ref.shape)


def _group_call(v, r, tws, pre, post, interpret):
    """One fused group over the whole (16, B, n) array: grid
    (B, M/T) of independent column tiles; input viewed as
    (16, B, 2^R, M), output written as (16, B, M, 2^R) — which IS the
    flat constant-geometry output vector, reshaped."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    L, B, n = v.shape
    rows = 1 << r
    m_cols = n // rows
    tile = _lane_tile(m_cols, rows, n)
    operands = [v.reshape(L, B, rows, m_cols)]
    in_specs = [pl.BlockSpec((L, 1, rows, tile), lambda b, c: (0, b, 0, c))]
    if pre is not None:
        operands.append(jnp.asarray(pre).reshape(L, rows, m_cols))
        in_specs.append(pl.BlockSpec((L, rows, tile), lambda b, c: (0, 0, c)))
    for t, tw in enumerate(tws):
        if tw is None:
            continue
        operands.append(jnp.asarray(tw))
        in_specs.append(
            pl.BlockSpec((L, 1 << t, tile), lambda b, c: (0, 0, c)))
    if post is not None:
        operands.append(jnp.asarray(post))
        in_specs.append(pl.BlockSpec((L, rows, tile), lambda b, c: (0, 0, c)))
    kernel = functools.partial(
        _ntt_group_kernel, kc=fr_consts(), rows=rows, tile=tile,
        stage_tabs=tuple(tw is not None for tw in tws),
        has_pre=pre is not None, has_post=post is not None)
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((L, B, m_cols, rows), jnp.uint32),
        grid=(B, m_cols // tile),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((L, 1, tile, rows), lambda b, c: (0, b, c, 0)),
        scratch_shapes=[pltpu.VMEM((4 * L, rows, tile), jnp.float32)],
        interpret=interpret,
    )(*operands)
    return out.reshape(L, B, n)


def run_groups(v, consts):
    """(16, B, n) natural-order Montgomery rows -> ALL butterfly stages,
    fused group-wise; output in the same bit-reversed (constant-geometry)
    order the XLA stage cores produce, so the caller applies
    consts['perm'] exactly as before. 'ppre' (coset pre-scale, flat
    (16, n)) rides the first group; 'ppost' (reordered inverse scales,
    (16, rows, M)) rides the last."""
    n = v.shape[2]
    log_n = n.bit_length() - 1
    schedule = schedule_from_consts(log_n, consts)
    if not schedule:
        raise ValueError("no pallas NTT tables in consts")
    interpret = pallas_interpret()
    last = len(schedule) - 1
    for g, (s0, r) in enumerate(schedule):
        tws = [consts.get(f"pg{g}s{t}") for t in range(r)]
        pre = consts.get("ppre") if g == 0 else None
        post = consts.get("ppost") if g == last else None
        v = _group_call(v, r, tws, pre, post, interpret)
    return v
