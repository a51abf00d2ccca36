"""Variable-base MSM on device: sort-free Pippenger over the limb G1 kernels.

Device replacement for `ark-ec`'s rayon Pippenger as the reference workers
run it (/root/reference/src/worker.rs:159-185). Scalars are decomposed into
W = 256/c radix-2^c windows — c size-dependent as in standard Pippenger
(8 bits at bench scale, smaller for small MSMs) — and each window's 2^c - 1
buckets are accumulated WITHOUT any sort or data-dependent scatter pattern:

  - points are split into G groups, each group owning a private (G, B)
    bucket array;
  - a lax.scan walks n/G point-batches: gather current buckets at the
    batch's digits (one per group), one G-wide vectorized COMPLETE
    projective mixed add (RCB15, a=0 — no edge cases, 2 stacked-lane
    multiplier instances), scatter back — all writes in a step hit
    distinct rows, so the scan is race-free by construction;
  - group bucket-planes then fold sequentially with a scan whose body is a
    single (24, W, B)-shaped complete projective add — the SAME body the
    mesh version reuses to fold planes across devices, so XLA's
    computation deduplication compiles it once;
  - the remaining O(W * B) tail (running-sum bucket aggregation,
    2^(c*w) window weighting, final window sum) runs as two more
    static-shape scans with no data-dependent indexing at all (see
    `finish`);
  - where the bases are a key's, fixed for its life, the weighting is not
    owed at commit time at all: a context over a signed wide-window key
    holds, beside each point, its 2^(c*w) multiples for every window
    (`window_table`, built once per context on the device: W times the
    points' bytes, 117 MB for the 16,416 points of a 2^14 key at c=7, and
    set-up seconds PERF.md sec. 5 gives as measured on the chip); lane
    (b, w) of the scan adds window w's copy, the planes of a polynomial's
    windows are added (`sum_windows`, 8 steps), and the tail is the bucket
    running sum alone (`finish_preweighted`, 65 steps), on B lanes: 73
    sequential steps where `finish` takes 323 on W * B lanes (c=8: 136
    for 382). `use_window_table` is the rule.

Accumulators are homogeneous PROJECTIVE (X : Y : Z), identity (0 : 1 : 0);
results decode as x = X/Z, y = Y/Z (_proj_limbs_to_affine). Large MSMs
(c = 8) use SIGNED digits: B = 128 buckets instead of 256. This keeps the
optimal ~n adds/window of Pippenger while the whole MSM compiles exactly
THREE complete-add bodies regardless of n — XLA compile time (the round-1
multichip-gate killer: >8 min for a 16-point mesh MSM) is O(1) in both n
and the number of reduction phases — and every memory access is regular.
"""

import os
from functools import partial

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from ..constants import FQ_MONT_R, Q_MOD, R_MOD, FR_LIMBS, FQ_LIMBS
from . import curve_jax as CJ
from . import field_jax as FJ
from .field_jax import FR
from .limbs import ints_to_limbs, limbs_to_int

SCALAR_BITS = 256

# accepted knob values
BUCKET_UPDATE_CHOICES = ("onehot", "put")
KERNEL_CHOICES = ("pallas", "xla")
C_CHOICES = (7, 8)


def window_bits(n):
    """Pippenger window size for an n-point MSM, restricted to divisors of
    the 16-bit limb width so digit extraction never crosses a limb.

    Standard size-dependent choice (ark picks ~ln n + 2): small inputs get
    small windows so the O(windows * 2^c) bucket-plane tail does not dwarf
    the O(n * windows) accumulation — this is also what keeps the tiny-shape
    multichip dry-run fast, where 8-bit windows would spend minutes adding
    planes of infinities."""
    if n >= 4096:
        return 8
    if n >= 64:
        return 4
    if n >= 8:
        return 2
    return 1


def _group_size(n):
    """Private-bucket group count for an n-point MSM.

    The accumulation scan does n*W lane-adds no matter what; the plane
    fold does G*W*2^c more. Measured on v5e (2.5us/lane-add end to end),
    total time tracks total lane-adds almost linearly, so G is kept at
    ~n/1024 — fold work <= 25% of scan work — instead of the old fixed 512
    (which at n=9216 made the fold 14x the scan and a 5-poly commit batch
    8x slower than G=8).

    DPT_MSM_GROUP_MAX raises the 512 cap: with the onehot plane update
    (no scatter op) per-ADD plane traffic is G-independent, so wider
    groups only amortize per-step overhead better — bounded by the fold
    work and the plane-budget cap in _group_size_batch."""
    g = int(os.environ.get("DPT_MSM_GROUP_MAX", "512"))
    if g < 1:
        g = 512
    g = 1 << (g.bit_length() - 1)  # round down to a power of two: the
    # halving search below only terminates on divisors of power-of-two n
    while g > 1 and (n % g != 0 or n // g < 2 or g * 1024 > n):
        g //= 2
    return g


# peak bucket-plane footprint allowed for a batched MSM (all three Jacobian
# coords); beyond this the group width halves, trading scan steps for HBM
_PLANE_BYTES_BUDGET = int(os.environ.get("DPT_MSM_PLANE_MB", "1536")) << 20

# Bucket-plane update strategy for the accumulation scans (DPT_BUCKET_UPDATE):
#   put:    take_along_axis / put_along_axis on the bucket axis.
#   onehot: gather = masked reduction over the bucket axis, update = broadcast
#           compare + where over the whole plane. No scatter op at all — pure
#           streaming reads/writes.
#   auto (default): onehot on TPU, put elsewhere. Measured round 4 on a v5e
#   (scripts/scatter_ab.py, G=256 M=32 B=128): put 15.6 ms/step (524k
#   lane-adds/s) vs onehot 3.5 ms/step (2.32M) — TPU scatter lowering, not
#   the projective add, was the MSM's 4.4x bottleneck. On CPU the scatter is
#   cheap and onehot's full-plane traffic (x buckets) would swamp the mesh
#   tests, hence the platform split.
_BUCKET_UPDATE = os.environ.get("DPT_BUCKET_UPDATE", "auto")


def _use_onehot_update():
    if _BUCKET_UPDATE in BUCKET_UPDATE_CHOICES:
        return _BUCKET_UPDATE == "onehot"
    return jax.default_backend() == "tpu"


# Limb-packed planes (onehot path only): scan carries hold the bucket
# planes as (12, ...) u32 with TWO 16-bit limbs per word, halving the
# dominant per-step streaming traffic (scatter_ab.py round 4: the
# gather+update pass alone is 1.6 ms of the 3.6 ms step at G=256).
# Pack/unpack are cheap shifts on the (24, G, M) gathered slice only.
# DPT_PLANE_PACK=0 opts out.
_PLANE_PACK = os.environ.get("DPT_PLANE_PACK", "1") != "0"


def _use_packed_planes():
    return _use_onehot_update() and _PLANE_PACK


# Bucket-accumulation kernel (DPT_MSM_KERNEL):
#   pallas: the fused msm_pallas kernel — digit decode, bucket gather,
#           RCB15 mixed add, and bucket update in ONE Pallas program
#           whose bucket planes stay VMEM-resident for the whole point
#           stream (no per-step HBM plane round trip).
#   xla:    the lax.scan path below (one-hot bucket update on TPU) — the
#           path every chip run to date has proved with.
#   auto (default): xla on every platform. On the v5e the fused kernel
#   now gets through Mosaic and its planes equal the scan's limb for
#   limb, but ONE shape took 387 s to compile (PR 21 chip run, libtpu
#   0.0.34; CHANGES.md) and a cold 2^13 prove commits at three batch
#   widths — so it is never what a device path falls into. Asking for
#   it by name (DPT_MSM_KERNEL=pallas) runs it; what
#   the compiler says then is the caller's to see. No handler
#   substitutes the scan at run time.
# Resolved per call (module attr, monkeypatchable) like _BUCKET_UPDATE;
# field_jax.pallas_disabled() / mesh.pallas_guard override even a forced
# "pallas" — a pallas_call has no GSPMD partitioning rule, so sharded
# traces outside shard_map must keep the XLA scan.
_MSM_KERNEL = os.environ.get("DPT_MSM_KERNEL", "auto")


def _use_pallas_kernel():
    if getattr(FJ._pallas_off, "v", False):
        return False
    if _MSM_KERNEL not in KERNEL_CHOICES + ("auto",):
        raise ValueError(
            f"DPT_MSM_KERNEL must be auto|pallas|xla, got {_MSM_KERNEL!r}")
    return _MSM_KERNEL == "pallas"


def _kernel_mode():
    return "pallas" if _use_pallas_kernel() else "xla"


# packed-pair layout shared with field_jax (round 3's packed coset evals
# use the same representation)
_pack_limbs = FJ.pack_limb_pairs
_unpack_limbs = FJ.unpack_limb_pairs


def _plane_init(proj_planes):
    """Scan-carry representation of initial projective planes."""
    if _use_packed_planes():
        return tuple(_pack_limbs(b) for b in proj_planes)
    return tuple(proj_planes)


def _plane_finish(planes):
    """Scan-carry planes -> (24, ...) limb planes for fold/finish."""
    if _use_packed_planes():
        return tuple(_unpack_limbs(b) for b in planes)
    return tuple(planes)


def _plane_gather(planes, dg):
    """Current bucket values at per-lane digits dg (G, M) from the scan's
    plane carry -> ((24, G, M),)*3 limbs, plus the reusable update
    context."""
    if _use_onehot_update():
        hit = dg[None, :, :, None] == lax.broadcasted_iota(
            dg.dtype, (1,) + planes[0].shape[1:], 3)
        cur = tuple(jnp.sum(jnp.where(hit, b, 0), axis=3, dtype=b.dtype)
                    for b in planes)
        if _use_packed_planes():
            cur = tuple(_unpack_limbs(c) for c in cur)
        return cur, hit
    dg4 = dg[None, :, :, None]
    dg4b = jnp.broadcast_to(dg4, (FQ_LIMBS,) + dg4.shape[1:])
    cur = tuple(jnp.take_along_axis(b, dg4b, axis=3)[..., 0] for b in planes)
    return cur, dg4b


def _plane_update(planes, vals, ctx):
    """Write (24, G, M) limb vals back at the gathered positions."""
    if _use_onehot_update():
        if _use_packed_planes():
            vals = tuple(_pack_limbs(v) for v in vals)
        return tuple(jnp.where(ctx, v[..., None], b)
                     for b, v in zip(planes, vals))
    return tuple(jnp.put_along_axis(b, ctx, v[..., None], axis=3,
                                    inplace=False)
                 for b, v in zip(planes, vals))


def _group_size_batch(n, batch, c, signed=False, kernel=None):
    """Group width for a B-poly batched MSM: work-optimal size per
    _group_size, further capped so the plane array (which scales with
    group * B * W * buckets) stays in budget.

    Under the fused Pallas kernel the planes live in VMEM, not HBM, so
    the cap is the VMEM lane budget instead: group shrinks so a window
    tile of >= ~8 lanes still fits (wider window tiles mean fewer
    re-reads of the point stream — see msm_pallas's traffic model);
    per-step overhead no longer rewards huge groups there.

    kernel: explicit resolved mode ('pallas'|'xla') from the caller —
    MsmContext passes the resolution its chunk memo key holds, so group
    sizing, the key and the traced branch all agree; None resolves here
    (direct/mesh callers)."""
    w = -(-SCALAR_BITS // c)  # ceil: c=7 has 37 windows, not 36
    buckets = 1 << (c - 1) if signed else 1 << c
    g = _group_size(n)
    if (kernel == "pallas") if kernel is not None \
            else _use_pallas_kernel():
        from . import msm_pallas
        cap = max(8, msm_pallas.plane_lanes_cap(
            buckets, _PLANE_PACK) // 8)
        while g > cap:
            g //= 2
    else:
        per_group = 3 * 4 * FQ_LIMBS * batch * w * buckets
        while g > 1 and g * per_group > _PLANE_BYTES_BUDGET:
            g //= 2
    while g > 1 and n % g != 0:
        g //= 2
    return g


def _scan_layout(ax, ay, group):
    """(24, n) points -> (steps, 24, group) scan inputs."""
    n = ax.shape[1]
    steps = n // group

    def to_scan(a):
        return a.reshape(FQ_LIMBS, group, steps).transpose(2, 0, 1)

    return to_scan(ax), to_scan(ay)


def _to_scan_m(a, group):
    """(M, n) per-lane rows -> (steps, group, M) scan inputs."""
    M, n = a.shape
    return a.reshape(M, group, n // group).transpose(2, 1, 0)


def _bucket_scan(ax, ay, ainf, digits, group, n_buckets, kernel=None):
    """Unsigned COMBINED-LANE bucket accumulation (small-window path).

    All M digit lanes (M = batch x windows) share the point stream: one
    gather + one scatter + ONE wide complete projective mixed add per
    scan step covers every lane — the former per-window vmap issued M
    separate gather/scatter/add op groups per step, which (a) kept each
    mont_mul below the Pallas kernel's profitable width and (b) paid the
    per-op dispatch fixed cost M times (round-4 chip measurement:
    scripts/msm_ab.py).

    ax/ay: (24, n) affine Montgomery; ainf: (n,) bool; digits: (M, n)
    uint32 < n_buckets. Returns ((24, group, M, n_buckets),)*3 PROJECTIVE
    planes with bucket b of (group g, lane m) = sum of g's points whose
    lane-m digit == b (bucket 0 included but ignored downstream).

    DPT_MSM_KERNEL=pallas runs the fused VMEM-resident kernel
    (msm_pallas.bucket_scan) — bit-identical planes at the same group
    width; this scan remains the parity/debug core. `kernel` pins the
    resolved mode from the caller (MsmContext's, so the trace matches
    its memo key); None resolves here.
    """
    if (kernel == "pallas") if kernel is not None \
            else _use_pallas_kernel():
        from . import msm_pallas
        return msm_pallas.bucket_scan(ax, ay, ainf, digits, group,
                                      n_buckets, packed=_PLANE_PACK)
    M = digits.shape[0]
    sx_all, sy_all = _scan_layout(ax, ay, group)
    xs = (sx_all, sy_all, _to_scan_m(ainf[None, :] | jnp.zeros_like(digits, bool),
                                     group),
          _to_scan_m(digits, group))

    # varying-zero: under shard_map the scan carry must inherit the inputs'
    # varying-manual-axes tag; adding a data-derived 0 does exactly that
    # (and constant-folds away otherwise)
    vz = ax.ravel()[0] & 0
    init = _plane_init(tuple(
        b + vz for b in CJ.proj_inf((group, M, n_buckets))))

    def step(carry, x):
        planes = carry                # plane carry (packed or limb) x3
        sx, sy, si, dg = x            # sx/sy (24, G); si/dg (G, M)
        cur, ctx = _plane_gather(planes, dg)
        sxb = jnp.broadcast_to(sx[:, :, None], cur[0].shape)
        syb = jnp.broadcast_to(sy[:, :, None], cur[0].shape)
        nv = CJ.proj_add_mixed(cur, (sxb, syb), si)
        return _plane_update(planes, nv, ctx), None

    planes, _ = lax.scan(step, init, xs)
    return _plane_finish(planes)


def _bucket_scan_signed(ax, ay, ainf, packed, group, n_buckets=128,
                        kernel=None, preweighted=False):
    """SIGNED-digit COMBINED-LANE bucket accumulation — the signed hot
    path (c=8: 128 bucket columns; c=7: 64): half the buckets of the
    unsigned scan (bucket i holds points whose |digit| == i+1; the sign
    is applied to the point's y on the fly), the accumulator add is
    RCB15's complete formula (11 muls in 2 stacked-lane instances, no
    doubling fallback, no edge selects), and every scan step is ONE wide
    gather/add/scatter across all M lanes (see _bucket_scan for why).

    ax/ay: (24, n) affine Montgomery; ainf: (n,) bool; packed: (M, n)
    uint32 = digit + n_buckets with digit in [-n_buckets, n_buckets-1].
    Returns ((24, group, M, n_buckets),)*3 PROJECTIVE bucket planes.

    preweighted=True: ax/ay are the two halves of a window table
    (`window_table`: n rows of 24*W, eight a tile, row i holding
    2^(c*w) * P_i for every window w) and M = B * W in the order
    m = b * W + w. Lane m then adds window w's copy of the step's point
    instead of the point itself, so every window's buckets carry the same
    weights and the planes may be added across windows (`sum_windows`).
    A step reads its `group` rows whole: point i is step i // group of
    group i % group, which needs no relayout of the table (the plain
    stream deals contiguous ranges to the groups; a sum of buckets does
    not care which).

    DPT_MSM_KERNEL=pallas runs the fused VMEM-resident kernel
    (msm_pallas.bucket_scan_signed) — bit-identical planes at the same
    group width; this scan remains the parity/debug core. `kernel`: see
    _bucket_scan. The fused kernel takes one point a step: no table.
    """
    if (kernel == "pallas") if kernel is not None \
            else _use_pallas_kernel():
        assert not preweighted, "the fused kernel takes one point a step"
        from . import msm_pallas
        return msm_pallas.bucket_scan_signed(ax, ay, ainf, packed, group,
                                             n_buckets,
                                             packed=_PLANE_PACK)
    M = packed.shape[0]
    off = packed.astype(jnp.int32) - n_buckets
    neg = off < 0
    mag = jnp.abs(off)
    skip = (mag == 0) | ainf[None, :]
    idx = jnp.maximum(mag, 1).astype(jnp.uint32) - 1  # 0..n_buckets-1

    if preweighted:
        row = ax.shape[-1]
        wins = row // FQ_LIMBS
        steps = ainf.shape[0] // group
        sx_all, sy_all = (a.reshape(steps, group, row) for a in (ax, ay))

        def to_m(a):  # (M, n) -> (steps, group, M), dealt like the rows
            return a.reshape(M, steps, group).transpose(1, 2, 0)

        def limbs(a):  # a step's rows (G, 24*W) -> (24, G, W)
            return a.reshape(group, FQ_LIMBS, wins).transpose(1, 0, 2)

        def lanes(a):  # (24, G, W) -> (24, G, M): window w on lane b*W + w
            return jnp.tile(a, (1, 1, M // wins))
    else:
        sx_all, sy_all = _scan_layout(ax, ay, group)
        to_m = partial(_to_scan_m, group=group)

        def limbs(a):  # a step's points are (24, G) as they come
            return a

        def lanes(a):  # the same point on every lane
            return a[:, :, None]

    xs = (sx_all, sy_all, to_m(skip), to_m(neg), to_m(idx))

    vz = ax.ravel()[0] & 0  # varying-zero, see _bucket_scan
    init = _plane_init(tuple(
        b + vz for b in CJ.proj_inf((group, M, n_buckets))))

    def step(carry, x):
        planes = carry                # plane carry (packed or limb) x3
        sx, sy, sk, ng, dg = x        # sk/ng/dg (G, M)
        cur, ctx = _plane_gather(planes, dg)
        sx, sy = limbs(sx), limbs(sy)
        nsy = FJ.neg(CJ.FQ, sy)       # negate once per step, select per lane
        qy = jnp.where(ng[None], lanes(nsy), lanes(sy))
        sxb = jnp.broadcast_to(lanes(sx), cur[0].shape)
        nv = CJ.proj_add_mixed(cur, (sxb, qy), sk)
        return _plane_update(planes, nv, ctx), None

    planes, _ = lax.scan(step, init, xs)
    return _plane_finish(planes)


def fold_planes(bx, by, bz):
    """(K, 24, W, B) PROJECTIVE bucket planes -> (24, W, B) bucketwise sum.

    Used for both the group fold and the mesh cross-device fold: the scan
    body is identical in both calls, so XLA compiles it once per program.
    (A log-depth pairwise tree was tried here and reverted: its first
    level is an add over K/2 planes at once, whose mont_mul column
    tensors transiently need ~150x the plane bytes — 33 GB at a batched
    2^10 MSM. The scan touches one plane per step, keeping transients at
    1/K of that; with batched pipelines the per-step lanes are wide enough
    that the sequential depth is not the bottleneck.)"""
    vz = bz.ravel()[0] & 0  # varying-zero, see _bucket_scan
    init = tuple(b + vz for b in CJ.proj_inf(bz.shape[2:]))

    def red(acc, plane):
        return CJ.proj_add(acc, plane), None

    acc, _ = lax.scan(red, init, (bx, by, bz))
    return acc


# --- finish tail -------------------------------------------------------------

def _running_sum(bx, by, bz, signed):
    """(24, L, B) buckets on L lanes -> (24, L): lane l's sum of its bucket
    columns, each times its weight, by the running-sum trick. A scan over
    the columns from the heaviest down (+ one infinity flush column), the
    carry (run_l, acc_l) stacked on a lane axis so each step is ONE
    (24, L, 2) complete projective add — pipelined:
    acc += run ; run += bucket[:, b]  per step. signed=True: column i
    weighs i+1 and all B columns count (B + 1 steps); else column 0 is
    the zero digit's and is dropped (B steps)."""
    vz = bz.ravel()[0] & 0  # varying-zero, see _bucket_scan
    inf_l = tuple(x + vz for x in CJ.proj_inf((bz.shape[1],)))

    def col_xs(a):  # (24, L, B) -> (B, 24, L): high-weight column first
        body = a if signed else a[:, :, 1:]
        return body[:, :, ::-1].transpose(2, 0, 1)

    xs = tuple(jnp.concatenate([col_xs(a), i[None, :, :]], axis=0)
               for a, i in zip((bx, by, bz), inf_l))

    def agg(carry, x):
        # carry: ((24, L, 2),)*3 with lane 0 = run, lane 1 = acc
        left = tuple(v for v in carry)
        right = tuple(jnp.stack([xi, v[:, :, 0]], axis=2)
                      for xi, v in zip(x, left))
        out = CJ.proj_add(left, right)
        return out, None

    init = tuple(jnp.stack([i, i], axis=2) for i in inf_l)
    acc2, _ = lax.scan(agg, init, xs)
    return tuple(v[:, :, 1] for v in acc2)


def finish(bx, by, bz, signed=False):
    """(24, W, B) folded buckets -> total point ((24,),)*3: the tail of a
    commit whose bases are the points themselves. What a context without
    a window table runs (the unsigned small-window path under 256 points,
    a table over `_TABLE_BYTES_BUDGET`, the fused Pallas scan), and the
    oracle `finish_preweighted` is tested against.

    Three phases, all static-shape scans with NO gather/scatter ops (this
    XLA:CPU build expands scatters into per-index buffer updates, which
    made an indexed-machine variant of this tail pathologically slow):

      1. running-sum bucket aggregation with the W windows as lanes
         (`_running_sum`): B + 1 steps (signed) of a (24, W, 2) add.
      2+3. window weighting and final sum in ONE scan of (shift, mask)
         steps on (24, W): `shift=0` steps double the masked windows
         (acc_w ends as 2^(c*w) * A_w), `shift=h` steps add acc[w+h] into
         acc[w] for w < h (pairwise tree); the total lands in lane 0.
         c * (W - 1) + log2(W) steps: 258 at c=7, 253 at c=8, four fifths
         of the tail — what the window table takes away.

    Points are PROJECTIVE with complete adds throughout, so the shift=0
    "doubling" steps and every identity lane need no special handling at
    all. signed=True: planes come from _bucket_scan_signed — B = 2^(c-1)
    columns where column i weighs (i+1), so phase 1 scans ALL columns
    (reversed) instead of dropping column 0.
    """
    wins, buckets = bz.shape[1], bz.shape[2]
    c = -(-SCALAR_BITS // wins)  # ceil: c=7 gives 37 windows (not 256/37=6)
    assert buckets == (1 << (c - 1) if signed else 1 << c), (wins, buckets)
    add = CJ.proj_add
    acc = _running_sum(bx, by, bz, signed)  # (24, W)

    # phase 2+3: doubling ladder + pairwise tree, one (shift, mask) scan
    steps = []
    for k in range(c * (wins - 1)):
        steps.append((0, [k < c * w for w in range(wins)]))
    # pairwise tree over a possibly NON-power-of-two window count (37 at
    # c=7): fold acc[w+h] into acc[w] only where w+h < wins — the roll's
    # wrap-around lanes are masked off
    h = 1 << max(0, (wins - 1).bit_length() - 1)
    while h >= 1:
        steps.append((h, [w < h and w + h < wins for w in range(wins)]))
        h //= 2
    shifts = jnp.asarray(np.array([s for s, _ in steps], dtype=np.int32))
    masks = jnp.asarray(np.array([m for _, m in steps]))

    def weight(carry, step):
        shift, mask = step
        rolled = tuple(jnp.roll(v, -shift, axis=1) for v in carry)
        summed = add(carry, rolled)
        return tuple(jnp.where(mask[None, :], s, v)
                     for s, v in zip(summed, carry)), None

    acc, _ = lax.scan(weight, acc, (shifts, masks))
    return tuple(v[:, 0] for v in acc)


def finish_preweighted(bx, by, bz):
    """((24, B, buckets),)*3 signed planes of B polynomials, their windows
    already added (`sum_windows`) -> ((24, B),)*3 totals: the running sum
    over the bucket columns with the polynomials as lanes, buckets + 1
    steps, and nothing after it. The 2^(c*w) that `finish`'s ladder
    applies is in the bases (`window_table`)."""
    return _running_sum(bx, by, bz, signed=True)


_WINDOW_LANES = 8


def sum_windows(bx, by, bz, batch):
    """((24, B*W, buckets),)*3 planes accumulated over a window table ->
    ((24, B, buckets),)*3: each polynomial's W window planes added, which
    the table's equal weights allow. Two scans, one add body each: the
    windows fold eight abreast (`fold_planes` over ceil(W / 8) slices,
    identity planes filling the last), then three cyclic roll-adds
    (4, 2, 1) leave the total on every one of the eight lanes. 8 steps at
    W = 37, 7 at 32, on a fifth of the lanes a masked tree over all W
    would add at every level."""
    wins, buckets = bx.shape[1] // batch, bx.shape[2]
    k = -(-wins // _WINDOW_LANES)
    vz = bz.ravel()[0] & 0  # varying-zero, see _bucket_scan

    def slices(a, inf):  # (24, B*W, buckets) -> (k, 24, B*8, buckets)
        a = a.reshape(FQ_LIMBS, batch, wins, buckets)
        a = jnp.concatenate([a, inf + vz], axis=2)
        return a.reshape(FQ_LIMBS, batch, k, _WINDOW_LANES, buckets) \
            .transpose(2, 0, 1, 3, 4) \
            .reshape(k, FQ_LIMBS, batch * _WINDOW_LANES, buckets)

    inf = CJ.proj_inf((batch, k * _WINDOW_LANES - wins, buckets))
    acc = fold_planes(*(slices(a, i) for a, i in zip((bx, by, bz), inf)))
    acc = tuple(a.reshape(FQ_LIMBS, batch, _WINDOW_LANES, buckets)
                for a in acc)

    def roll_add(acc, shift):
        return CJ.proj_add(
            acc, tuple(jnp.roll(a, shift, axis=2) for a in acc)), None

    acc, _ = lax.scan(roll_add, acc, jnp.asarray([4, 2, 1], jnp.int32))
    return tuple(a[:, :, 0] for a in acc)


def bucket_planes_batch(ax, ay, ainf, digits, group, kernel=None):
    """B-polynomial bucket accumulation over SHARED bases: affine points
    (24, nc) + inf mask (nc,) + digits (B, W, nc) -> folded planes
    ((24, B*W, 2^c),)*3.

    The prover's per-round commitment batches (5 wires, 5 quotient splits,
    2 openings — the join_all fan-outs of reference dispatcher2.rs:316-321,
    526-533) share every scan step, so fixed per-step latency is paid once
    per round instead of once per polynomial."""
    B, W, n = digits.shape
    buckets = 1 << (SCALAR_BITS // W)
    flat = digits.reshape(B * W, n)
    wb = _bucket_scan(ax, ay, ainf, flat, group, buckets, kernel=kernel)
    planes = tuple(x.transpose(1, 0, 2, 3) for x in wb)  # (G, 24, B*W, buckets)
    return fold_planes(*planes)


def bucket_planes_batch_signed(ax, ay, ainf, packed, group, kernel=None,
                               preweighted=False):
    """Signed-digit analog of bucket_planes_batch: affine bases (24, nc) +
    inf mask (nc,) + packed digits (B, W, nc) -> ((24, B*W, 2^(c-1)),)*3.
    The window count W determines c (32 -> c=8, 37 -> c=7).

    preweighted=True: ax/ay are nc rows of a window table
    (nc/8, 8, 24*W); after the group fold the W window planes of each
    polynomial are added -> ((24, B, 2^(c-1)),)*3."""
    B, W, n = packed.shape
    c = -(-SCALAR_BITS // W)
    flat = packed.reshape(B * W, n)
    wb = _bucket_scan_signed(ax, ay, ainf, flat, group,
                             n_buckets=1 << (c - 1), kernel=kernel,
                             preweighted=preweighted)
    planes = tuple(x.transpose(1, 0, 2, 3) for x in wb)
    acc = fold_planes(*planes)
    return sum_windows(*acc, batch=B) if preweighted else acc


def finish_batch(acc_x, acc_y, acc_z, batch, signed=False):
    """((24, B*W, buckets),)*3 folded planes -> ((24, B),)*3 totals."""
    acc_b = tuple(a.reshape(FQ_LIMBS, batch, a.shape[1] // batch, a.shape[2])
                  for a in (acc_x, acc_y, acc_z))
    return jax.vmap(partial(finish, signed=signed),
                    in_axes=(1, 1, 1), out_axes=1)(*acc_b)


def msm_pipeline_batch(ax, ay, ainf, digits, group):
    """One-shot batched MSM (small inputs / tests): bucket accumulation +
    finish in a single program."""
    acc = bucket_planes_batch(ax, ay, ainf, digits, group)
    return finish_batch(*acc, batch=digits.shape[0])


def _canon_padded(v, padded_n):
    """(16, L) Montgomery coefficients -> (16, padded_n) canonical limbs
    (the shared device prologue of every digit-extraction path)."""
    canon = FJ.from_mont(FR, v)
    if canon.shape[1] < padded_n:
        canon = jnp.pad(canon, ((0, 0), (0, padded_n - canon.shape[1])))
    return canon


def digits_from_mont(v, c, padded_n):
    """(16, L) Montgomery Fr coefficients -> (256/c, padded_n) uint32
    digits, entirely on device (no host round-trip before a commitment)."""
    canon = _canon_padded(v, padded_n)
    per_limb = 16 // c
    mask = (1 << c) - 1
    parts = [(canon >> (c * i)) & mask for i in range(per_limb)]
    return jnp.stack(parts, axis=1).reshape(SCALAR_BITS // c, padded_n)


def digits_of_scalars(scalars, padded_n, c):
    """Host int scalars -> (256/c, padded_n) uint32 radix-2^c digits.

    c must divide 16 so every window lives inside one 16-bit limb."""
    assert 16 % c == 0
    scalars = [s % R_MOD for s in scalars]
    scalars += [0] * (padded_n - len(scalars))
    limbs = ints_to_limbs(scalars, FR_LIMBS)  # (16, n)
    per_limb = 16 // c
    mask = (1 << c) - 1
    parts = [(limbs >> (c * i)) & mask for i in range(per_limb)]
    # window order: limb0's sub-digits (low->high), then limb1's, ...
    digits = np.stack(parts, axis=1).astype(np.uint32)
    return digits.reshape(SCALAR_BITS // c, padded_n)


# NOTE on signed-digit safety: recoding carries can only overflow the top
# window if a scalar's top window digit can reach the sign threshold; Fr
# scalars are canonical (< r < 2^255), so at c=8 the top radix-256 digit
# is <= 0x73 and at c=7 the top (bits 252..258) window is <= 7 — the
# final carry is always 0 at BOTH widths. Tiny keys (< 256 points) keep
# the unsigned small-window path for plane-tile reasons, not safety.

def _signed_recode(u, bias, xp):
    """Windowed unsigned digits -> packed signed digits (d + bias, d in
    [-bias, bias-1]): the ONE carry loop shared by the host (xp=numpy)
    and device (xp=jax.numpy) recodes at both window widths (bias 128
    for c=8, 64 for c=7).

    The wrap is a MASK, not `t + bias - (carry << shift)`: with t <
    2*bias + 1 the two are identical ((t + bias) mod 2*bias), but the
    subtraction's uint32 interval dips below zero unless the verifier
    knows carry == (t >= bias) — a correlation interval analysis cannot
    see (analysis/bounds.py flagged it); the masked form is provably
    in-range for any t the digit bound admits."""
    outs = []
    carry = xp.zeros_like(u[0])
    for w in range(u.shape[0]):
        t = u[w] + carry
        carry = (t >= bias).astype(xp.uint32)
        outs.append((t + bias) & (2 * bias - 1))
    return outs, carry


def _signed_recode_np(u, bias=128):
    outs, carry = _signed_recode(u, bias, np)
    assert not np.asarray(carry).any(), "signed recode overflow (>= r?)"
    return np.stack(outs)


def signed_digits_of_scalars(scalars, padded_n):
    """Host int scalars -> (32, padded_n) packed signed radix-256 digits."""
    return _signed_recode_np(digits_of_scalars(scalars, padded_n, 8))


def signed_digits_from_mont(v, padded_n):
    """(16, L) Montgomery Fr coefficients -> (32, padded_n) packed signed
    radix-256 digits, entirely on device (32-step static recode loop)."""
    outs, _ = _signed_recode(digits_from_mont(v, 8, padded_n), 128, jnp)
    return jnp.stack(outs)


# --- c = 7 windows (37 windows x 64 buckets) ---------------------------------
# Halves the bucket-plane bytes/traffic vs c=8 for +16% window-adds
# (roadmap #2). 7 does not divide 16, so each window may straddle a limb
# boundary: window k covers bits [7k, 7k+7), i.e. limb (7k)>>4 shifted by
# (7k)&15, OR'd with the next limb's low bits when the window crosses.
# Signed safety at c=7: scalars are canonical (< r < 2^255), so the top
# window (bits 252..258) is <= 7; recode carries add <= 1 — never >= 64.

W7 = 37  # ceil(256 / 7)


def _digits7_rows(limbs, stack):
    """(16, n) canonical 16-bit limbs -> 37 rows of 7-bit digits (u32)."""
    rows = []
    for k in range(W7):
        bit = 7 * k
        i, off = bit >> 4, bit & 15
        lo = limbs[i] >> off
        if off > 9 and i + 1 < FR_LIMBS:  # window crosses into limb i+1
            lo = lo | (limbs[i + 1] << (16 - off))
        rows.append(lo & 127)
    return stack(rows)


def signed_digits7_of_scalars(scalars, padded_n):
    """Host int scalars -> (37, padded_n) packed signed base-128 digits
    (d + 64, d in [-64, 63])."""
    scalars = [s % R_MOD for s in scalars]
    scalars += [0] * (padded_n - len(scalars))
    u = _digits7_rows(ints_to_limbs(scalars, FR_LIMBS), np.stack)
    return _signed_recode_np(u, bias=64)


def signed_digits7_from_mont(v, padded_n):
    """(16, L) Montgomery Fr coefficients -> (37, padded_n) packed signed
    base-128 digits, entirely on device."""
    canon = _canon_padded(v, padded_n)
    outs, _ = _signed_recode(_digits7_rows(canon, jnp.stack), 64, jnp)
    return jnp.stack(outs)


def points_to_device(bases_affine, pad):
    """list[(x, y) | None] + pad count -> affine Montgomery limb arrays
    ((24, n+pad) x, (24, n+pad) y, (n+pad,) inf mask), as HOST numpy —
    placement is the caller's call (the mesh context device_puts shards;
    building on the default device first would bounce every base through
    whatever chip owns it, round-2 weakness #1)."""
    xs, ys, infs = [], [], []
    for p in bases_affine:
        if p is None:
            xs.append(0)
            ys.append(0)
            infs.append(True)
        else:
            xs.append(p[0] * FQ_MONT_R % Q_MOD)
            ys.append(p[1] * FQ_MONT_R % Q_MOD)
            infs.append(False)
    xs += [0] * pad
    ys += [0] * pad
    infs += [True] * pad
    x = ints_to_limbs(xs, FQ_LIMBS)
    y = ints_to_limbs(ys, FQ_LIMBS)
    inf = np.array(infs)
    return x, y, inf


# --- window-weighted bases ---------------------------------------------------
# The weight 2^(c*w) that `finish` gives window w AFTER its buckets are
# summed can sit in the bases instead, which are fixed for the life of a
# key: beside P_i a context holds 2^(c*w) * P_i for every window w, lane
# (b, w) of the scan adds window w's copy, and the whole tail of a commit
# is `sum_windows` + `finish_preweighted`: ceil(W / 8) + 3 steps to add a
# polynomial's windows and buckets + 1 for the running sum, where the
# ladder took c * (W - 1) more and ran both on W times the lanes (323
# sequential steps -> 73 at c=7, 382 -> 136 at c=8; on the chip the tail
# of a 5-polynomial commit went from 107 ms to 8, PERF.md sec. 5). The
# price is memory, W * n * 192 bytes: 117 MB
# for the 16,416 points of a 2^14 key at c=7, 1.86 GB at 2^18; a key whose
# table would pass the budget keeps the ladder, under 1% of its commit.
_TABLE_BYTES_BUDGET = 2 << 30


def table_bytes(n, c):
    """Bytes of an n-point window table at window width c."""
    return -(-SCALAR_BITS // c) * n * 2 * 4 * FQ_LIMBS


def use_window_table(signed, kernel, n, c):
    """THE RULE for the pre-weighted tail, on what a context can observe:
    the signed wide-window pipeline (the unsigned one is for keys under
    256 points, where a tail is nothing), the XLA scan (the fused Pallas
    kernel takes one point a step), and a table within the byte budget."""
    return (signed and kernel == "xla"
            and table_bytes(n, c) <= _TABLE_BYTES_BUDGET)


def _next_window(c, x, y, inf):
    """Affine points -> their 2^c multiples, Jacobian: c doublings over all
    lanes (the identity stays the identity, and no other point of prime
    order becomes it)."""
    return lax.fori_loop(0, c, lambda _, p: CJ.jac_double(p),
                         CJ.from_affine(x, y, inf))


_next_window_fn = FJ.named_jit("msm_table_window", _next_window,
                               static_argnums=0)


TABLE_TILE = 8  # rows of a window table in one TPU tile; its keys are a
# whole number of them


def _table_pack(cols):
    """W arrays (24, n) -> (n/8, 8, 24*W): a point's limbs of all its
    windows in one row, the window minor. Rows, not a trailing window
    axis of 37: a TPU tile is 8 x 128 of the two minor dimensions and
    pads what does not fill it, 888 to 896 here and 37 to 128 there. And
    three dimensions, eight rows a tile, because the layout a TPU gives
    two follows their sizes (16,416 x 888 is held column-major, which a
    scan over rows would undo with a copy of the table a call)."""
    t = jnp.stack(cols, axis=2).transpose(1, 0, 2)
    return t.reshape(t.shape[0] // TABLE_TILE, TABLE_TILE, -1)


_table_pack_fn = FJ.named_jit("msm_table_pack", _table_pack)


def window_table(ax, ay, ainf, c):
    """Affine Montgomery points (24, n) + inf mask (n,) -> the window
    table (tx, ty), each (n/8, 8, 24*W) uint32: row i (of the n the two
    leading axes make) holds the 24 limbs of 2^(c*w) * P_i for
    w = 0..W-1 at columns [k*W + w]. Identity columns (the key's padding)
    are zeros in every window and keep the one mask.
    Built on the device, window by window: c doublings of the last
    window's affine points, then one batched inversion (`batch_to_affine`:
    one field element a window crosses to the host), so nothing larger
    than the table is ever held."""
    xs, ys = [jnp.asarray(ax)], [jnp.asarray(ay)]
    ainf = jnp.asarray(ainf)
    for _ in range(-(-SCALAR_BITS // c) - 1):
        x, y, _inf = CJ.batch_to_affine(
            _next_window_fn(c, xs[-1], ys[-1], ainf))
        xs.append(x)
        ys.append(y)
    return _table_pack_fn(xs), _table_pack_fn(ys)


class DeviceCommitKey:
    """A commit key that lives on device as Jacobian Montgomery limb arrays
    (e.g. straight out of the fixed-base SRS generator) — no host affine
    normalization on the prover path. Identity padding columns (z == 0) are
    part of the key, mirroring the affine path's None-padded ck list."""

    def __init__(self, px, py, pz):
        assert px.shape == py.shape == pz.shape == (FQ_LIMBS, px.shape[1])
        self.point = (px, py, pz)

    def __len__(self):
        return self.point[0].shape[1]


class MsmContext:
    """Device-resident base set (the SRS chunk a worker holds,
    reference src/worker.rs:42-48). Reused across commitments.

    What it holds on the device: the affine points `point` (x, y (24, n),
    inf mask (n,): 192 B a point) and, where `use_window_table` says so,
    their window table `table` (tx, ty (n/8, 8, 24*W): W times as much,
    117 MB at the 16,416 points of a 2^14 key), built once here. A commit
    served from the table ends in `finish_preweighted`; without one, in
    `finish`.

    count(name, by): where `msm_commit_polys`,
    `msm_commit_polys_preweighted`, `msm_commit_calls` (one a
    `_exec_chunked`) and `msm_commit_chunks` (the bucket-scan device calls
    it made) go (JaxBackend._count), if anywhere."""

    def __init__(self, bases, count=None):
        self._count = count or (lambda name, by=1: None)
        n = len(bases)
        self.n = n
        pad = n % 2  # groups need >= 2 scan steps
        if n + pad >= 256:  # a wide-window key: whole tiles of a table
            pad = (-n) % TABLE_TILE
        self.padded_n = n + pad
        self.c = window_bits(self.padded_n)
        # batched pipelines use wide SIGNED windows once the key is big
        # enough: DPT_MSM_C picks 8 (32 windows x 128 buckets, planes
        # exactly fill (8, 128) minor tiles) or 7 (37 x 64 — half the
        # plane traffic per step at +16% window-adds; A/B'd on chip,
        # msm_c7_ab_r05.json). Tiny keys keep the unsigned small-window
        # scan (a 16-bucket c=4 plane is layout-padded 8x otherwise).
        self.c_batch = self._C_BATCH if self.padded_n >= 256 else self.c
        # wide windows run the SIGNED pipeline (half the buckets, sign
        # folded into y); both pipelines take affine bases + inf mask and
        # accumulate with complete projective adds
        self.signed = self.c_batch in (7, 8)
        if isinstance(bases, DeviceCommitKey):
            point = bases.point
            if pad:
                point = tuple(jnp.pad(p, ((0, 0), (0, pad))) for p in point)
            # device-built SRS is Jacobian with arbitrary Z: normalize
            # once with a batched inversion (one scalar host round-trip)
            self.point = CJ.batch_to_affine(point)
        else:
            # place once at context build: leaving host numpy here would
            # re-upload the whole sliced key on every _exec_chunked call
            self.point = tuple(jax.device_put(p)
                               for p in points_to_device(bases, pad))
        self.table = (window_table(*self.point, self.c_batch)
                      if use_window_table(self.signed, self._mode(),
                                          self.padded_n, self.c_batch)
                      else None)
        # every program of the commit pipeline has a name of its own in
        # the device trace (field_jax.named_jit): msm_digits,
        # msm_digits_many, msm_bucket_scan, msm_merge, msm_finish (and, at
        # the build above, msm_table_window, msm_table_pack)
        if self.c_batch == 7:
            digits = partial(signed_digits7_from_mont,
                             padded_n=self.padded_n)
        elif self.signed:
            digits = partial(signed_digits_from_mont, padded_n=self.padded_n)
        else:
            digits = partial(digits_from_mont, c=self.c_batch,
                             padded_n=self.padded_n)
        self._digits_batch_fn = FJ.named_jit("msm_digits", digits)
        # stacked digit extraction (the cross-job commit_batch path): one
        # vmapped launch turns B same-width coefficient handles into the
        # (B, W, padded_n) digit tensor, instead of B separate dispatches.
        # vmap of the same elementwise program — bit-identical digits.
        self._digits_many_fn = FJ.named_jit(
            "msm_digits_many", jax.vmap(self._digits_batch_fn))
        self._chunk_fns = {}
        self._finish_fns = {}
        self._merge_fn = FJ.named_jit(
            "msm_merge", lambda a, b: CJ.proj_add(tuple(a), tuple(b)))

    # one device execution is kept under a lane-add budget: the runtime of
    # rounds 2-5 killed executions in the ~60 s range ("TPU worker process
    # crashed"), observed for single calls at 2^19 points and above on the
    # round-2 integer kernels; whether today's does is not measured, and
    # the chunking stays until it is. The budget is ONE constant, so a
    # commit's chunk shapes follow from its shape alone: 2^27 lane-adds is
    # about 20 s of the v5e's bucket scan (6.3 M lane-adds/s, PERF.md
    # sec. 5), and every size served today (2^13 to 2^16 points, B <= 8)
    # is one call under it.
    _CALL_ADDS = int(os.environ.get("DPT_MSM_CALL_ADDS", str(1 << 27)))
    # default 7 (37 windows x 64 buckets): chip A/B at 2^20
    # (msm_c7_ab_r05.json) measured 29.8 s vs 31.4 s for c=8 (~5%), same
    # result point, both host-oracle-checked at 2^12
    _C_BATCH = int(os.environ.get("DPT_MSM_C", "7"))
    assert _C_BATCH in C_CHOICES, \
        f"DPT_MSM_C must be 7 or 8, got {_C_BATCH}"

    def _mode(self):
        """Resolved bucket kernel."""
        return _kernel_mode()

    def _preweighted(self):
        """Whether a commit is served from the window table: the table is
        there and the kernel resolved for this call is still the one it
        was built for."""
        return self.table is not None and self._mode() == "xla"

    def _chunk_key(self, nc, group):
        """Chunk-fn/call memo key, resolved mode included — the
        pallas/xla branch is taken at TRACE time inside the jit, so an
        env/attr flip (bench A/B, tests) must not reuse the other
        configuration's executable. (Whether the table serves the call
        follows from the context and that mode.)"""
        return (nc, group, self._mode())

    def _chunk_fn(self, nc, group):
        key = self._chunk_key(nc, group)
        if key not in self._chunk_fns:
            # kernel pinned to the memo key's resolution, so the traced
            # branch cannot diverge from the key
            fn = (partial(bucket_planes_batch_signed,
                          preweighted=self._preweighted())
                  if self.signed else bucket_planes_batch)
            self._chunk_fns[key] = FJ.named_jit(
                "msm_bucket_scan",
                partial(fn, group=group, kernel=self._mode()))
        return self._chunk_fns[key]

    def _finish_fn(self, batch):
        key = (batch, self._preweighted())
        if key not in self._finish_fns:
            self._finish_fns[key] = FJ.named_jit(
                "msm_finish", finish_preweighted if key[1] else
                partial(finish_batch, batch=batch, signed=self.signed))
        return self._finish_fns[key]

    def _chunk_lanes(self, B, W):
        """Points a device call takes of a commit of B polynomials in W
        windows (1024-aligned)."""
        return max(1024, (self._CALL_ADDS // (B * W)) & ~1023)

    def _exec_chunked(self, digits):
        """digits (B, W, padded_n) -> ((24, B),)*3 totals, in as many
        device calls as the per-call budget requires: per-chunk bucket
        accumulation, cheap cross-chunk plane merges, one finish tail."""
        B, W, n = digits.shape
        ainf = self.point[2]
        # the bases of a call: the table's rows (n/8, 8, 24*W), cut by the
        # tile, or the points' columns (24, n)
        if self._preweighted():
            (ax, ay), axis, tile = self.table, 0, TABLE_TILE
            self._count("msm_commit_polys_preweighted", B)
        else:
            (ax, ay), axis, tile = self.point[:2], 1, 1
        self._count("msm_commit_polys", B)
        self._count("msm_commit_calls")

        def cut(a, i0, nc, axis=0, tile=1):
            return a if nc == tile * a.shape[axis] else lax.slice_in_dim(
                a, i0 // tile, (i0 + nc) // tile, axis=axis)

        chunk = self._chunk_lanes(B, W)
        acc = None
        for i0 in range(0, n, chunk):
            nc = min(chunk, n - i0)
            g = _group_size_batch(nc, B, -(-SCALAR_BITS // W),
                                  signed=self.signed, kernel=self._mode())
            part = self._chunk_fn(nc, g)(
                cut(ax, i0, nc, axis, tile), cut(ay, i0, nc, axis, tile),
                cut(ainf, i0, nc), cut(digits, i0, nc, 2))
            self._count("msm_commit_chunks")
            acc = part if acc is None else tuple(self._merge_fn(acc, part))
        return self._finish_fn(B)(*acc)

    def aot_compile(self, batch_sizes=(1,), digit_widths=None):
        """Ahead-of-time `lower().compile()` of the commitment pipeline for
        this key at the given batch widths: on-device digit extraction, the
        per-chunk bucket-accumulation scan, the cross-chunk plane merge,
        and the finish tail — no execution (`JaxBackend.warm_stages` used
        to warm this path by RUNNING one zero-scalar MSM, which baked only
        one shape and cost a real bucket-scan pass). Executables land in
        the persistent compilation cache like the NTT AOT path.

        Chunk/finish/merge shapes are those of a full-width commit's first
        chunk (`_chunk_lanes`). Digit extraction jit-caches per EXACT
        handle width, so `digit_widths` must be the coefficient-handle
        widths the caller will commit
        (`warm_stages` passes the prover's n+2/n+3 blinded widths);
        default: this key's full padded width.

        Pallas paths are covered too: with DPT_MSM_KERNEL resolving to
        pallas, the chunk lowering IS the fused bucket kernel (Mosaic
        compile, the expensive part of its cold start); and when the
        fused multiplier gate (field_jax._use_pallas) would route the
        XLA scan's group products to field_pallas, those multiplier
        executables are pre-lowered at the scan's 5/6-pair stacked lane
        widths — closing the PR 3 "Pallas mul path has no AOT hook"
        remainder.
        Returns {"compiled", "failed", "errors", "shapes", "kernel",
        "mul_path_widths"}; `errors` holds what the compiler said for
        every stage counted in `failed` — a warm-up keeps going past a
        refusal, but never swallows it."""
        compiled = 0
        errors = []
        shapes = []
        u32 = jnp.uint32

        def aot(fn, *specs):
            nonlocal compiled
            try:
                fn.lower(*specs).compile()
                compiled += 1
            except Exception as e:  # noqa: BLE001 - reported, see above
                errors.append(repr(e))

        W = -(-SCALAR_BITS // self.c_batch)
        c = -(-SCALAR_BITS // W)
        buckets = 1 << (c - 1) if self.signed else 1 << c
        if digit_widths is None:
            digit_widths = (self.padded_n,)
        for L in sorted({min(w, self.padded_n) for w in digit_widths}):
            aot(self._digits_batch_fn,
                jax.ShapeDtypeStruct((FR_LIMBS, L), u32))
        mul_widths = set()
        for B in sorted(set(batch_sizes)):
            nc = min(self._chunk_lanes(B, W), self.padded_n)
            g = _group_size_batch(nc, B, c, signed=self.signed,
                                  kernel=self._mode())
            # what a chunk takes of the bases and gives back: the table's
            # rows and planes with the windows added, or the points'
            # columns and a plane a window
            base, lanes = (((nc // TABLE_TILE, TABLE_TILE, FQ_LIMBS * W), B)
                           if self._preweighted()
                           else ((FQ_LIMBS, nc), B * W))
            aot(self._chunk_fn(nc, g),
                jax.ShapeDtypeStruct(base, u32),
                jax.ShapeDtypeStruct(base, u32),
                jax.ShapeDtypeStruct((nc,), jnp.bool_),
                jax.ShapeDtypeStruct((B, W, nc), u32))
            planes = tuple(
                jax.ShapeDtypeStruct((FQ_LIMBS, lanes, buckets), u32)
                for _ in range(3))
            aot(self._finish_fn(B), *planes)
            aot(self._merge_fn, planes, planes)
            shapes.append({"batch": B, "chunk": nc, "group": g,
                           "kernel": self._mode()})
            # the XLA scan's RCB15 add stages its products as 5- and
            # 6-pair stacked-lane mont_muls at g * B * W lanes; collect
            # the padded widths the fused multiplier would compile at
            for pairs in (5, 6):
                lanes = pairs * g * B * W
                if FJ._use_pallas((FQ_LIMBS, lanes)):
                    from . import field_pallas as FP
                    tile = FP.LANE_TILE
                    mul_widths.add((lanes + (-lanes) % tile, tile))
        for Nw, tile in sorted(mul_widths):
            from . import field_pallas as FP
            spec = jax.ShapeDtypeStruct((FQ_LIMBS, Nw), u32)
            aot(FP._mont_mul_flat, "fq", FJ.pallas_interpret(),
                FP._VARIANT, tile, spec, spec)
        return {"compiled": compiled, "failed": len(errors),
                "errors": errors, "shapes": shapes, "kernel": self._mode(),
                "mul_path_widths": sorted(w for w, _ in mul_widths)}

    def msm(self, scalars):
        """Σ scalars_i * bases_i -> affine point (host ints) or None."""
        assert len(scalars) <= self.n
        return self.msm_many([scalars])[0]

    def msm_mont_limbs(self, h):
        """Commit a (16, L <= padded_n) Montgomery Fr coefficient handle:
        digit extraction happens on device; only the resulting group
        element returns to the host (for the transcript)."""
        return self.msm_mont_limbs_many([h])[0]

    # batched launches are chunked: bucket planes and mont_mul transients
    # scale with B, and a fixed chunk width keeps the set of compiled batch
    # shapes small across prover rounds (8, then the 5/2-size residuals)
    _BATCH_CHUNK = int(os.environ.get("DPT_MSM_BATCH", "8"))

    def _run_batches(self, items, make_digits, chunk=None, stacked=False,
                     defer=False):
        """items -> affine points; digits are materialized per batch chunk
        so peak digit memory is `chunk` (default _BATCH_CHUNK) tensors,
        not len(items).

        stacked=True (items are same-width device handles): each chunk's
        digit extraction runs as ONE vmapped launch over the stacked
        handles (`_digits_many_fn`) instead of one dispatch per handle —
        the cross-job commit_batch path, where a placement batch of N
        jobs commits 5N wire polys per round.

        Double-buffered: batch k's (24, B) device totals convert to host
        only AFTER batch k+1's work is enqueued, so the device never sits
        idle behind the host-side decode fence (the totals are tiny; only
        ONE extra batch's queued work is ever outstanding).

        defer=True: ALL launches are still enqueued here, in order — but
        every host-side projective decode moves into the returned
        _MsmPending's force(). This is the async commit path: the
        pipelined prover dispatches a member's round commits, then runs
        another member's host work before forcing."""
        # one entry per batch chunk, in item order; a drain rewrites the
        # entry in place
        parts = []  # ["dev", batch_width, device totals] | ["done", points]
        pending = None  # last parts entry still awaiting decode
        batch_chunk = chunk or self._BATCH_CHUNK

        def drain(part):
            if part[0] == "dev":
                part[:] = ["done", _decode_totals(part[1], part[2])]

        for i in range(0, len(items), batch_chunk):
            part_items = items[i:i + batch_chunk]
            if stacked and len({it.shape for it in part_items}) == 1:
                digits = self._digits_many_fn(jnp.stack(part_items))
            else:
                digits = jnp.stack([make_digits(it) for it in part_items])
            totals = self._exec_chunked(digits)
            if pending is not None and not defer:
                drain(pending)
            pending = ["dev", digits.shape[0], totals]
            parts.append(pending)
        if defer:
            return _MsmPending(parts)
        out = []
        for part in parts:
            drain(part)
            out.extend(part[1])
        return out

    def msm_mont_limbs_many(self, hs, chunk=None):
        """Commit B Montgomery coefficient handles in batched launches;
        returns B affine points (host ints). `chunk` widens/narrows the
        per-launch batch (the cross-job commit path passes the job-batch
        width so one placement batch's same-round commits share launches);
        same-width handles in a chunk get ONE stacked digit-extraction
        launch."""
        for h in hs:
            assert h.shape[1] <= self.n, (h.shape, self.n)
        return self._run_batches(hs, self._digits_batch_fn, chunk=chunk,
                                 stacked=True)

    def msm_mont_limbs_many_async(self, hs, chunk=None):
        """Like msm_mont_limbs_many, but returns an unforced _MsmPending:
        the digit-extraction + bucket-accumulation launches are enqueued
        before returning; the host-side projective decode (the part that
        blocks on the device) runs at pending.force()."""
        for h in hs:
            assert h.shape[1] <= self.n, (h.shape, self.n)
        return self._run_batches(hs, self._digits_batch_fn, chunk=chunk,
                                 stacked=True, defer=True)

    def msm_many(self, scalar_lists):
        """B MSMs over host int scalar lists in batched launches."""
        if self.c_batch == 7:
            make = lambda s: jnp.asarray(
                signed_digits7_of_scalars(s, self.padded_n))
        elif self.signed:
            make = lambda s: jnp.asarray(
                signed_digits_of_scalars(s, self.padded_n))
        else:
            make = lambda s: jnp.asarray(
                digits_of_scalars(s, self.padded_n, self.c_batch))
        return self._run_batches(scalar_lists, make)


def _decode_totals(B, totals):
    """One batch chunk's (24, B) device totals -> B affine host points.
    The np.asarray calls are the device sync point."""
    tx, ty, tz = totals
    tx, ty, tz = np.asarray(tx), np.asarray(ty), np.asarray(tz)
    return [_proj_limbs_to_affine(tx[:, j], ty[:, j], tz[:, j])
            for j in range(B)]


class _MsmPending:
    """Deferred MSM results from _run_batches(defer=True): every launch is
    already enqueued; force() walks the batch parts in item order and
    performs the host-side decodes. Exactly one consumer forces — the prover
    member's host-finalize."""

    __slots__ = ("_parts",)

    def __init__(self, parts):
        self._parts = parts

    def arrays(self):
        """The device totals still to be decoded: their readiness is the
        commit's completion (what the device ledger's watcher blocks on)."""
        return [a for part in self._parts if part[0] == "dev"
                for a in part[2]]

    def force(self):
        out = []
        for part in self._parts:
            if part[0] == "dev":
                part[:] = ["done", _decode_totals(part[1], part[2])]
            out.extend(part[1])
        return out


def _proj_limbs_to_affine(tx, ty, tz):
    """Homogeneous projective (X : Y : Z) Montgomery limbs -> affine host
    ints or None. Every pipeline result (signed, unsigned, mesh) is
    projective; decode is x = X/Z, y = Y/Z."""
    def dec(v):
        return limbs_to_int(np.asarray(v)) * CJ._MONT_R_INV % Q_MOD

    z = dec(tz)
    if z == 0:
        return None
    zi = pow(z, Q_MOD - 2, Q_MOD)
    return (dec(tx) * zi % Q_MOD, dec(ty) * zi % Q_MOD)


def msm(bases_affine, scalars):
    """One-shot MSM (context built and discarded)."""
    return MsmContext(bases_affine).msm(scalars)
