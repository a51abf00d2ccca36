"""Device G1 kernels + MSM vs the curve.py oracle."""

import random

import jax
import pytest

from distributed_plonk_tpu import curve as C
from distributed_plonk_tpu.constants import R_MOD
from distributed_plonk_tpu.backend import curve_jax as CJ
from distributed_plonk_tpu.backend import msm_jax

RNG = random.Random(0xC0FFEE)


def _rand_points(n):
    return [C.g1_mul(C.G1_GEN, RNG.randrange(1, R_MOD)) for _ in range(n)]


def test_jac_add_double_random():
    n = 16
    ps = _rand_points(n)
    qs = _rand_points(n)
    dev_p = CJ.affine_to_device(ps)
    dev_q = CJ.affine_to_device(qs)
    add_fn = jax.jit(CJ.jac_add)
    dbl_fn = jax.jit(CJ.jac_double)
    got_add = CJ.device_to_affine(add_fn(dev_p, dev_q))
    got_dbl = CJ.device_to_affine(dbl_fn(dev_p))
    assert got_add == [C.g1_add_affine(p, q) for p, q in zip(ps, qs)]
    assert got_dbl == [C.g1_add_affine(p, p) for p in ps]


def test_jac_add_edge_cases():
    p = _rand_points(1)[0]
    q = _rand_points(1)[0]
    lhs = [p, p, p, None, None, p]
    rhs = [p, C.g1_neg(p), None, p, None, q]
    dev_l = CJ.affine_to_device(lhs)
    dev_r = CJ.affine_to_device(rhs)
    got = CJ.device_to_affine(jax.jit(CJ.jac_add)(dev_l, dev_r))
    assert got == [C.g1_add_affine(a, b) for a, b in zip(lhs, rhs)]


@pytest.mark.parametrize("n", [64])
def test_msm_matches_oracle(n):
    bases = _rand_points(n - 2) + [None, None]  # infinity padding like the SRS
    scalars = ([RNG.randrange(R_MOD) for _ in range(n - 4)]
               + [0, 1, R_MOD - 1, RNG.randrange(R_MOD)])
    got = msm_jax.msm(bases, scalars)
    assert got == C.g1_msm(bases, scalars)


@pytest.mark.tier2
def test_msm_short_scalars_and_reuse():
    bases = _rand_points(32)
    ctx = msm_jax.MsmContext(bases)
    s1 = [RNG.randrange(R_MOD) for _ in range(20)]  # shorter than bases
    s2 = [RNG.randrange(R_MOD) for _ in range(32)]
    assert ctx.msm(s1) == C.g1_msm(bases[:20], s1)
    assert ctx.msm(s2) == C.g1_msm(bases, s2)


@pytest.mark.tier2
def test_msm_aot_compile_then_correct():
    """warm_stages' true AOT path: lower().compile() every pipeline stage
    without executing anything — digit extraction at the COMMIT-handle
    widths (it jit-caches per exact width; warm_stages passes n+2/n+3),
    then verify a real Montgomery-handle commit and a scalar MSM still
    match the oracle."""
    import jax.numpy as jnp
    from distributed_plonk_tpu.backend.limbs import ints_to_limbs
    from distributed_plonk_tpu.constants import FR_MONT_R

    bases = _rand_points(32)
    ctx = msm_jax.MsmContext(bases)
    report = ctx.aot_compile(batch_sizes=(1, 2), digit_widths=(20, 32))
    # 2x digit extraction + 2x (chunk scan, finish, merge)
    assert report["compiled"] == 8 and report["failed"] == 0, report
    assert [s["batch"] for s in report["shapes"]] == [1, 2]
    scalars = [RNG.randrange(R_MOD) for _ in range(32)]
    assert ctx.msm(scalars) == C.g1_msm(bases, scalars)
    h = jnp.asarray(ints_to_limbs(
        [s * FR_MONT_R % R_MOD for s in scalars[:20]], 16))  # warmed width
    assert ctx.msm_mont_limbs(h) == C.g1_msm(bases[:20], scalars[:20])


def _proj_to_affine_list(p3):
    """Per-column decode via the production converter (no re-implementation
    of the Montgomery/Z-inversion logic)."""
    import numpy as np

    tx, ty, tz = (np.asarray(c) for c in p3)
    return [msm_jax._proj_limbs_to_affine(tx[:, j], ty[:, j], tz[:, j])
            for j in range(tx.shape[1])]


def _affine_to_proj(points):
    """list[(x, y) | None] -> projective device tuple ((24, n),)*3 with
    identity = (0 : 1 : 0)."""
    import jax.numpy as jnp
    from distributed_plonk_tpu.constants import Q_MOD, FQ_MONT_R
    from distributed_plonk_tpu.backend.limbs import ints_to_limbs

    xs = [(p[0] * FQ_MONT_R % Q_MOD) if p else 0 for p in points]
    ys = [(p[1] * FQ_MONT_R % Q_MOD) if p else FQ_MONT_R % Q_MOD
          for p in points]
    zs = [FQ_MONT_R % Q_MOD if p else 0 for p in points]
    return tuple(jnp.asarray(ints_to_limbs(v, 24)) for v in (xs, ys, zs))


def test_proj_complete_add_matches_oracle():
    """RCB15 complete adds (the signed bucket pipeline's group ops) vs the
    oracle, covering the cases a complete formula must absorb with no
    special handling: P+Q, P+P, P+(-P), identity on either/both sides."""
    import jax.numpy as jnp

    p = _rand_points(1)[0]
    q = _rand_points(1)[0]
    lhs = [p, p, p, None, None, p, q]
    rhs = [p, C.g1_neg(p), None, p, None, q, p]
    want = [C.g1_add_affine(a, b) for a, b in zip(lhs, rhs)]

    got = _proj_to_affine_list(jax.jit(CJ.proj_add)(
        _affine_to_proj(lhs), _affine_to_proj(rhs)))
    assert got == want

    # mixed variant: q affine + inf mask (q = None lanes masked)
    x, y, inf = msm_jax.points_to_device(rhs, 0)
    got_m = _proj_to_affine_list(jax.jit(CJ.proj_add_mixed)(
        _affine_to_proj(lhs), (jnp.asarray(x), jnp.asarray(y)),
        jnp.asarray(inf)))
    assert got_m == want


def test_batch_to_affine_roundtrip():
    """Jacobian points with arbitrary Z (like a fixed-base SRS) normalize
    back to their affine coordinates, infinity columns preserved."""
    import numpy as np
    import jax.numpy as jnp
    from distributed_plonk_tpu.constants import Q_MOD, FQ_MONT_R
    from distributed_plonk_tpu.backend.limbs import ints_to_limbs, limbs_to_ints

    pts = _rand_points(6) + [None, None]
    zs = [RNG.randrange(2, Q_MOD) for _ in range(len(pts))]
    X, Y, Z = [], [], []
    for pt, z in zip(pts, zs):
        if pt is None:
            X.append(0); Y.append(0); Z.append(0)
        else:
            X.append(pt[0] * z * z % Q_MOD)
            Y.append(pt[1] * z * z * z % Q_MOD)
            Z.append(z)
    to_mont = lambda vs: ints_to_limbs([v * FQ_MONT_R % Q_MOD for v in vs], 24)
    jac = tuple(jnp.asarray(to_mont(v)) for v in (X, Y, Z))
    ax, ay, inf = CJ.batch_to_affine(jac)
    inv_r = pow(FQ_MONT_R, Q_MOD - 2, Q_MOD)
    ax_i = [v * inv_r % Q_MOD for v in limbs_to_ints(np.asarray(ax))]
    ay_i = [v * inv_r % Q_MOD for v in limbs_to_ints(np.asarray(ay))]
    for k, pt in enumerate(pts):
        if pt is None:
            assert bool(np.asarray(inf)[k])
        else:
            assert not bool(np.asarray(inf)[k])
            assert (ax_i[k], ay_i[k]) == pt, k


@pytest.mark.tier2
def test_msm_signed_path_matches_oracle(monkeypatch):
    """The c=8 signed pipeline (32x128) must keep oracle coverage even
    though the single-chip default is now c=7 — the mesh context
    (msm_mesh.py) still runs c=8 unconditionally. Duplicate bases force
    the P==Q fallback inside the scan, and the edge scalars cover digit
    0 / +-max recodings."""
    monkeypatch.setattr(msm_jax.MsmContext, "_C_BATCH", 8)
    n = 256
    distinct = _rand_points(30)
    bases = (distinct * 9)[:n - 2] + [None, None]
    scalars = ([RNG.randrange(R_MOD) for _ in range(n - 4)]
               + [0, 1, R_MOD - 1, 128])
    ctx = msm_jax.MsmContext(bases)
    assert ctx.signed and ctx.c_batch == 8
    assert ctx.msm(scalars) == C.g1_msm(bases, scalars)


def test_signed_recode_roundtrip():
    """Packed signed digits reconstruct the scalar exactly."""
    import numpy as np

    for s in [0, 1, 127, 128, 255, 256, R_MOD - 1,
              RNG.randrange(R_MOD), RNG.randrange(R_MOD)]:
        packed = msm_jax.signed_digits_of_scalars([s], 1)
        digits = packed.astype(np.int64)[:, 0] - 128
        assert sum(int(d) << (8 * w) for w, d in enumerate(digits)) == s
        assert (np.abs(digits) <= 128).all()


def test_signed7_recode_roundtrip():
    """c=7 packed signed digits (37 windows, bias 64, limb-straddling
    extraction) reconstruct the scalar exactly."""
    import numpy as np

    for s in [0, 1, 63, 64, 127, 128, (1 << 254) + 12345, R_MOD - 1,
              RNG.randrange(R_MOD), RNG.randrange(R_MOD)]:
        packed = msm_jax.signed_digits7_of_scalars([s], 1)
        assert packed.shape == (msm_jax.W7, 1)
        digits = packed.astype(np.int64)[:, 0] - 64
        assert sum(int(d) << (7 * w) for w, d in enumerate(digits)) == s
        assert (np.abs(digits) <= 64).all()


def test_msm_c7_matches_oracle(monkeypatch):
    """DPT_MSM_C=7 engages the 37x64 signed pipeline end to end (digit
    extraction across limb boundaries, 64-bucket planes, ceil-window
    finish with the non-power-of-two pairwise tree)."""
    monkeypatch.setattr(msm_jax.MsmContext, "_C_BATCH", 7)
    n = 256
    distinct = _rand_points(30)
    bases = (distinct * 9)[:n - 2] + [None, None]
    scalars = ([RNG.randrange(R_MOD) for _ in range(n - 4)]
               + [0, 1, R_MOD - 1, 64])
    ctx = msm_jax.MsmContext(bases)
    assert ctx.c_batch == 7 and ctx.signed
    assert ctx.msm(scalars) == C.g1_msm(bases, scalars)
    # device digit extraction agrees with the host recode
    import numpy as np
    import jax.numpy as jnp
    from distributed_plonk_tpu.constants import FR_MONT_R
    from distributed_plonk_tpu.backend.limbs import ints_to_limbs

    h = jnp.asarray(ints_to_limbs(
        [s * FR_MONT_R % R_MOD for s in scalars], 16))
    dev = np.asarray(msm_jax.signed_digits7_from_mont(h, ctx.padded_n))
    host = msm_jax.signed_digits7_of_scalars(scalars, ctx.padded_n)
    assert np.array_equal(dev, host)
