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


# --- window-weighted bases (PR 36) --------------------------------------------
# A context over a signed wide-window key holds 2^(c*w) * P_i for every
# window w beside P_i; its commits end in the bucket running sum alone.

_TABLE_N = 256
_DISTINCT = 15


@pytest.fixture(scope="module")
def table_bases():
    """254 points of 15 distinct ones (duplicates force P == Q inside the
    scan) and two identities, like an SRS's padding."""
    distinct = _rand_points(_DISTINCT)
    return (distinct * 17)[:_TABLE_N - 2] + [None, None]


@pytest.fixture(scope="module")
def table_ctxs(table_bases):
    """{c: (context with the table, context made to keep the ladder)}."""
    mp = pytest.MonkeyPatch()
    out = {}
    try:
        for c in msm_jax.C_CHOICES:
            mp.setattr(msm_jax.MsmContext, "_C_BATCH", c)
            with_table = msm_jax.MsmContext(table_bases)
            mp.setattr(msm_jax, "_TABLE_BYTES_BUDGET", 0)
            ladder = msm_jax.MsmContext(table_bases)
            mp.setattr(msm_jax, "_TABLE_BYTES_BUDGET", 2 << 30)
            assert with_table.table is not None and ladder.table is None
            assert with_table.c_batch == ladder.c_batch == c
            out[c] = (with_table, ladder)
    finally:
        mp.undo()
    return out


def _table_rows(t, wins):
    """A table half (n/8, 8, 24*W) -> [[int of window w] * W] * n, out of
    Montgomery form."""
    import numpy as np
    from distributed_plonk_tpu.constants import Q_MOD
    from distributed_plonk_tpu.backend.limbs import limbs_to_ints

    t = np.asarray(t)
    n = t.shape[0] * t.shape[1]
    limbs = t.reshape(n, 24, wins).transpose(1, 0, 2).reshape(24, n * wins)
    ints = [v * CJ._MONT_R_INV % Q_MOD for v in limbs_to_ints(limbs)]
    return [ints[i * wins:(i + 1) * wins] for i in range(n)]


@pytest.mark.parametrize("c", msm_jax.C_CHOICES)
def test_window_table_is_the_host_oracles_multiples(table_bases, table_ctxs,
                                                    c):
    """T[w][i] = 2^(c*w) * P_i by curve.py's doubling, for every window and
    every point; the padding's identities stay identities (zeros under the
    one mask) in every window."""
    import numpy as np

    ctx = table_ctxs[c][0]
    wins = -(-256 // c)
    assert ctx.padded_n == _TABLE_N
    assert ctx.table[0].shape == (_TABLE_N // 8, 8, 24 * wins)
    assert ctx.table[0].nbytes + ctx.table[1].nbytes \
        == msm_jax.table_bytes(_TABLE_N, c)
    want = {}
    for p in table_bases[:_DISTINCT]:
        col, q = [], p
        for _ in range(wins):
            col.append(q)
            for _ in range(c):
                q = C.g1_add_affine(q, q)
        want[p] = col
    xs, ys = _table_rows(ctx.table[0], wins), _table_rows(ctx.table[1], wins)
    inf = np.asarray(ctx.point[2])
    for i, p in enumerate(table_bases):
        if p is None:
            assert inf[i] and not any(xs[i]) and not any(ys[i])
        else:
            assert not inf[i]
            assert list(zip(xs[i], ys[i])) == want[p], i


def _edge_polys(c, batch, n):
    """`batch` scalar lists over an n-point key: random full-length ones,
    then (as the batch allows) one shorter than the key that carries the
    extreme digits, and one of zeros."""
    half = 1 << (c - 1)
    edge = [half, half - 1, half << c, (half - 1) << c, 0, 1, R_MOD - 1,
            R_MOD - half]
    polys = [[RNG.randrange(R_MOD) for _ in range(n)] for _ in range(batch)]
    if batch >= 2:
        polys[1] = edge + [RNG.randrange(R_MOD) for _ in range(n // 3)]
    if batch >= 3:
        polys[2] = [0] * n
    return polys


def test_extreme_digits_are_in_the_edge_polynomial():
    import numpy as np

    for c, recode in ((7, msm_jax.signed_digits7_of_scalars),
                      (8, msm_jax.signed_digits_of_scalars)):
        half = 1 << (c - 1)
        d = recode(_edge_polys(c, 2, 64)[1], 64).astype(np.int64) - half
        assert d.min() == -half and d.max() == half - 1


@pytest.mark.parametrize("c,batch", [
    (7, 1), (7, 2), (7, 5), (8, 2),
    pytest.param(8, 1, marks=pytest.mark.tier2),
    pytest.param(8, 5, marks=pytest.mark.tier2)])
def test_commit_through_the_table_is_the_oracles_and_the_ladders(
        table_bases, table_ctxs, c, batch):
    """A batched commit served from the table equals the host oracle's MSM
    (what PythonBackend commits with), point for point: zero scalars, the
    extreme digits (-64, 63; -128, 127) and a polynomial shorter than the
    key included. At B = 2, the batch that holds the extreme digits, also
    the ladder path's (every other batch too under DPT_TIER2=1: a batch
    width is a compile of the pipeline for each path, half a minute of
    XLA:CPU each; c = 8 at B = 1 and 5 likewise, and the mesh tests
    commit through a c = 8 table in tier 1)."""
    import os

    with_table, ladder = table_ctxs[c]
    polys = _edge_polys(c, batch, _TABLE_N - 2)
    got = with_table.msm_many(polys)
    assert got == [C.g1_msm(table_bases[:len(s)], s) for s in polys]
    if batch == 2 or os.environ.get("DPT_TIER2") == "1":
        assert got == ladder.msm_many(polys)
    if batch >= 3:
        assert got[2] is None


def _scan_lengths(jaxpr):
    """Lengths of every lax.scan in a jaxpr, nested ones included, and the
    number of while loops (a fori_loop or a scan lowered otherwise)."""
    scans, whiles = [], 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            scans.append(eqn.params["length"])
        whiles += eqn.primitive.name == "while"
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else [v]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    s, w = _scan_lengths(inner)
                    scans += s
                    whiles += w
    return scans, whiles


@pytest.mark.parametrize("c", msm_jax.C_CHOICES)
def test_finish_of_a_table_context_is_the_running_sum_alone(table_ctxs, c):
    """Structure, so that the ladder cannot come back unseen: the
    `msm_finish` a table context runs holds ONE scan, of buckets + 1 steps
    (65 at c=7, 129 at c=8); the ladder context's holds the same scan and
    c * (W - 1) + log2(W) steps more."""
    import jax.numpy as jnp

    with_table, ladder = table_ctxs[c]
    wins, buckets, batch = -(-256 // c), 1 << (c - 1), 2

    def planes(lanes):
        return [jax.ShapeDtypeStruct((24, lanes, buckets), jnp.uint32)] * 3

    fn = with_table._finish_fn(batch)
    assert fn.__name__ == "msm_finish"
    assert _scan_lengths(jax.make_jaxpr(fn)(*planes(batch)).jaxpr) \
        == ([buckets + 1], 0)
    tree = (wins - 1).bit_length()
    assert _scan_lengths(
        jax.make_jaxpr(ladder._finish_fn(batch))(*planes(batch * wins)).jaxpr
    ) == ([buckets + 1, c * (wins - 1) + tree], 0)
    # and the scan program gives the finish one plane a polynomial
    digits = jax.ShapeDtypeStruct((batch, wins, _TABLE_N), jnp.uint32)
    group = msm_jax._group_size_batch(_TABLE_N, batch, c, signed=True)
    out = jax.eval_shape(with_table._chunk_fn(_TABLE_N, group),
                         *with_table.table, with_table.point[2], digits)
    assert [o.shape for o in out] == [(24, batch, buckets)] * 3
