"""Fused Pallas MSM bucket kernel (msm_pallas) vs the XLA scan paths.

The VMEM-resident bucket-accumulation kernel must be BIT-IDENTICAL to
msm_jax's lax.scan cores at the same group width — planes, not just
points — for every registered digit width (signed c=7/c=8, unsigned
c=4), both plane packings, batched lanes, and the prover's blinded
n+2/n+3 handle widths; and the DPT_MSM_KERNEL dispatch must leave the
end-to-end MSM (and proof bytes, test_jax_backend_prove) unchanged.
Interpret mode on CPU (DPT_PALLAS_INTERPRET=1, conftest); on the v5e the
kernel compiles and matches the scan limb for limb, at 387 s of Mosaic
compile per shape (CHANGES.md PR 21), so `auto` never picks it.

Interpret-mode emulation compiles ~30 s per distinct kernel shape, so
the bit-identity checks are tier2 (scripts/ci.sh fast runs them);
tier-1 keeps the dispatch checks and the TPU cross-lowering.
"""

import random

import numpy as np
import jax.numpy as jnp
import pytest

from distributed_plonk_tpu import curve as C
from distributed_plonk_tpu.constants import FR_MONT_R, R_MOD
from distributed_plonk_tpu.backend import field_jax as FJ
from distributed_plonk_tpu.backend import msm_jax as M
from distributed_plonk_tpu.backend import msm_pallas as MP
from distributed_plonk_tpu.backend.limbs import ints_to_limbs

RNG = random.Random(0xB0C8)


@pytest.fixture(scope="module")
def pts16():
    n = 16
    pts = [C.g1_mul(C.G1_GEN, RNG.randrange(1, R_MOD))
           for _ in range(n - 2)] + [None, None]
    ax, ay, ainf = M.points_to_device(pts, 0)
    return pts, jnp.asarray(ax), jnp.asarray(ay), jnp.asarray(ainf)


def _assert_planes_equal(got, ref, what):
    for g, r in zip(got, ref):
        assert np.array_equal(np.asarray(g), np.asarray(r)), what


def _c7_batch_digits():
    scal = [[RNG.randrange(R_MOD) for _ in range(16)] for _ in range(2)]
    return jnp.asarray(np.stack(
        [M.signed_digits7_of_scalars(s, 16) for s in scal]).reshape(74, 16))


@pytest.mark.tier2
def test_signed_c7_batch_bit_identity(pts16, monkeypatch):
    """Signed c=7 (the default batched pipeline), 2-poly batch, G=2:
    the fused kernel's planes are limb-identical to the XLA onehot
    scan. (Each distinct kernel shape costs ~30 s of interpret-mode
    Mosaic emulation compile, so the unpacked/put cross-checks ride the
    slow tier below.)"""
    _, ax, ay, ainf = pts16
    flat = _c7_batch_digits()
    monkeypatch.setattr(M, "_MSM_KERNEL", "xla")
    ref = M._bucket_scan_signed(ax, ay, ainf, flat, 2, n_buckets=64)
    got = MP.bucket_scan_signed(ax, ay, ainf, flat, 2, n_buckets=64)
    _assert_planes_equal(got, ref, "pallas packed c7")


@pytest.mark.slow
def test_signed_c7_unpacked_and_put_identity(pts16, monkeypatch):
    """The unpacked-plane kernel variant and the XLA put-strategy scan
    agree with the onehot reference limb for limb."""
    _, ax, ay, ainf = pts16
    flat = _c7_batch_digits()
    monkeypatch.setattr(M, "_MSM_KERNEL", "xla")
    ref = M._bucket_scan_signed(ax, ay, ainf, flat, 2, n_buckets=64)
    monkeypatch.setattr(M, "_BUCKET_UPDATE", "put")
    monkeypatch.setattr(M, "_PLANE_PACK", False)
    _assert_planes_equal(
        M._bucket_scan_signed(ax, ay, ainf, flat, 2, n_buckets=64), ref,
        "xla put vs onehot")
    got = MP.bucket_scan_signed(ax, ay, ainf, flat, 2, n_buckets=64,
                                packed=False)
    _assert_planes_equal(got, ref, "pallas unpacked c7")


@pytest.mark.tier2
def test_signed_c8_bit_identity(pts16, monkeypatch):
    _, ax, ay, ainf = pts16
    scal = [RNG.randrange(R_MOD) for _ in range(16)]
    flat = jnp.asarray(M.signed_digits_of_scalars(scal, 16))  # (32, 16)
    monkeypatch.setattr(M, "_MSM_KERNEL", "xla")
    ref = M._bucket_scan_signed(ax, ay, ainf, flat, 1, n_buckets=128)
    got = MP.bucket_scan_signed(ax, ay, ainf, flat, 1, n_buckets=128)
    _assert_planes_equal(got, ref, "pallas signed c8")


@pytest.mark.tier2
def test_unsigned_c4_bit_identity(pts16, monkeypatch):
    """Unsigned small-window scan (tiny keys): bucket 0 rows included,
    only infinity columns skipped — exactly like the XLA core."""
    _, ax, ay, ainf = pts16
    scal = [RNG.randrange(R_MOD) for _ in range(16)]
    flat = jnp.asarray(M.digits_of_scalars(scal, 16, 4))  # (64, 16)
    monkeypatch.setattr(M, "_MSM_KERNEL", "xla")
    ref = M._bucket_scan(ax, ay, ainf, flat, 2, 16)
    got = MP.bucket_scan(ax, ay, ainf, flat, 2, 16)
    _assert_planes_equal(got, ref, "pallas unsigned c4")


@pytest.mark.slow
def test_msm_forced_pallas_matches_oracle_and_xla(monkeypatch):
    """End-to-end MsmContext dispatch: DPT_MSM_KERNEL=pallas must give
    the same point as the XLA path and the host oracle (the fold /
    finish tails are shared, so plane identity implies point identity —
    this locks the dispatch plumbing and the pallas group-size cap)."""
    n = 64
    pts = [C.g1_mul(C.G1_GEN, RNG.randrange(1, R_MOD))
           for _ in range(16)] * (n // 16)
    ks = [RNG.randrange(R_MOD) for _ in range(n)]
    monkeypatch.setattr(M, "_MSM_KERNEL", "xla")
    want = M.msm(pts, ks)
    assert want == C.g1_msm(pts, ks)
    monkeypatch.setattr(M, "_MSM_KERNEL", "pallas")
    assert M.msm(pts, ks) == want


@pytest.mark.slow
def test_blinded_handle_widths(monkeypatch):
    """Montgomery coefficient handles at the prover's blinded n+2/n+3
    widths (narrower than the key) commit to the same points under both
    kernels — the digit-extraction width is part of the jit key, so the
    widths must be exercised, not assumed."""
    dom = 32
    pts = [C.g1_mul(C.G1_GEN, RNG.randrange(1, R_MOD))
           for _ in range(dom + 8)]
    handles = []
    for L in (dom + 2, dom + 3):
        vals = [RNG.randrange(R_MOD) for _ in range(L)]
        handles.append(jnp.asarray(
            ints_to_limbs([v * FR_MONT_R % R_MOD for v in vals], 16)))
    monkeypatch.setattr(M, "_MSM_KERNEL", "xla")
    want = M.MsmContext(pts).msm_mont_limbs_many(handles)
    monkeypatch.setattr(M, "_MSM_KERNEL", "pallas")
    got = M.MsmContext(pts).msm_mont_limbs_many(handles)
    assert got == want


@pytest.mark.tier2
def test_aot_compile_pallas_kernel_and_mul_path(monkeypatch):
    """MsmContext.aot_compile under DPT_MSM_KERNEL=pallas lowers the
    fused bucket kernel (the Mosaic compile is the cold-start cost the
    warmup exists to hide) and, with the fused multiplier gate on,
    pre-lowers field_pallas at the XLA scan's group-product widths —
    the PR 3 'Pallas mul path has no AOT hook' remainder. The context
    must still commit correctly afterwards."""
    monkeypatch.setattr(M, "_MSM_KERNEL", "pallas")
    monkeypatch.setattr(FJ, "_MUL_MODE", "pallas")
    monkeypatch.setattr(FJ, "_PALLAS_MIN_LANES", 1)
    n = 64
    pts = [C.g1_mul(C.G1_GEN, RNG.randrange(1, R_MOD))
           for _ in range(16)] * (n // 16)
    ctx = M.MsmContext(pts)
    rep = ctx.aot_compile(batch_sizes=(1,),
                          digit_widths=(n + 2, n + 3))
    assert rep["failed"] == 0, rep
    assert rep["kernel"] == "pallas"
    assert rep["shapes"][0]["kernel"] == "pallas"
    assert rep["mul_path_widths"], rep
    monkeypatch.setattr(FJ, "_MUL_MODE", "auto")
    ks = [RNG.randrange(R_MOD) for _ in range(n)]
    assert ctx.msm(ks) == C.g1_msm(pts, ks)


def test_bucket_kernel_lowers_for_tpu(monkeypatch):
    """Asked for by name, the fused bucket kernel gets through the
    Pallas->Mosaic lowering at the 2^13 prove's commit shape (8,224-point
    key, 5-polynomial batch, c = 7): jax.export cross-lowers it for TPU
    here on the CPU. The three refusals PR 21 met (a 1-row op-word block,
    an unsigned reduction, lane jnp.repeat / strided pack) stay fixed.
    What the Mosaic COMPILER says about the result only a chip run shows,
    which is why `auto` does not pick this kernel."""
    import jax
    from jax import export

    monkeypatch.setenv("DPT_PALLAS_INTERPRET", "0")
    n, batch = 8224, 5
    group = M._group_size_batch(n, batch, 7, signed=True, kernel="pallas")
    u32 = jnp.uint32
    exp = export.export(
        jax.jit(lambda ax, ay, ainf, d: MP.bucket_scan_signed(
            ax, ay, ainf, d, group, n_buckets=64)),
        platforms=["tpu"])(
        jax.ShapeDtypeStruct((24, n), u32), jax.ShapeDtypeStruct((24, n), u32),
        jax.ShapeDtypeStruct((n,), jnp.bool_),
        jax.ShapeDtypeStruct((batch * M.W7, n), u32))
    assert "tpu_custom_call" in exp.mlir_module()
