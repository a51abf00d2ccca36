"""Sharded NTT + MSM on the 8-device virtual CPU mesh vs the host oracles.

The mesh analog of the reference's distributed integration tests
(`test_fft` /root/reference/src/dispatcher.rs:246-350 — all 8 flag combos
against ark-poly — and `test_msm` src/dispatcher.rs:177-244), but run on an
in-process device mesh instead of a live 2-host cluster (SURVEY.md §4's
"missing piece" the rebuild adds).
"""

import random

import jax
import pytest

from distributed_plonk_tpu import poly as P
from distributed_plonk_tpu import curve as C
from distributed_plonk_tpu.constants import R_MOD
from distributed_plonk_tpu.parallel.mesh import make_mesh
from distributed_plonk_tpu.parallel.ntt_mesh import MeshNttPlan
from distributed_plonk_tpu.parallel.msm_mesh import MeshMsmContext

RNG = random.Random(0x8E5)


def _oracle(domain, values, inverse, coset):
    if inverse and coset:
        return P.coset_ifft(domain, values)
    if inverse:
        return P.ifft(domain, values)
    if coset:
        return P.coset_fft(domain, values)
    return P.fft(domain, values)


@pytest.fixture(scope="module")
def mesh8():
    return make_mesh(8, platform="cpu")


@pytest.fixture(scope="module")
def plan256(mesh8):
    return MeshNttPlan(mesh8, 256)


@pytest.mark.parametrize("inverse", [False, True])
@pytest.mark.parametrize("coset", [False, True])
def test_mesh_ntt_matches_oracle(plan256, inverse, coset):
    n = plan256.n
    domain = P.Domain(n)
    values = [RNG.randrange(R_MOD) for _ in range(n)]
    got = plan256.run_ints(values, inverse=inverse, coset=coset)
    assert got == _oracle(domain, values, inverse, coset)


def test_mesh_ntt_radix2_core_parity(mesh8, plan256, monkeypatch):
    """The mesh 4-step NTT runs its row/column butterflies through the
    SHARED stage core (ntt_jax.run_stages): flipping DPT_NTT_RADIX=2
    must reproduce the default radix-4 mesh result bit for bit."""
    values = [RNG.randrange(R_MOD) for _ in range(plan256.n)]
    want = plan256.run_ints(values)
    monkeypatch.setenv("DPT_NTT_RADIX", "2")
    got = plan256.run_ints(values)
    assert got == want
    assert (False, False, "plain", 2) in plan256._fns
    assert (False, False, "plain", 4) in plan256._fns


@pytest.mark.tier2
def test_mesh_ntt_roundtrip_uneven_rc(mesh8):
    # n = 512: r = 16, c = 32 (r != c exercises the all_to_all shapes)
    plan = MeshNttPlan(mesh8, 512)
    values = [RNG.randrange(R_MOD) for _ in range(512)]
    domain = P.Domain(512)
    assert plan.run_ints(values) == P.fft(domain, values)
    assert plan.run_ints(plan.run_ints(values), inverse=True) == values


def test_mesh_commit_paths_never_dispatch_pallas(mesh8, monkeypatch):
    """ADVICE r4 regression: _digits_of_handles and _merge_fn trace
    mont_mul on GSPMD-sharded/replicated operands OUTSIDE shard_map,
    where a pallas_call (no SPMD partitioning rule) breaks on a real TPU
    mesh. Force the pallas dispatch mode at any width and assert those
    jits never reach the pallas kernel — while still extracting correct
    digits."""
    import numpy as np
    import jax.numpy as jnp
    from distributed_plonk_tpu.backend import field_jax as FJ
    from distributed_plonk_tpu.backend import field_pallas as FP
    from distributed_plonk_tpu.backend.limbs import ints_to_limbs
    from distributed_plonk_tpu.constants import FR_MONT_R

    monkeypatch.setattr(FJ, "_MUL_MODE", "pallas")
    monkeypatch.setattr(FJ, "_PALLAS_MIN_LANES", 1)
    hits = []
    real_mul = FP.mont_mul

    def spy(spec, a, b):
        hits.append(a.shape)
        return real_mul(spec, a, b)

    monkeypatch.setattr(FP, "mont_mul", spy)

    n = 64
    pts = [C.g1_mul(C.G1_GEN, RNG.randrange(1, R_MOD)) for _ in range(8)]
    ctx = MeshMsmContext(mesh8, [pts[i % 8] for i in range(n)])
    scalars = [RNG.randrange(R_MOD) for _ in range(n)]
    h = jnp.asarray(ints_to_limbs([s * FR_MONT_R % R_MOD for s in scalars], 16))
    digits = ctx._digits_of_handles([h])
    assert not hits, f"pallas dispatched in sharded digit extraction: {hits}"
    assert np.array_equal(np.asarray(digits)[0], ctx._digits_np(scalars))

    planes = tuple(jnp.ones((24, 8, 16), jnp.uint32) for _ in range(3))
    jax.block_until_ready(ctx._merge_fn(planes, planes))
    assert not hits, f"pallas dispatched in the cross-chunk merge: {hits}"


@pytest.mark.slow
def test_mesh_msm_pallas_kernel_parity(mesh8, monkeypatch):
    """The per-shard bucket scans inside the mesh MSM pick up
    DPT_MSM_KERNEL=pallas unchanged (shard_map bodies see per-device
    local shapes, where a pallas_call is legal), and the folded result
    matches the XLA-kernel mesh run. On the CPU test mesh pallas_guard
    would veto the kernel (it exists to keep Mosaic off non-TPU
    meshes), so the guard is opened and the kernel runs interpret-mode
    — the same dispatch seam a TPU mesh exercises compiled."""
    import contextlib
    from distributed_plonk_tpu.backend import msm_jax as MJ
    from distributed_plonk_tpu.parallel import msm_mesh as MM

    n = 32
    bases = [C.g1_mul(C.G1_GEN, RNG.randrange(1, R_MOD)) for _ in range(n)]
    scalars = [RNG.randrange(R_MOD) for _ in range(n)]
    want = MeshMsmContext(mesh8, bases).msm(scalars)
    assert want == C.g1_msm(bases, scalars)
    monkeypatch.setattr(MJ, "_MSM_KERNEL", "pallas")
    monkeypatch.setattr(MM, "pallas_guard",
                        lambda mesh: contextlib.nullcontext())
    assert MeshMsmContext(mesh8, bases).msm(scalars) == want


def test_mesh_msm_matches_oracle(mesh8):
    n = 64
    bases = [C.g1_mul(C.G1_GEN, RNG.randrange(1, R_MOD)) for _ in range(n - 2)]
    bases += [None, None]
    scalars = ([RNG.randrange(R_MOD) for _ in range(n - 3)] + [0, 1, R_MOD - 1])
    ctx = MeshMsmContext(mesh8, bases)
    assert ctx.msm(scalars) == C.g1_msm(bases, scalars)
    # short scalar vector (zero-padded on device)
    short = [RNG.randrange(R_MOD) for _ in range(40)]
    assert ctx.msm(short) == C.g1_msm(bases[:40], short)


@pytest.mark.parametrize("inverse, coset, name", [
    (False, False, "mesh_ntt_fwd_plain"),
    (True, False, "mesh_ntt_inv_plain"),
    (False, True, "mesh_ntt_fwd_coset_plain"),
    (True, True, "mesh_ntt_inv_coset_plain"),
])
def test_mesh_ntt_programs_carry_their_mode_in_their_name(plan256, inverse,
                                                          coset, name):
    """field_jax.named_jit: a device trace reads `jit_mesh_ntt_<mode>`, not
    four programs all called `jit_fn`."""
    plan256.kernel(inverse, coset, boundary="plain")
    fn, consts = plan256._fns[(inverse, coset, "plain", 4)]
    assert fn.__name__ == name
    x = jax.ShapeDtypeStruct((16, plan256.n), "uint32")
    assert "jit_" + name in fn.lower(x, consts).as_text()[:200]
    # the Montgomery boundary the prover runs has no suffix
    plan256.kernel(inverse, coset, boundary="mont")
    fn, _ = plan256._fns[(inverse, coset, "mont", 4)]
    assert fn.__name__ == name[:-len("_plain")]


def test_mesh_msm_programs_are_named_and_its_collective_is_counted(mesh8):
    n = 64
    bases = [C.g1_mul(C.G1_GEN, RNG.randrange(1, R_MOD)) for _ in range(n)]
    counted = {}

    def count(name, by=1):
        counted[name] = counted.get(name, 0) + by

    ctx = MeshMsmContext(mesh8, bases, count=count)
    scalars = [RNG.randrange(R_MOD) for _ in range(n)]
    assert ctx.msm(scalars) == C.g1_msm(bases, scalars)
    assert ctx._merge_fn.__name__ == "mesh_msm_merge"
    assert {fn.__name__ for fn in ctx._chunk_fns.values()} == {
        "mesh_msm_chunk"}
    assert {fn.__name__ for fn in ctx._finish_fns.values()} == {
        "mesh_msm_finish"}
    # one chunk covers a local slice of 16 points; its all_gather hands each
    # of 8 chips the other 7 chips' three (24, windows * buckets) planes
    assert counted["mesh_msm_chunks"] == 1
    planes = counted["mesh_all_gather_bytes"] // (8 * 7)
    assert planes * 8 * 7 == counted["mesh_all_gather_bytes"]
    assert planes % (3 * 24 * 4 * ctx.windows) == 0


def test_mesh_commit_through_the_window_table_on_four_devices():
    """ISSUE 36: a mesh context whose per-device slice runs the signed
    pipeline (272 points a device here) holds the window table of its key,
    dealt like the points with the window axis whole; it commits what
    `MsmContext` and the host oracle commit, a chip adds its windows'
    planes BEFORE the all_gather, which therefore moves one (24, B, 128)
    plane a coordinate and not thirty-two, and the finish on the one chip
    is the running sum alone. Both commit counters count every polynomial."""
    from distributed_plonk_tpu.backend import msm_jax
    from test_curve_msm_jax import _scan_lengths

    n, d, batch = 1030, 4, 2
    distinct = [C.g1_mul(C.G1_GEN, RNG.randrange(1, R_MOD)) for _ in range(12)]
    bases = (distinct * 86)[:n - 2] + [None, None]
    counted = {}

    def count(name, by=1):
        counted[name] = counted.get(name, 0) + by

    ctx = MeshMsmContext(make_mesh(d, platform="cpu"), bases, count=count)
    assert (ctx.c, ctx.signed, ctx.local_n) == (8, True, 272)
    wins, buckets = 32, 128
    for t in ctx.table:
        assert t.shape == (d, ctx.local_n // 8, 8, 24 * wins)
        assert t.sharding.spec == jax.sharding.PartitionSpec(
            "shards", None, None, None)
        assert {s.data.shape for s in t.addressable_shards} == {
            (1, ctx.local_n // 8, 8, 24 * wins)}
    half = 128
    polys = [[RNG.randrange(R_MOD) for _ in range(n)],
             [half, half - 1, 0, 1, R_MOD - 1]
             + [RNG.randrange(R_MOD) for _ in range(300)]]
    want = [C.g1_msm(bases[:len(s)], s) for s in polys]
    assert ctx.msm_many(polys) == want
    assert msm_jax.MsmContext(bases).msm_many(polys) == want
    assert counted == {
        "msm_commit_polys": batch, "msm_commit_polys_preweighted": batch,
        "msm_commit_calls": 1, "msm_commit_chunks": 1, "mesh_msm_chunks": 1,
        "mesh_all_gather_bytes": d * (d - 1) * 3 * 24 * batch * buckets * 4}
    (finish,) = ctx._finish_fns.values()
    planes = [jax.ShapeDtypeStruct((24, batch, buckets), "uint32")] * 3
    assert _scan_lengths(jax.make_jaxpr(finish)(*planes).jaxpr) \
        == ([buckets + 1], 0)
