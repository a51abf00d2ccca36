"""Fused multi-stage Pallas NTT (ntt_pallas) vs the XLA stage cores.

The VMEM-resident kernel must be BIT-IDENTICAL to the radix-4 XLA core
and the host oracle for every (inverse, coset, boundary) mode, edge
widths down to n=1 (where the dispatch falls back exactly like
radix-4's n<=2 fallback), batch kernels, forced multi-group schedules,
and the shared run_stages core the mesh/fleet paths consume; and the
round-3 pointwise fusion (gate/sigma epilogues + combine prologue,
DPT_R3_FUSE) must be value-identical to the unfused product path.
Interpret mode on CPU (DPT_PALLAS_INTERPRET=1, conftest). On the v5e
Mosaic has not yet returned a compiled fused NTT (CHANGES.md PR 21), so
`auto` never picks this kernel and most of its interpret-mode checks
(~15-25 s of emulation compile per distinct program) are tier2 (scripts/ci.sh fast);
tier-1 keeps the dispatch, schedule and edge-width checks and the TPU
cross-lowering.
"""

import random

import numpy as np
import jax.numpy as jnp
import pytest

from distributed_plonk_tpu import poly as P
from distributed_plonk_tpu.constants import R_MOD
from distributed_plonk_tpu.backend import field_jax as FJ
from distributed_plonk_tpu.backend import ntt_jax as NTT
from distributed_plonk_tpu.backend import ntt_pallas as NP
from distributed_plonk_tpu.backend.limbs import ints_to_limbs

RNG = random.Random(0xF057)


def _vals(n):
    return [RNG.randrange(R_MOD) for _ in range(n)]


def _mont_rows(n, b=None):
    """CANONICAL Montgomery-form field elements (bit-identity across
    different stage decompositions only holds for reduced inputs — the
    kernels' documented boundary contract)."""
    from distributed_plonk_tpu.constants import FR_MONT_R

    def one(_):
        return ints_to_limbs([RNG.randrange(R_MOD) * FR_MONT_R % R_MOD
                              for _ in range(n)], 16)

    if b is None:
        return jnp.asarray(one(0))
    return jnp.asarray(np.stack([one(i) for i in range(b)], axis=1))


def _oracle(n, vals, inverse, coset):
    d = P.Domain(n)
    fn = {(False, False): P.fft, (False, True): P.coset_fft,
          (True, False): P.ifft, (True, True): P.coset_ifft}[(inverse, coset)]
    return fn(d, vals)


@pytest.mark.tier2
def test_pallas_matches_xla_and_oracle_n64(monkeypatch):
    """n=64 (even log2, single fused group at the default rows cap):
    the pallas kernel is limb-identical to the radix-4 XLA kernel at
    the Montgomery boundary in the plain and fused-coset-pre-scale
    modes, and matches the host oracle through the plain boundary in
    the fused-inverse-post-scale mode. (Each distinct pallas program
    costs ~20 s of interpret-mode compile, and tier-1 has a wall-clock
    budget: the full 8-mode x odd/even matrix rides the slow tier.)"""
    n = 64
    plan = NTT.get_plan(n)
    v = _mont_rows(n)
    got = np.asarray(plan.kernel(False, True, kernel="pallas")(v))
    ref = np.asarray(plan.kernel(False, True, kernel="xla")(v))
    assert np.array_equal(got, ref)
    vals = _vals(n)
    assert (plan.run_ints(vals, inverse=True, coset=True, kernel="pallas")
            == _oracle(n, vals, True, True))


@pytest.mark.slow
def test_pallas_all_modes_odd_even_sweep():
    """The full 8-mode sweep at odd AND even log2(n) — every
    (inverse, coset, boundary) combination bit-identical to the host
    oracle (plain boundary) / radix-4 core (Montgomery boundary)."""
    for n in (32, 64):
        plan = NTT.get_plan(n)
        vals = _vals(n)
        v = _mont_rows(n)
        for inverse in (False, True):
            for coset in (False, True):
                got = plan.run_ints(vals, inverse=inverse, coset=coset,
                                    kernel="pallas")
                assert got == _oracle(n, vals, inverse, coset), \
                    (n, inverse, coset, "plain")
                gm = np.asarray(plan.kernel(inverse, coset,
                                            kernel="pallas")(v))
                rm = np.asarray(plan.kernel(inverse, coset,
                                            kernel="xla")(v))
                assert np.array_equal(gm, rm), (n, inverse, coset, "mont")


def test_edge_widths_and_fallback():
    """n=1/2 have no fused schedule: kernel='pallas' falls back to the
    XLA body (like radix-4's n<=2 fallback) and still matches the
    oracle. n=4 is the smallest real fused program (single group,
    rows=4, one-lane tiles)."""
    for n in (1, 2):
        plan = NTT.get_plan(n)
        vals = _vals(n)
        assert plan._effective_kernel("pallas") == "xla"
        assert plan.run_ints(vals, kernel="pallas") == _oracle(
            n, vals, False, False)
    plan = NTT.get_plan(4)
    vals = _vals(4)
    assert plan.run_ints(vals, coset=True, kernel="pallas") == _oracle(
        4, vals, False, True)


@pytest.mark.slow
def test_edge_width_sweep():
    """n=8..128: one fused mode per width (they alternate so both the
    forward-coset pre-scale and the inverse post-scale paths see every
    schedule shape, including the odd-log2 unbalanced group splits)."""
    for i, n in enumerate((8, 16, 32, 128)):
        plan = NTT.get_plan(n)
        vals = _vals(n)
        inverse = bool(i % 2)
        assert plan.run_ints(vals, inverse=inverse, coset=True,
                             kernel="pallas") == _oracle(
            n, vals, inverse, True), n


@pytest.mark.slow
def test_batch_kernel_matches_single(monkeypatch):
    """(16, B, n) pallas batch kernel == the XLA batch kernel, B=3
    (the prover's round-1/round-3 launch shape, (B, tiles) grid)."""
    n = 32
    plan = NTT.get_plan(n)
    vb = _mont_rows(n, b=3)
    got = np.asarray(plan.kernel_batch(False, True, kernel="pallas")(vb))
    ref = np.asarray(plan.kernel_batch(False, True, kernel="xla")(vb))
    assert np.array_equal(got, ref)


@pytest.mark.tier2
def test_multi_group_and_vmem_knobs(monkeypatch):
    """A narrow group cap forces MULTIPLE sequential fused groups and a
    small VMEM budget forces narrow lane tiles — both must stay
    bit-identical (fresh NttPlan instances so the forced schedules do
    not poison the shared plan cache)."""
    n = 64
    vals = _vals(n)
    monkeypatch.setattr(NP, "_ROWS_CAP", 8)   # groups of R=3,3 at n=64
    monkeypatch.setattr(NP, "_VMEM_MB", 1)
    plan = NTT.NttPlan(n)
    sched = NP.plan_schedule(plan.log_n)
    assert len(sched) == 2 and all(r == 3 for _, r in sched)
    assert plan.run_ints(vals, inverse=True, coset=True,
                         kernel="pallas") == _oracle(n, vals, True, True)


@pytest.mark.tier2
def test_run_stages_shared_core(monkeypatch):
    """The shared stage core dispatches to the fused kernel from the
    SAME consts dict the mesh/fleet paths build (core_consts), and is
    bit-identical to the XLA tables — covering the mesh 4-step and
    fleet panel integration seam without a mesh."""
    n = 16
    plan = NTT.get_plan(n)
    v = _mont_rows(n, b=2)
    monkeypatch.setattr(NTT, "_NTT_KERNEL", "pallas")
    consts_p = {k: jnp.asarray(a)
                for k, a in plan.core_consts(False).items()}
    assert any(k.startswith("pg") for k in consts_p)
    got = np.asarray(NTT.run_stages(v, consts_p))
    monkeypatch.setattr(NTT, "_NTT_KERNEL", "xla")
    consts_x = {k: jnp.asarray(a)
                for k, a in plan.core_consts(False).items()}
    assert not any(k.startswith("pg") for k in consts_x)
    ref = np.asarray(NTT.run_stages(v, consts_x))
    assert np.array_equal(got, ref)


def test_dispatch_knob(monkeypatch):
    """DPT_NTT_KERNEL resolution: auto is xla on every platform (the
    fused kernel is only ever asked for by name, see ntt_jax),
    pallas/xla force, bad values raise, pallas_disabled overrides even a
    forced pallas (the GSPMD invariant), and the mesh guard path falls
    back at trace time (same seam msm_jax pins)."""
    import jax
    monkeypatch.setattr(NTT, "_NTT_KERNEL", "auto")
    assert NTT._active_kernel() == "xla"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert NTT._active_kernel(n=1 << 13) == "xla"
    monkeypatch.setattr(NTT, "_NTT_KERNEL", "pallas")
    assert NTT._active_kernel() == "pallas"
    monkeypatch.setattr(NTT, "_NTT_KERNEL", "xla")
    assert NTT._active_kernel() == "xla"
    assert NTT._active_kernel("pallas") == "pallas"
    with pytest.raises(ValueError):
        NTT._active_kernel("mosaic")
    monkeypatch.setattr(NTT, "_NTT_KERNEL", "turbo")
    with pytest.raises(ValueError):
        NTT._active_kernel()
    monkeypatch.setattr(NTT, "_NTT_KERNEL", "pallas")
    with FJ.pallas_disabled():
        assert NTT._active_kernel() == "xla"
        assert NTT._active_kernel("pallas") == "xla"


def test_schedule_consistency():
    """plan_schedule covers every stage exactly once for all widths and
    caps, and schedule_from_consts round-trips it (the trace-time
    re-derivation used inside run_groups)."""
    import itertools
    for log_n, cap in itertools.product(range(2, 21), (4, 8, 16, 64)):
        saved = NP._ROWS_CAP
        NP._ROWS_CAP = cap
        try:
            sched = NP.plan_schedule(log_n)
        finally:
            NP._ROWS_CAP = saved
        assert sum(r for _, r in sched) == log_n
        assert [s0 for s0, _ in sched] == [
            sum(r for _, r in sched[:i]) for i in range(len(sched))]
        assert all(1 <= r <= max(2, cap.bit_length() - 1) for _, r in sched)
        # group 0 always has a stage-1 table, later groups a stage-0 one
        # (schedule_from_consts depends on at least one table per group)
        assert sched[0][1] >= 2 or len(sched) == 1


@pytest.mark.slow
def test_aot_compile_pallas_mode(monkeypatch):
    """NttPlan.aot_compile under the pallas kernel lowers the fused
    programs (mode-aware, like MsmContext.aot_compile) — this is the
    warm_stages / warmup.py --aot path. Montgomery boundary only keeps
    the interpret-mode compile budget small; the kernel stays correct
    after the AOT pass."""
    n = 16
    monkeypatch.setattr(NTT, "_NTT_KERNEL", "pallas")
    plan = NTT.NttPlan(n)
    rep = plan.aot_compile(boundaries=("mont",))
    assert rep["kernel"] == "pallas"
    assert rep["compiled"] == 4 and rep["failed"] == 0
    vals = _vals(n)
    assert plan.run_ints(vals, coset=True) == _oracle(n, vals, False, True)


@pytest.mark.slow
def test_mesh_kernel_parity(monkeypatch):
    """The mesh 4-step NTT under DPT_NTT_KERNEL=pallas: per-shard
    run_stages calls pick the fused kernel inside shard_map (the guard
    is forced open the way test_mesh_parallel does for the MSM) and the
    result matches the host oracle bit for bit."""
    import contextlib
    from distributed_plonk_tpu.parallel import ntt_mesh
    from distributed_plonk_tpu.parallel.mesh import make_mesh

    monkeypatch.setattr(NTT, "_NTT_KERNEL", "pallas")
    monkeypatch.setattr(ntt_mesh, "pallas_guard",
                        lambda mesh: contextlib.nullcontext())
    mesh = make_mesh(2, platform="cpu")
    n = 64
    plan = ntt_mesh.MeshNttPlan(mesh, n)
    vals = _vals(n)
    assert plan.run_ints(vals, inverse=True, coset=True) == _oracle(
        n, vals, True, True)
    # and with the REAL guard (cpu mesh): trace-time fallback to the
    # XLA tables, still correct
    monkeypatch.undo()
    monkeypatch.setattr(NTT, "_NTT_KERNEL", "pallas")
    plan2 = ntt_mesh.MeshNttPlan(mesh, n)
    assert plan2.run_ints(vals, coset=True) == _oracle(n, vals, False, True)


@pytest.mark.tier2
def test_round3_fusion_matches_unfused():
    """DPT_R3_FUSE: the fused round 3 (gate/sigma folds as coset-FFT
    epilogues + the combine as the coset-iNTT prologue, via
    NttPlan.kernel_fused) produces the SAME quotient polynomial as the
    unfused standalone-step path, bit for bit."""
    from distributed_plonk_tpu.poly import Domain
    from distributed_plonk_tpu.backend import prover_jax as PJ
    from distributed_plonk_tpu.backend import jax_backend as JB

    n, m = 64, 256
    qd = Domain(m)

    def rand_h(length):
        return jnp.asarray(PJ.lift([RNG.randrange(R_MOD)
                                    for _ in range(length)]))

    sel = [rand_h(n) for _ in range(13)]
    sig = [rand_h(n) for _ in range(5)]
    wir = [rand_h(n + 2) for _ in range(5)]
    zpoly = rand_h(n + 3)
    pi = rand_h(n)
    k = [RNG.randrange(R_MOD) for _ in range(5)]
    beta, gamma, alpha, asdn = (RNG.randrange(R_MOD) for _ in range(4))
    args = (n, m, qd, k, beta, gamma, alpha, asdn, sel, sig, wir, zpoly, pi)

    saved = JB._R3_FUSE
    saved_br = JB._R3_BITREV
    try:
        JB._R3_FUSE = True
        fused = np.asarray(JB.JaxBackend().quotient_poly_streamed(*args))
        JB._R3_FUSE = False
        unfused = np.asarray(JB.JaxBackend().quotient_poly_streamed(*args))
        # DPT_R3_BITREV: the deferred-bit-reversal pipeline (producer
        # launches emit constant-geometry order, tables re-indexed, one
        # input gather at the consuming iNTT) must be bit-identical to
        # BOTH the per-launch-permuted fused path and the unfused path
        JB._R3_FUSE = True
        JB._R3_BITREV = not saved_br
        flipped = np.asarray(JB.JaxBackend().quotient_poly_streamed(*args))
    finally:
        JB._R3_FUSE = saved
        JB._R3_BITREV = saved_br
    assert np.array_equal(fused, unfused)
    assert np.array_equal(fused, flipped)


def test_group_kernel_lowers_for_tpu(monkeypatch):
    """Asked for by name, the fused NTT gets through the Pallas->Mosaic
    lowering at the 2^13 prove's round-1 shape (jax.export cross-lowers
    for TPU here on the CPU). What the Mosaic COMPILER does with it only
    a chip run shows — on the v5e it has not yet returned (CHANGES.md
    PR 21), which is why `auto` does not pick this kernel."""
    import jax
    from jax import export

    monkeypatch.setenv("DPT_PALLAS_INTERPRET", "0")
    n, batch = 1 << 13, 5
    fn, consts = NTT.NttPlan(n).traced_kernel(True, False, batch=True,
                                              kernel="pallas")
    cspec = {k: jax.ShapeDtypeStruct(a.shape, a.dtype)
             for k, a in consts.items()}
    exp = export.export(fn, platforms=["tpu"])(
        jax.ShapeDtypeStruct((16, batch, n), jnp.uint32), cspec)
    assert exp.mlir_module().count("tpu_custom_call") >= 3  # one per group
