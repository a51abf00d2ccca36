"""CPU-side checks of what the chip will run (chip_smoke.py / serve.py).

The chip check itself (`python chip_smoke.py`) has no CPU mode; these tests
pin, without chip time, the four things it rests on: the served flow it
shares with the daemon, its refusal to run without a TPU, that every kernel
`auto` selects on a TPU lowers for TPU, and that the compile cache stays
where JAX_COMPILATION_CACHE_DIR puts it.
"""

import os
import pathlib
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent


@pytest.mark.parametrize("aot_warm", [
    False, pytest.param(True, marks=pytest.mark.tier2)])
def test_served_flow_jax_backend_matches_oracle(aot_warm):
    """The flow chip_smoke.py runs on the chip, rehearsed at a toy size on
    XLA:CPU: service started by start_service("jax") — the helper
    scripts/serve.py calls — SUBMIT -> RESULT over TCP, verifier.verify,
    and the first proof byte-equal to the PythonBackend proof; with
    aot_warm (the one-chip smoke's last leg, ~90 s more of XLA:CPU
    compiles, so tier2) WARMUP with aot reports every stage compiled. The
    toy circuit has the conftest circuit's domain (n = 16), so its
    compiled programs are the ones test_jax_backend_prove reuses."""
    import chip_smoke

    jobs, metrics, runtime, mesh_backends = chip_smoke.serve_and_check(
        {"kind": "toy", "gates": 8}, seeds=(41,), wait_s=800,
        aot_warm=aot_warm)
    (job,) = jobs
    assert job["placement"] == "pool" and job["oracle_equal"] is True
    assert len(job["proof"]) == chip_smoke.PROOF_BYTES
    assert metrics["counters"]["jobs_completed"] == 1
    # a proof commits 13 polynomials (5 wires, the permutation product, 5
    # quotient splits, 2 openings), counted by the commit context of the
    # job's key, which the key build filled first with its 18 selector and
    # sigma commitments: start_service builds a new shape's key on the
    # backend it proves on, SRS walk included. A toy key is under 256
    # points and holds no window table
    assert metrics["counters"]["bucket_builds_srs_device"] == 1
    assert metrics["counters"]["msm_commit_polys"] == 18 + 13
    assert "msm_commit_polys_preweighted" not in metrics["counters"]
    assert runtime["backend"] == "jax" and runtime["platform"] == "cpu"
    assert runtime["domain_size"] == 16
    assert mesh_backends == []
    if aot_warm:
        assert runtime["aot_warm"]["msm"] > 0
        assert all(k > 0 for k in runtime["aot_warm"]["ntt"].values())
    # the host has no published peak, so no MFU gauge was made up
    assert not any(k.startswith("mfu_") for k in metrics["gauges"])


def test_chip_smoke_refuses_to_run_without_a_tpu():
    """`python chip_smoke.py` on a machine where jax finds no TPU exits
    non-zero within seconds, names the missing TPU, and prints no result
    line."""
    proc = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py")], cwd=str(REPO),
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=120)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert proc.stdout.strip() == ""


def _as_on_tpu(monkeypatch):
    """Resolve `auto` the way a TPU process does, with compiled (not
    interpreted) Pallas kernels — then jax.export cross-lowers for TPU
    here on the CPU, which catches what the Pallas->Mosaic lowering
    refuses (not what the Mosaic compiler refuses later; that is
    chip_smoke.py's job)."""
    import jax
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setenv("DPT_PALLAS_INTERPRET", "0")


def _export_for_tpu(fn, *specs):
    from jax import export
    return export.export(fn, platforms=["tpu"])(*specs).mlir_module()


def test_auto_field_mul_lowers_for_tpu(monkeypatch):
    import jax
    import jax.numpy as jnp
    from distributed_plonk_tpu.backend import field_jax as FJ

    _as_on_tpu(monkeypatch)
    for spec in (FJ.FR, FJ.FQ):
        shape = (spec.n_limbs, 1 << 13)
        assert FJ._use_pallas(shape)
        arg = jax.ShapeDtypeStruct(shape, jnp.uint32)
        mlir = _export_for_tpu(
            jax.jit(lambda a, b, spec=spec: FJ.mont_mul(spec, a, b)),
            arg, arg)
        assert "tpu_custom_call" in mlir


@pytest.mark.parametrize("n,batch,inverse,coset", [
    (1 << 13, 5, True, False),    # round 1: wire iNTTs
    (1 << 16, 8, False, True),    # round 3: quotient-domain coset NTTs
])
def test_auto_ntt_lowers_for_tpu(monkeypatch, n, batch, inverse, coset):
    import jax
    import jax.numpy as jnp
    from distributed_plonk_tpu.backend import ntt_jax

    _as_on_tpu(monkeypatch)
    # the radix-4 XLA core, whose wide multiplies are the Pallas kernel
    assert ntt_jax._active_radix() == 4
    fn, consts = ntt_jax.NttPlan(n).traced_kernel(inverse, coset, batch=True)
    cspec = {k: jax.ShapeDtypeStruct(a.shape, a.dtype)
             for k, a in consts.items()}
    mlir = _export_for_tpu(
        fn, jax.ShapeDtypeStruct((16, batch, n), jnp.uint32), cspec)
    assert "tpu_custom_call" in mlir


def test_auto_msm_lowers_for_tpu(monkeypatch):
    """The commit pipeline's bucket accumulation at the 2^13 prove's shape
    (8,224-point key, 5-polynomial wire batch, c = 7 signed windows), on
    the kernel `auto` resolves to for a TPU."""
    import jax
    import jax.numpy as jnp
    from distributed_plonk_tpu.backend import msm_jax

    _as_on_tpu(monkeypatch)
    n, batch, windows = 8224, 5, msm_jax.W7
    mode = msm_jax._kernel_mode()
    assert mode == "xla"   # the one-hot scan; its wide multiplies are Pallas
    # a mistyped kernel name is an error, not a quiet "xla"
    monkeypatch.setattr(msm_jax, "_MSM_KERNEL", "palas")
    with pytest.raises(ValueError, match="DPT_MSM_KERNEL"):
        msm_jax._kernel_mode()
    monkeypatch.setattr(msm_jax, "_MSM_KERNEL", "auto")
    group = msm_jax._group_size_batch(n, batch, 7, signed=True, kernel=mode)
    u32 = jnp.uint32
    mlir = _export_for_tpu(
        jax.jit(lambda ax, ay, ainf, d: msm_jax.bucket_planes_batch_signed(
            ax, ay, ainf, d, group, kernel=mode)),
        jax.ShapeDtypeStruct((24, n), u32), jax.ShapeDtypeStruct((24, n), u32),
        jax.ShapeDtypeStruct((n,), jnp.bool_),
        jax.ShapeDtypeStruct((batch, windows, n), u32))
    assert "tpu_custom_call" in mlir


_TRACE_FROM = """
import hashlib, os, re, sys
sys.path.insert(0, sys.argv[2])
import jax, jax.numpy as jnp
from jax import export
from distributed_plonk_tpu.backend import field_jax as FJ
jax.default_backend = lambda: "tpu"
arg = jax.ShapeDtypeStruct((FJ.FQ.n_limbs, 1 << 13), jnp.uint32)
program = FJ.named_jit("probe", lambda a, b: FJ.mont_mul(FJ.FQ, a, b))


def lower():
    return export.export(program, platforms=["tpu"])(arg, arg).mlir_module()


def from_a_key_build():
    def build():
        return lower()
    return build()


text = lower() if sys.argv[1] == "prove" else from_a_key_build()
body = "".join(re.findall(r'backend_config = "([^"]*)"', text))
print(len(body), hashlib.sha256(body.encode()).hexdigest())
"""


def test_a_pallas_programs_cache_key_does_not_follow_its_caller():
    """A Pallas kernel's Mosaic body is part of its program's compile-cache
    key, with the source locations of the trace in it. A key build on the
    device traces programs that a prove uses too; where those locations
    held the frames of the caller, a restart that first reached the same
    program from the prover missed the cache and compiled it again. Two
    processes lower one program for the TPU from two call paths: one
    Mosaic body."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", DPT_PALLAS_INTERPRET="0")
    bodies = [subprocess.run(
        [sys.executable, "-c", _TRACE_FROM, path, str(REPO)], env=env,
        capture_output=True, text=True, timeout=300, check=True,
        cwd=str(REPO)).stdout.split()[-2:] for path in ("prove", "build")]
    assert int(bodies[0][0]) > 0 and bodies[0] == bodies[1]


def test_compile_cache_env_is_never_overridden(monkeypatch, tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, no code path repoints
    jax.config.jax_compilation_cache_dir: the guard lives inside
    field_jax.configure_compile_cache, so neither a direct caller nor the
    fleet worker's store hook can bypass it."""
    import jax
    from distributed_plonk_tpu.backend import field_jax
    from distributed_plonk_tpu.store import set_jax_cache_env

    before = jax.config.jax_compilation_cache_dir
    outside = str(tmp_path / "placed-from-outside")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", outside)
    monkeypatch.delenv("DPT_JAX_CACHE_DIR", raising=False)
    assert field_jax.configure_compile_cache(str(tmp_path / "a")) == outside
    set_jax_cache_env(str(tmp_path / "store"))
    assert "DPT_JAX_CACHE_DIR" not in os.environ
    assert jax.config.jax_compilation_cache_dir == before
    # without the variable the cache goes where the caller says, per
    # machine fingerprint (the import-time default is <checkout>/.jax_cache)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        got = field_jax.configure_compile_cache(str(tmp_path / "b"))
        assert got == jax.config.jax_compilation_cache_dir
        assert got.startswith(str(tmp_path / "b"))
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


@pytest.mark.tier2
def test_daemon_defaults_share_one_jax_backend():
    """What serve.py's defaults do with concurrent traffic, on the ONE
    JaxBackend start_service hands every pool worker (chip_smoke.py sends
    its jobs one at a time to one worker, so this is not covered there):
    two threads proving side by side on the shared instance, then three
    same-bucket jobs queued together, which the placement layer proves as
    one batch. Every proof is the oracle's, byte for byte."""
    import random
    import threading

    from distributed_plonk_tpu.backend.python_backend import PythonBackend
    from distributed_plonk_tpu.proof_io import serialize_proof
    from distributed_plonk_tpu.prover import prove
    from distributed_plonk_tpu.service import (JobSpec, ProofService,
                                               build_bucket_keys,
                                               build_circuit, make_backend)

    toy = {"kind": "toy", "gates": 8}
    specs = [dict(toy, seed=60 + i) for i in range(5)]
    pk = build_bucket_keys(JobSpec.from_wire(specs[0]))[1]

    def proof(spec, backend):
        js = JobSpec.from_wire(spec)
        return serialize_proof(prove(random.Random(js.seed),
                                     build_circuit(js), pk, backend))
    want = [proof(s, PythonBackend()) for s in specs]

    be = make_backend("jax")
    got = {}
    threads = [threading.Thread(
        target=lambda i=i: got.__setitem__(i, proof(specs[i], be)))
        for i in (0, 1)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=800)
    assert [got.get(0), got.get(1)] == want[:2]

    # daemon defaults (two workers, max_batch 8); queued before the
    # scheduler starts so the three pop as one shape batch
    svc = ProofService(port=0, backend=be)
    jobs = [svc.submit_local(s) for s in specs[2:]]
    svc.start()
    try:
        for job in jobs:
            assert job.done_event.wait(timeout=800), job.status()
        assert [job.state for job in jobs] == ["done"] * 3
        assert [job.placement for job in jobs] == ["batch"] * 3
        assert [job.proof_bytes for job in jobs] == want[2:]
        ctr = svc.metrics.snapshot()["counters"]
        assert ctr.get("batch_jobs") == 3 and not ctr.get("job_attempt_errors")
    finally:
        svc.shutdown()
