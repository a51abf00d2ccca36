"""Placement-aware scheduler + cross-job batched proving tests.

The hard contract pinned here: a BATCHED prove (N same-shape jobs in one
prover.prove_many lockstep, commit MSMs / evaluations launched across
jobs) produces proof bytes BYTE-IDENTICAL to N sequential proves — with
mixed per-job blinding RNGs, through the whole service path, with the
DPT_BATCH_PROVE=0 parity escape, and when one batch member is killed
mid-prove (it resumes ALONE from its snapshot; the others finish in the
original batch). Plus the submesh leasing model: a big "mesh"-classified
job and a small batch divide one injected device pool disjointly and
every lease is released.

Everything runs the host oracle backend at tiny domains (jax-free), so
the module lives in the fast/chaos tier.
"""

import random
import threading
import time

import pytest

from distributed_plonk_tpu.backend.python_backend import PythonBackend
from distributed_plonk_tpu.proof_io import serialize_proof
from distributed_plonk_tpu.prover import prove, prove_many
from distributed_plonk_tpu.service import ProofService
from distributed_plonk_tpu.service import placement as PL
from distributed_plonk_tpu.service.jobs import (JobSpec, build_bucket_keys,
                                                build_circuit)
from distributed_plonk_tpu.service.placement import (SubmeshLeaser, classify)

TOY = {"kind": "toy", "gates": 16}


def _sequential_proof(spec_obj, _pk_cache={}):
    """Uninterrupted single prove of a spec — the byte oracle."""
    s = JobSpec.from_wire(spec_obj)
    key = (s.kind, tuple(sorted(s.params.items())))
    if key not in _pk_cache:
        _pk_cache[key] = build_bucket_keys(s)[1]
    return serialize_proof(prove(random.Random(s.seed), build_circuit(s),
                                 _pk_cache[key], PythonBackend()))


# --- classification + leasing units ------------------------------------------

def test_classify_thresholds(monkeypatch):
    monkeypatch.setattr(PL, "SMALL_MAX", 1 << 14)
    monkeypatch.setattr(PL, "LARGE_MIN", 1 << 18)
    assert classify(1 << 10) == "batch"
    assert classify(1 << 14) == "batch"
    assert classify((1 << 14) + 1) == "pool"
    assert classify((1 << 18) - 1) == "pool"
    assert classify(1 << 18) == "mesh"
    assert classify(1 << 20) == "mesh"


def test_leaser_disjoint_contiguous_release():
    leaser = SubmeshLeaser([10, 11, 12, 13])
    a = leaser.lease(2)
    b = leaser.lease(1)
    # disjoint, and the 2-wide lease is a contiguous run
    assert set(a.devices).isdisjoint(b.devices)
    assert list(a.devices) == [10, 11]
    assert leaser.free_count() == 1
    # opportunistic probe: only 1 device free, a 2-wide ask says no NOW
    assert leaser.lease(2, timeout_s=0) is None
    c = leaser.lease(1, timeout_s=0)
    assert c is not None and leaser.free_count() == 0
    # nothing free: probe fails, blocking ask with a timeout times out
    assert leaser.lease(1, timeout_s=0) is None
    assert leaser.lease(1, timeout_s=0.05) is None
    for lease in (a, b, c):
        leaser.release(lease)
    assert leaser.free_count() == 4
    # double release is a no-op, not a free-list corruption
    leaser.release(a)
    assert leaser.free_count() == 4
    # oversized asks clamp to the pool
    big = leaser.lease(99)
    assert len(big) == 4


def test_leaser_blocking_handoff():
    leaser = SubmeshLeaser([0, 1])
    a = leaser.lease(2)
    got = {}

    def taker():
        got["lease"] = leaser.lease(1)  # blocks until the release

    t = threading.Thread(target=taker, daemon=True)
    t.start()
    time.sleep(0.05)
    assert "lease" not in got
    leaser.release(a)
    t.join(timeout=5)
    assert len(got["lease"]) == 1


# --- batched-vs-sequential byte-identity -------------------------------------

@pytest.mark.parametrize("n_jobs", [
    2, pytest.param(4, marks=pytest.mark.tier2)])
def test_prove_many_byte_identity_mixed_rngs(n_jobs):
    """prove_many == N sequential proves, bit for bit, with a DIFFERENT
    blinding rng per member (the per-member rng/transcript isolation the
    placement batch depends on)."""
    specs = [JobSpec.from_wire(dict(TOY, seed=50 + 7 * i))
             for i in range(n_jobs)]
    pk = build_bucket_keys(specs[0])[1]
    be = PythonBackend()
    want = [serialize_proof(prove(random.Random(s.seed), build_circuit(s),
                                  pk, be)) for s in specs]
    proofs, errors = prove_many(
        [random.Random(s.seed) for s in specs],
        [build_circuit(s) for s in specs], pk, PythonBackend())
    assert errors == [None] * n_jobs
    assert [serialize_proof(p) for p in proofs] == want


def _batched_service_run(specs, **svc_kwargs):
    """Submit specs BEFORE the scheduler starts (so pop_batch sees them
    as one shape batch), wait for all, return (service, jobs)."""
    svc = ProofService(port=0, prover_workers=1, **svc_kwargs)
    jobs = [svc.submit_local(s) for s in specs]
    svc.start()
    for j in jobs:
        assert j.done_event.wait(timeout=180), j.status()
    return svc, jobs


@pytest.mark.tier2
def test_service_batch_byte_identity():
    """The whole service path: 4 same-shape jobs pop as ONE placement
    batch, prove data-parallel, and every proof is byte-identical to an
    uninterrupted sequential prove of its spec."""
    specs = [dict(TOY, seed=900 + i) for i in range(4)]
    svc, jobs = _batched_service_run(specs)
    try:
        ctr = svc.metrics.snapshot()["counters"]
        assert ctr.get("placement_batch") == 1
        assert ctr.get("batch_proves") == 1
        assert ctr.get("batch_jobs") == 4
        for spec, job in zip(specs, jobs):
            assert job.state == "done"
            assert job.placement == "batch"
            assert job.status()["placement"] == "batch"
            assert job.proof_bytes == _sequential_proof(spec)
    finally:
        svc.shutdown()


@pytest.mark.tier2
def test_batch_prove_knob_off_parity(monkeypatch):
    """DPT_BATCH_PROVE=0: same traffic takes the sequential per-job pool
    path — zero batched attempts — and lands on the identical bytes."""
    monkeypatch.setattr(PL, "BATCH_PROVE", False)
    specs = [dict(TOY, seed=930 + i) for i in range(3)]
    svc, jobs = _batched_service_run(specs)
    try:
        ctr = svc.metrics.snapshot()["counters"]
        assert "batch_proves" not in ctr
        assert ctr.get("placement_pool") == 1
        for spec, job in zip(specs, jobs):
            assert job.placement == "pool"
            assert job.proof_bytes == _sequential_proof(spec)
    finally:
        svc.shutdown()


# --- batch member kill: resumes alone, others unaffected ---------------------

@pytest.mark.tier2
def test_batch_member_kill_resumes_alone():
    """A kill armed at round 2 fires on exactly ONE batch member (the
    first to reach that boundary). The member's snapshot is durable, so
    its solo retry RESUMES (no round-1 re-prove) to byte-identical
    bytes; the other members finish inside the original batch; the
    worker thread survives (no respawn)."""
    specs = [dict(TOY, seed=950 + i) for i in range(3)]
    svc = ProofService(port=0, prover_workers=1)
    jobs = [svc.submit_local(s) for s in specs]
    victim_name = svc.pool.kill_worker(at_round=2)  # pre-armed on w0g1
    svc.start()
    try:
        for j in jobs:
            assert j.done_event.wait(timeout=180), j.status()
            assert j.state == "done"
        ctr = svc.metrics.snapshot()["counters"]
        assert ctr.get("batch_member_kills") == 1
        assert ctr.get("checkpoint_resumes", 0) >= 1
        # the batch's worker thread was NOT killed/respawned
        assert ctr.get("workers_spawned") == 1
        assert "workers_killed" not in ctr
        killed = [j for j in jobs
                  if any(a["outcome"] == "killed" for a in j.attempts)]
        assert len(killed) == 1
        assert [a["outcome"] for a in killed[0].attempts] == ["killed", "ok"]
        assert killed[0].worker == victim_name  # same slot retried it
        for j in jobs:
            if j is not killed[0]:
                assert [a["outcome"] for a in j.attempts] == ["ok"]
        for spec, job in zip(specs, jobs):
            assert job.proof_bytes == _sequential_proof(spec)
    finally:
        svc.shutdown()


@pytest.mark.tier2
def test_batch_member_kill_by_job_id():
    """A JOB-targeted kill inside a running batch takes down only that
    member. Uses a bigger shape so the kill lands mid-prove."""
    specs = [{"kind": "toy", "gates": 120, "seed": 970 + i}
             for i in range(3)]
    svc = ProofService(port=0, prover_workers=1)
    jobs = [svc.submit_local(s) for s in specs]
    target = jobs[2]
    svc.start()
    try:
        deadline = time.monotonic() + 60
        killed_armed = False
        while time.monotonic() < deadline and not killed_armed:
            if target.state == "running":
                try:
                    svc.pool.kill_worker(job_id=target.id, at_round=None)
                    killed_armed = True
                except LookupError:
                    pass
            if target.done_event.is_set():
                break
            time.sleep(0.005)
        for j in jobs:
            assert j.done_event.wait(timeout=180), j.status()
            assert j.state == "done"
        for spec, job in zip(specs, jobs):
            assert job.proof_bytes == _sequential_proof(spec)
        if killed_armed and any(a["outcome"] == "killed"
                                for a in target.attempts):
            # the kill landed: it must have hit ONLY the target
            for j in jobs:
                if j is not target:
                    assert all(a["outcome"] != "killed"
                               for a in j.attempts)
    finally:
        svc.shutdown()


# --- submesh leasing: big sharded job + small batch coexist ------------------

class _RecordingMeshFactory:
    """Stub mesh-backend factory: records each lease's devices and
    proves on the host oracle (placement logic is what is under test,
    not mesh kernels)."""

    def __init__(self, hold_s=0.0):
        self.calls = []
        self.hold_s = hold_s

    def __call__(self, devices):
        self.calls.append(tuple(devices))
        hold = self.hold_s

        class _SlowBackend(PythonBackend):
            def pk_polys(self, pk):  # first backend touch of a prove
                if hold:
                    time.sleep(hold)
                return super().pk_polys(pk)

        return _SlowBackend()


@pytest.mark.tier2
def test_submesh_lease_interleaved(monkeypatch):
    """A big 'mesh'-classified job leases a disjoint submesh of the
    injected 4-device pool while a small batch still gets served (and
    takes its own 1-device lease); every lease is released at the end."""
    monkeypatch.setattr(PL, "LARGE_MIN", 256)  # n=512 toy -> "mesh"
    factory = _RecordingMeshFactory(hold_s=0.3)
    devices = ["d0", "d1", "d2", "d3"]
    svc = ProofService(port=0, prover_workers=2, devices=devices,
                       mesh_backend_factory=factory)
    big_spec = {"kind": "toy", "gates": 300, "seed": 777}   # n=512
    small_specs = [dict(TOY, seed=980 + i) for i in range(2)]
    big = svc.submit_local(big_spec)
    smalls = [svc.submit_local(s) for s in small_specs]
    svc.start()
    try:
        # while the big job holds its submesh, the small batch completes
        for j in smalls:
            assert j.done_event.wait(timeout=180), j.status()
        assert big.done_event.wait(timeout=180), big.status()
        assert big.state == "done" and big.placement == "mesh"
        assert all(j.placement == "batch" for j in smalls)
        ctr = svc.metrics.snapshot()["counters"]
        assert ctr.get("placement_mesh") == 1
        assert ctr.get("placement_batch") == 1
        # big job leased half the pool (auto policy: 4 devices -> 2),
        # contiguous; the batch's opportunistic lease was disjoint
        assert ctr.get("submesh_leases", 0) >= 2
        assert len(factory.calls) == 1
        leased = list(factory.calls[0])
        assert len(leased) == 2 and set(leased) <= set(devices)
        idx = sorted(devices.index(d) for d in leased)
        assert idx[1] - idx[0] == 1  # contiguous run (ICI locality)
        # all leases released: the pool is whole again, and the gauge
        # tracked the release edge (not just the grant low-water mark)
        assert svc.scheduler.leaser().free_count() == 4
        gauges = svc.metrics.snapshot()["gauges"]
        assert gauges.get("submesh_devices_free") == 4
        # byte-identity holds on the mesh-placed job too
        assert big.proof_bytes == _sequential_proof(big_spec)
        for spec, j in zip(small_specs, smalls):
            assert j.proof_bytes == _sequential_proof(spec)
    finally:
        svc.shutdown()


@pytest.mark.tier2
def test_mesh_retry_replaces_on_submesh(monkeypatch):
    """A mesh-placed job whose attempt is killed mid-prove goes BACK
    through the scheduler for re-placement: the retry runs on a fresh
    submesh lease (not silently on the worker's shared single-device
    backend), resumes from its snapshot, and lands on identical bytes."""
    monkeypatch.setattr(PL, "LARGE_MIN", 256)
    factory = _RecordingMeshFactory()
    svc = ProofService(port=0, prover_workers=1,
                       devices=["m0", "m1", "m2", "m3"],
                       mesh_backend_factory=factory)
    spec = {"kind": "toy", "gates": 300, "seed": 444}
    job = svc.submit_local(spec)
    svc.pool.kill_worker(at_round=2)  # fires on the mesh prove's worker
    svc.start()
    try:
        assert job.done_event.wait(timeout=180), job.status()
        assert job.state == "done"
        assert job.retries >= 1
        assert [a["outcome"] for a in job.attempts] == ["killed", "ok"]
        # re-placed: still "mesh", a SECOND lease was granted, and both
        # attempts ran on factory-built (leased-submesh) backends
        assert job.placement == "mesh"
        ctr = svc.metrics.snapshot()["counters"]
        assert ctr.get("placement_mesh") == 2
        assert ctr.get("submesh_leases", 0) >= 2
        assert ctr.get("checkpoint_resumes", 0) >= 1
        assert svc.scheduler.leaser().free_count() == 4
        assert job.proof_bytes == _sequential_proof(spec)
    finally:
        svc.shutdown()


def test_mesh_lease_released_on_failure(monkeypatch):
    """A mesh prove that dies still returns its devices to the pool."""
    monkeypatch.setattr(PL, "LARGE_MIN", 256)

    class _Boom(PythonBackend):
        def pk_polys(self, pk):
            raise RuntimeError("mesh backend exploded")

    svc = ProofService(port=0, prover_workers=1, max_retries=0,
                       devices=["a", "b"],
                       mesh_backend_factory=lambda devs: _Boom())
    job = svc.submit_local({"kind": "toy", "gates": 300, "seed": 5})
    svc.start()
    try:
        assert job.done_event.wait(timeout=120), job.status()
        assert job.state == "failed"
        assert svc.scheduler.leaser().free_count() == 2
    finally:
        svc.shutdown()
