"""Proof service tests: queue admission, bucket reuse, TCP round trip,
and checkpoint-resume retry after a deterministic worker kill.

Everything runs in-process on the host oracle backend at tiny domains
(n=16..32) so the whole module stays in the fast tier; the wire tests go
through real TCP via the native framed transport.
"""

import random
import threading

import pytest

from distributed_plonk_tpu.service import (ProofService, ServiceClient,
                                           JobQueue, Rejected)
from distributed_plonk_tpu.service.jobs import (Job, JobSpec, build_circuit,
                                                build_bucket_keys)
from distributed_plonk_tpu.service.client import ServiceError
from distributed_plonk_tpu.proof_io import deserialize_proof, serialize_proof
from distributed_plonk_tpu.verifier import verify

TOY_A = {"kind": "toy", "gates": 8}
TOY_B = {"kind": "toy", "gates": 12}


def _job(spec_dict, seed=0, priority=0):
    d = dict(spec_dict)
    d.update(seed=seed, priority=priority)
    return Job(JobSpec.from_wire(d))


# --- queue -------------------------------------------------------------------

def test_queue_admission_and_backpressure():
    q = JobQueue(max_depth=2)
    q.submit(_job(TOY_A))
    q.submit(_job(TOY_A))
    with pytest.raises(Rejected, match="queue_full"):
        q.submit(_job(TOY_A))
    assert q.depth() == 2 and q.high_water == 2
    q.close()
    with pytest.raises(Rejected, match="draining"):
        q.submit(_job(TOY_A))


def test_queue_priority_and_shape_batching():
    q = JobQueue(max_depth=16)
    low = _job(TOY_A, seed=1, priority=0)
    high_b = _job(TOY_B, seed=2, priority=5)
    high_b2 = _job(TOY_B, seed=3, priority=1)
    low_b = _job(TOY_B, seed=4, priority=0)
    for j in (low, high_b, high_b2, low_b):
        q.submit(j)
    # best job is high_b; the batch is every TOY_B job, priority order
    batch = q.pop_batch(max_batch=8, timeout=0)
    assert [j.id for j in batch] == [high_b.id, high_b2.id, low_b.id]
    assert q.pop_batch(max_batch=8, timeout=0) == [low]
    assert q.pop_batch(max_batch=8, timeout=0) == []


def test_queue_batch_cap():
    q = JobQueue(max_depth=16)
    a_jobs = [_job(TOY_A, seed=i) for i in range(4)]
    for j in a_jobs:
        q.submit(j)
    batch = q.pop_batch(max_batch=3, timeout=0)
    assert [j.id for j in batch] == [j.id for j in a_jobs[:3]]
    assert q.depth() == 1


# --- spec validation ---------------------------------------------------------

def test_spec_validation():
    with pytest.raises(ValueError, match="unknown kind"):
        JobSpec.from_wire({"kind": "nope"})
    with pytest.raises(ValueError, match="gates"):
        JobSpec.from_wire({"kind": "toy", "gates": 0})
    with pytest.raises(ValueError, match="JSON object"):
        JobSpec.from_wire([1, 2])
    spec = JobSpec.from_wire({"kind": "merkle", "height": 2, "seed": 9})
    assert spec.params == {"height": 2, "num_proofs": 1, "num_leaves": 3}


# --- full service over TCP ---------------------------------------------------

@pytest.fixture()
def service():
    svc = ProofService(port=0, prover_workers=2, chaos=True).start()
    yield svc
    svc.shutdown()


def _verify_wire_result(header, blob):
    spec = JobSpec.from_wire(header["spec"])
    vk = build_bucket_keys(spec)[2]
    pub = [int(x, 16) for x in header["public_input"]]
    return verify(vk, pub, deserialize_proof(blob), rng=random.Random(1))


def test_tcp_round_trip_and_bucket_reuse(service):
    with ServiceClient("127.0.0.1", service.port) as c:
        c.ping()
        ids = [c.submit(dict(TOY_A, seed=s))["job_id"] for s in (1, 2)]
        ids.append(c.submit(dict(TOY_B, seed=3))["job_id"])
        for jid in ids:
            st = c.wait(jid, timeout_s=180)
            assert st["state"] == "done", st
            header, blob = c.result(jid)
            assert header["job_id"] == jid
            assert _verify_wire_result(header, blob)
        m = c.metrics()
    # two shapes -> exactly two key builds, the same-shape job reused one:
    # from memory, or, when the second worker took it while the first was
    # still building (a loaded machine), by waiting on that build's latch,
    # or, when the scheduler popped both same-shape jobs as one batch (a
    # slow scheduler thread), through that batch's one lookup
    assert m["counters"]["bucket_misses"] == 2
    sizes = m["histograms"]["batch_size"]
    rode_along = round(sizes["sum_s"]) - sizes["count"]
    assert (m["counters"].get("bucket_hits", 0)
            + m["counters"].get("bucket_latch_waits", 0) + rode_along) >= 1
    assert m["counters"]["jobs_completed"] == 3
    assert "queue_depth" in m["gauges"]
    assert m["histograms"]["job_wait"]["count"] == 3
    assert m["histograms"]["prove_round/round1"]["count"] >= 3


def test_warmup_over_wire(tmp_path):
    svc = ProofService(port=0, prover_workers=1,
                       store_dir=str(tmp_path / "store")).start()
    try:
        with ServiceClient("127.0.0.1", svc.port) as c:
            w1 = c.warmup(TOY_A)
            assert w1["source"] == "built" and w1["build_s"] > 0
            w2 = c.warmup(TOY_A)
            assert w2["source"] == "memory"
            # aot on the host-oracle pool backend: reported, not an error
            assert c.warmup(TOY_A, aot=True)["aot"]["aot"] == "unsupported"
            with pytest.raises(ServiceError, match="bad_spec"):
                c.warmup({"kind": "toy", "gates": 0})
            # a submit for the warmed shape never builds keys
            jid = c.submit(dict(TOY_A, seed=4))["job_id"]
            assert c.wait(jid, timeout_s=180)["state"] == "done"
            m = c.metrics()
        assert m["counters"]["warmups"] == 3
        assert m["counters"]["bucket_misses"] == 1   # the warmup's build
        assert m["counters"]["bucket_hits"] >= 3
        assert m["counters"]["store_put_bytes"] > 0  # keys persisted
    finally:
        svc.shutdown()

    # restarted service over the same store: WARMUP reports a disk hit
    svc2 = ProofService(port=0, prover_workers=1,
                        store_dir=str(tmp_path / "store")).start()
    try:
        with ServiceClient("127.0.0.1", svc2.port) as c:
            assert c.warmup(TOY_A)["source"] == "disk"
        assert svc2.metrics.snapshot()["counters"]["bucket_disk_hits"] == 1
    finally:
        svc2.shutdown()


def test_tcp_errors(service):
    with ServiceClient("127.0.0.1", service.port) as c:
        with pytest.raises(ServiceError, match="bad_spec"):
            c.submit({"kind": "toy", "gates": -1})
        with pytest.raises(ServiceError, match="unknown job"):
            c.status("job-999999")
        jid = c.submit(dict(TOY_A, seed=7))["job_id"]
        # RESULT before completion is a clean not_ready, then real bytes
        try:
            c.result(jid)
        except ServiceError as e:
            assert e.info["reason"] == "not_ready"
        c.wait(jid, timeout_s=180)
        header, blob = c.result(jid)
        assert len(blob) == 944


def test_queue_full_over_wire():
    svc = ProofService(port=0, prover_workers=1, queue_depth=1).start()
    try:
        # stall the scheduler's only consumer path by filling depth-1 queue
        # faster than the single worker drains it
        with ServiceClient("127.0.0.1", svc.port) as c:
            seen_full = False
            ids = []
            for s in range(12):
                try:
                    ids.append(c.submit(dict(TOY_A, seed=100 + s))["job_id"])
                except ServiceError as e:
                    assert e.info["reason"] == "queue_full"
                    assert "max_depth" in e.info
                    seen_full = True
            assert seen_full, "depth-1 queue never pushed back on a burst"
            for jid in ids:
                assert c.wait(jid, timeout_s=300)["state"] == "done"
    finally:
        svc.shutdown()


# --- kill / checkpoint-resume retry -----------------------------------------

def test_killed_worker_resumes_from_checkpoint():
    svc = ProofService(port=0, prover_workers=1, chaos=True).start()
    try:
        # arm the kill BEFORE the job runs: the single worker dies right
        # after persisting round 2, deterministically
        victim = svc.pool.kill_worker(worker="w0g1", at_round=2)
        assert victim == "w0g1"
        job = svc.submit_local(dict(TOY_B, seed=11, priority=0))
        assert job.done_event.wait(timeout=240)
        assert job.state == "done"
        assert job.retries == 1
        assert [a["outcome"] for a in job.attempts] == ["killed", "ok"]
        assert job.attempts[0]["worker"] == "w0g1"
        assert job.attempts[1]["worker"] == "w0g2"  # respawned slot

        # resume must be byte-identical to an uninterrupted prove of the
        # same spec against the same bucket keys
        spec = JobSpec.from_wire(dict(TOY_B, seed=11))
        _, pk, vk = build_bucket_keys(spec)
        ckt = build_circuit(spec)
        from distributed_plonk_tpu.backend.python_backend import PythonBackend
        from distributed_plonk_tpu.prover import prove
        want = serialize_proof(prove(random.Random(11), ckt, pk,
                                     PythonBackend()))
        assert job.proof_bytes == want
        assert verify(vk, job.public_input,
                      deserialize_proof(job.proof_bytes),
                      rng=random.Random(2))
        m = svc.metrics.snapshot()
        assert m["counters"]["workers_killed"] == 1
        assert m["counters"]["job_retries"] == 1
        assert m["counters"]["workers_spawned"] == 2
    finally:
        svc.shutdown()


def test_job_timeout_fails_cleanly():
    svc = ProofService(port=0, prover_workers=1, job_timeout_s=0.0001).start()
    try:
        job = svc.submit_local(dict(TOY_A, seed=5))
        assert job.done_event.wait(timeout=240)
        assert job.state == "failed"
        assert "timeout" in job.error
        m = svc.metrics.snapshot()
        assert m["counters"]["jobs_timeout"] == 1
        assert m["counters"]["jobs_failed"] == 1
    finally:
        svc.shutdown()
