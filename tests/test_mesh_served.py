"""A mesh-placed job, served: SUBMIT to RESULT through ProofService with
placement sending a toy `merkle` job to a real MeshBackend over two of the
eight virtual CPU devices. What the four-chip deployment
(`merkle-v2cut.mesh4`) rests on, at a size tier-1 can hold: the proof is the
host oracle's byte for byte, the job has its five device rounds, every NTT
took the sharded plan, and the service keeps ONE fed/unfed account, which
the mesh's rounds feed. Bytes and counts, never times.
"""

import random

import jax
import pytest

from distributed_plonk_tpu import prover
from distributed_plonk_tpu.backend.jax_backend import JaxBackend
from distributed_plonk_tpu.backend.python_backend import PythonBackend
from distributed_plonk_tpu.proof_io import serialize_proof
from distributed_plonk_tpu.service import ProofService, ServiceClient
from distributed_plonk_tpu.service import jobs as J
from distributed_plonk_tpu.service import placement as PL

SPEC = {"kind": "merkle", "height": 1, "num_proofs": 1, "seed": 41}
ROUNDS = {"round%d" % i for i in range(1, 6)}


@pytest.fixture(scope="module")
def served():
    """One job through a service whose every job of n >= 512 goes to a
    two-device mesh lease, the rest of the service as start_service builds
    it: (STATUS, proof bytes, METRICS before, METRICS after, pool backend,
    mesh backends)."""
    mp = pytest.MonkeyPatch()
    mp.setattr(PL, "LARGE_MIN", 512)
    mp.setattr(PL, "MESH_LEASE", 2)
    be = JaxBackend()
    svc = ProofService(port=0, prover_workers=2, backend=be,
                       devices=jax.devices()[:2]).start()
    try:
        before = svc.metrics.snapshot()["counters"]
        client = ServiceClient("127.0.0.1", svc.port)
        job_id = client.submit(SPEC)["job_id"]
        status = client.wait(job_id, timeout_s=900)
        _header, proof = client.result(job_id)
        after = svc.metrics.snapshot()["counters"]
        yield (status, proof, before, after, be,
               list(svc.scheduler._mesh_backends.values()))
    finally:
        svc.shutdown()
        be.device_ledger.close_threads()
        mp.undo()


def test_the_served_mesh_proof_is_the_host_oracles(served):
    status, proof, *_ = served
    assert status["state"] == "done", status
    assert status["placement"] == "mesh"
    spec = J.JobSpec.from_wire(SPEC)
    ckt = J.build_circuit(spec)
    pk = J.build_bucket_keys(spec)[1]
    want = prover.prove(random.Random(spec.seed), ckt, pk, PythonBackend())
    assert proof == serialize_proof(want)


def test_a_mesh_job_has_its_five_device_rounds(served):
    status, *_ = served
    assert set(status["device"]) == ROUNDS
    assert all(v > 0 for v in status["device"].values())
    assert set(status["rounds"]) == ROUNDS       # never pipelined
    # a round's charge lies inside its span: it opens at _feed, after the
    # span has begun, and closes before the span ends
    for name in ROUNDS:
        assert status["device"][name] <= status["rounds"][name]


def test_every_ntt_of_the_job_took_the_sharded_plan(served):
    _status, _proof, before, after, _be, meshes = served
    assert "mesh_ntt_calls" not in before
    assert after["mesh_ntt_sharded"] == after["mesh_ntt_calls"] > 0
    assert after["mesh_msm_chunks"] > 0
    # round 1's five wire iNTTs at n = 512 and the quotient's at m = 4096
    # each move half of their 64-byte elements to the other chip
    assert after["mesh_all_to_all_bytes"] % (64 * 512 // 2) == 0
    assert after["mesh_all_to_all_bytes"] >= 5 * 64 * 512 // 2
    assert after["mesh_all_gather_bytes"] > 0
    assert after["placement_mesh"] == after["submesh_leases"] == 1
    assert len(meshes) == 1 and meshes[0].d == 2
    ctxs = [ctx for _bases, ctx in meshes[0]._msm_ctxs.values()]
    assert {fn.__name__ for ctx in ctxs
            for fn in ctx._digits_fns.values()} == {"mesh_msm_digits"}


def test_the_service_keeps_one_account_and_the_mesh_feeds_it(served):
    _status, _proof, before, after, be, meshes = served
    # the leased backend opens its rounds on the pool backend's ledger
    assert meshes[0].device_ledger is be.device_ledger
    assert be.device_ledger._open == 0
    grew = {k: after[k] - before.get(k, 0.0)
            for k in ("phase_clock_s", "device_unfed_s")}
    assert 0 < grew["device_unfed_s"] < grew["phase_clock_s"]
    assert after["device_unfed_s"] == pytest.approx(
        sum(v for k, v in after.items() if k.startswith("device_unfed_s/")))
