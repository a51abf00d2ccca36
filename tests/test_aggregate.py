"""Batch-KZG proof aggregation tests (ISSUE 17): N proofs in, ONE 2-pair
pairing check out — pinned by the curve-level pairing counters — accepting
iff every constituent verifies, rejecting bit-flipped members and tampered
artifacts, and surviving a service restart via journal AGG recovery.
"""

import json
import random

import pytest

from distributed_plonk_tpu import aggregate as AGG
from distributed_plonk_tpu import curve
from distributed_plonk_tpu.backend.python_backend import PythonBackend
from distributed_plonk_tpu.proof_io import serialize_proof
from distributed_plonk_tpu.prover import prove
from distributed_plonk_tpu.service.jobs import (JobSpec, build_bucket_keys,
                                                build_circuit, shape_key)

# mixed-kind member pool: both shapes finalize at n=32, so the whole
# 8-member batch proves in seconds while still exercising cross-kind folds
_SHAPES = [{"kind": "toy", "gates": 16},
           {"kind": "range", "bits": 8, "count": 2}]
_keys = {}  # shape_key -> bucket keys, shared across every test here


def _member(i):
    wire = dict(_SHAPES[i % len(_SHAPES)], seed=9000 + i)
    spec = JobSpec.from_wire(wire)
    k = shape_key(spec)
    if k not in _keys:
        _keys[k] = build_bucket_keys(spec)
    ckt = build_circuit(spec)
    proof = prove(random.Random(spec.seed), ckt, _keys[k][1],
                  PythonBackend())
    return {"job_id": f"job-{i}", "spec": spec.to_wire(),
            "pub": ckt.public_input(), "proof": serialize_proof(proof)}


def _vks():
    return {k: v[2] for k, v in _keys.items()}


@pytest.fixture(scope="module")
def members8():
    return [_member(i) for i in range(8)]


@pytest.mark.tier2
def test_n8_mixed_kind_single_pairing_check(members8):
    """THE amortization claim: verifying an 8-member mixed-kind batch
    costs exactly one pairing check with two pairs."""
    agg = AGG.build(members8)
    assert len({m["spec"]["kind"] for m in agg["members"]}) == 2
    curve.reset_pairing_counters()
    assert AGG.verify(agg, _vks())
    assert curve.PAIRING_COUNTERS == {"checks": 1, "pairs": 2}


def test_content_addressed_and_byte_roundtrip(members8):
    agg = AGG.build(members8)
    assert AGG.build(members8) == agg  # deterministic
    blob = AGG.to_bytes(agg)
    assert AGG.from_bytes(blob) == agg
    assert AGG.to_bytes(AGG.from_bytes(blob)) == blob
    # member order is part of the content address
    assert AGG.build(list(reversed(members8)))["agg_id"] != agg["agg_id"]


def test_transcript_binds_every_member_bit(members8):
    norm = AGG.build(members8)["members"]
    base = AGG.derive_challenges(norm)
    assert len({c for pair in base for c in pair}) == 16  # all distinct
    tam = [dict(m) for m in norm]
    pb = bytearray(bytes.fromhex(tam[-1]["proof"]))
    pb[0] ^= 1
    tam[-1]["proof"] = bytes(pb).hex()
    shifted = AGG.derive_challenges(tam)
    # absorb-everything-THEN-draw: flipping the LAST member's first bit
    # moves even the FIRST member's challenges
    assert shifted[0] != base[0]


def test_rejects_one_bit_flipped_member(members8):
    bad = [dict(m) for m in members8]
    pb = bytearray(bad[3]["proof"])
    pb[len(pb) // 2] ^= 0x01
    bad[3]["proof"] = bytes(pb)
    # a CONSISTENT artifact around a corrupt constituent: the content
    # address matches, so rejection comes from the fold itself
    assert not AGG.verify(AGG.build(bad), _vks())
    # the other 7 still aggregate fine
    assert AGG.verify(AGG.build(bad[:3] + bad[4:]), _vks())


def test_rejects_tampered_artifact(members8):
    agg = AGG.build(members8)
    tam = json.loads(AGG.to_bytes(agg).decode())
    tam["members"][0]["job_id"] = "evil"
    assert not AGG.verify(tam, _vks())  # content address mismatch
    tam2 = json.loads(AGG.to_bytes(agg).decode())
    tam2["agg_id"] = "agg-" + "0" * 16
    assert not AGG.verify(tam2, _vks())


def test_accepts_iff_every_member_verifies(members8):
    vks = _vks()
    assert AGG.verify(AGG.build(members8[:1]), vks)
    assert AGG.verify(AGG.build(members8[:5]), vks)
    bad = dict(members8[0], job_id="forged")
    pb = bytearray(bad["proof"])
    pb[100] ^= 0xFF
    bad["proof"] = bytes(pb)
    assert not AGG.verify(AGG.build(members8[:5] + [bad]), vks)


def test_empty_and_malformed_artifacts():
    with pytest.raises(ValueError):
        AGG.build([])
    for blob in (b"junk", b"{}", b'{"schema": 1, "members": []}'):
        with pytest.raises(ValueError):
            AGG.from_bytes(blob)
    assert not AGG.verify(b"junk")


@pytest.mark.tier2
def test_aggregate_all_or_nothing_on_pending_or_unknown_member():
    from distributed_plonk_tpu.service import ProofService
    svc = ProofService(port=0, prover_workers=1).start()
    try:
        done = svc.submit_local({"kind": "toy", "gates": 16, "seed": 41})
        assert done.done_event.wait(180) and done.state == "done"
        pending = svc.submit_local({"kind": "toy", "gates": 300,
                                    "seed": 42})
        if pending.state != "done":  # n=512 proves for seconds; no race
            with pytest.raises(ValueError):
                svc.aggregate_jobs([done.id, pending.id])
        with pytest.raises(LookupError):
            svc.aggregate_jobs([done.id, "job-unknown"])
        with pytest.raises(ValueError):
            svc.aggregate_jobs([])
        assert svc.metrics.snapshot()["counters"].get(
            "aggregates_built", 0) == 0
    finally:
        svc.shutdown()


def test_service_aggregate_round_trip_survives_restart(tmp_path):
    """End to end over the wire: submit a mixed-kind batch, AGGREGATE,
    fetch + client-verify the artifact, restart the service on the same
    journal/store, and fetch + verify the SAME artifact again."""
    from distributed_plonk_tpu.service import ProofService, ServiceClient
    from distributed_plonk_tpu.service.client import ServiceError
    jdir, sdir = str(tmp_path / "j"), str(tmp_path / "s")
    specs = [{"kind": "toy", "gates": 16, "seed": 21},
             {"kind": "range", "bits": 8, "count": 2, "seed": 22},
             {"kind": "toy", "gates": 16, "seed": 23}]
    svc = ProofService(port=0, prover_workers=1, journal_dir=jdir,
                       store_dir=sdir).start()
    try:
        jobs = [svc.submit_local(s) for s in specs]
        for j in jobs:
            assert j.done_event.wait(180) and j.state == "done"
        with ServiceClient("127.0.0.1", svc.port) as c:
            rep = c.aggregate([j.id for j in jobs])
            agg = c.fetch_aggregate(rep["agg_id"])
            with pytest.raises(ServiceError):
                c.aggregate([jobs[0].id, "job-nope"])
            with pytest.raises(ServiceError):
                c.fetch_aggregate("agg-missing")
        assert rep["kinds"] == ["range", "toy"]
        assert AGG.verify(agg, _vks())
        ctr = svc.metrics.snapshot()["counters"]
        assert ctr["aggregates_built"] == 1
        assert ctr["aggregate_members"] == 3
        assert ctr["circuit_kind_toy"] == 2
        assert ctr["circuit_kind_range"] == 1
    finally:
        svc.shutdown()

    svc2 = ProofService(port=0, prover_workers=1, journal_dir=jdir,
                        store_dir=sdir).start()
    try:
        assert svc2.metrics.snapshot()["counters"].get(
            "aggregates_recovered", 0) == 1
        with ServiceClient("127.0.0.1", svc2.port) as c:
            agg2 = c.fetch_aggregate(rep["agg_id"])
        assert agg2 == agg and AGG.verify(agg2, _vks())
    finally:
        svc2.shutdown()


def test_storeless_aggregate_recovers_from_journal_hex(tmp_path):
    """No artifact store: the AGG record carries the blob inline
    (agg_hex) and a crashed service still serves it after recovery."""
    from distributed_plonk_tpu.service import ProofService
    jdir = str(tmp_path / "j")
    svc = ProofService(port=0, prover_workers=1, journal_dir=jdir)
    svc.start()
    agg_id = None
    try:
        job = svc.submit_local({"kind": "toy", "gates": 16, "seed": 31})
        assert job.done_event.wait(180) and job.state == "done"
        agg_id = svc.aggregate_jobs([job.id])["agg_id"]
        assert svc.load_aggregate_blob(agg_id) is not None
    finally:
        svc.crash()
    svc2 = ProofService(port=0, prover_workers=1, journal_dir=jdir)
    svc2.start()
    try:
        blob = svc2.load_aggregate_blob(agg_id)
        assert blob is not None
        assert AGG.verify(AGG.from_bytes(blob), _vks())
    finally:
        svc2.shutdown()
