"""The reference's v2 job shape (several Merkle membership proofs in one
circuit, the job of the benchmark's `merkle-v2-2p16` configuration), served
at a height a CPU test can hold from a cold key store: the key of a new
shape is built on the service's own jax backend, its SRS walk on the device,
and has to be the key the host builds and the key a restart loads from the
store, element for element; the proof is held to the benchmark's plain
reference, the frozen host oracle's bytes and the independent verifier.
Bytes and counts, no times."""

import concurrent.futures
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

SHAPE = {"kind": "merkle", "height": 2, "num_proofs": 4}   # n = 2^11
TAU = 0xDEADBEEF
SEED = 2 ** 31 + 42


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """One job SUBMITted to start_service("jax") over TCP, its store empty:
    the RESULT, the bucket the service built, its metrics and its store,
    and the frozen host oracle's bytes for the same job, proved on a
    thread beside the service (it shares no module with the program)."""
    from benchmark.reference import oracle
    from distributed_plonk_tpu.service import (JobSpec, ServiceClient,
                                               start_service)
    store_dir = str(tmp_path_factory.mktemp("store"))
    beside = concurrent.futures.ThreadPoolExecutor(1)
    oracle_proof = beside.submit(oracle.oracle_proof, dict(SHAPE, seed=SEED))
    svc, runtime = start_service("jax", port=0, prover_workers=1,
                                 store_dir=store_dir)
    try:
        with ServiceClient("127.0.0.1", svc.port) as client:
            job_id = client.submit(dict(SHAPE, seed=SEED))["job_id"]
            status = client.wait(job_id, timeout_s=1400, poll_s=0.2)
            assert status["state"] == "done", status
            header, proof = client.result(job_id)
        spec = JobSpec.from_wire(dict(SHAPE, seed=SEED))
        return {"status": status, "header": header, "proof": proof,
                "runtime": runtime, "spec": spec,
                "bucket": svc.buckets.get(spec),
                "backend": svc.buckets.backend, "store": svc.store,
                "metrics": svc.metrics.snapshot(),
                "oracle_proof": oracle_proof.result()["proof"]}
    finally:
        svc.shutdown()
        beside.shutdown()


def test_the_configuration_is_the_shape_served_here():
    with open(os.path.join(REPO, "benchmark", "configs",
                           "merkle-v2-2p16.json")) as f:
        conf = json.load(f)
    with open(os.path.join(REPO, "benchmark", "configs",
                           "merkle-v2cut-2p14.json")) as f:
        sibling = json.load(f)
    assert conf["job"] == dict(SHAPE, height=32, num_proofs=12)
    assert conf["reduced"] == ["num_proofs"]
    assert set(conf["reduced_why"]) == {"num_proofs"}
    assert conf["check"] == {"oracle_jobs": 1, "srs_tau": "0xDEADBEEF"}
    assert int(conf["check"]["srs_tau"], 16) == TAU
    assert conf["guarantees"] == sibling["guarantees"]
    assert conf["env"] == {} and conf["chips"] == 1
    assert conf["service"] == sibling["service"]
    # the source's own count, 5,173 constraints a proof of height 32: 12
    # fill 2^16 and 13 would not
    per_proof = 157 * 32 + 149
    assert conf["sizes"]["constraints"] == 12 * per_proof == 62076
    assert 12 * per_proof <= 65536 < 13 * per_proof
    assert conf["sizes"]["domain_size"] == 65536
    assert conf["sizes"]["quotient_domain_size"] == 8 * 65536
    assert conf["sizes"]["srs_points"] == 65536 + 32


def test_a_served_v2_job_equals_the_oracle_and_verifies_plainly(served):
    """SUBMIT -> RESULT on the jax backend (XLA:CPU here), one job alone as
    the one-client cell sends them, from a store that held no key: the key
    is built on the device and stored, and the proof is the oracle's."""
    from benchmark.lib import served as plain_served
    from benchmark.plain import statement

    spec = dict(SHAPE, seed=SEED)
    assert served["runtime"]["backend"] == "jax"
    assert served["bucket"].domain_size == 2048
    assert served["status"]["placement"] == "pool"
    assert served["proof"] == served["oracle_proof"]
    pub = statement.public_input(spec)
    assert served["header"]["public_input"] == [hex(x) for x in pub]
    assert plain_served.check_served(
        spec, served["proof"], served["header"]["public_input"], TAU) == {
        "pub_equal": True, "verified": True, "why": ""}
    metrics = served["metrics"]
    assert metrics["counters"]["bucket_misses"] == 1
    assert metrics["counters"]["bucket_builds_srs_device"] == 1
    for part in ("bucket_build", "bucket_build_srs",
                 "bucket_build_preprocess", "bucket_build_store"):
        assert metrics["histograms"][part]["count"] == 1, part


def test_the_key_built_on_the_device_is_the_host_built_key(served):
    """The device walk's key against the serial host walk's: the same
    powers, tau_g2, commit key, coefficients and commitments, and the same
    store blob."""
    from distributed_plonk_tpu.service import build_bucket_keys
    from distributed_plonk_tpu.store import keycache as KC

    bucket = served["bucket"]
    srs_h, pk_h, vk_h = build_bucket_keys(served["spec"])
    assert bucket.srs.powers_of_g1 == srs_h.powers_of_g1
    assert bucket.srs.tau_g2 == srs_h.tau_g2
    assert bucket.pk.ck == pk_h.ck and len(pk_h.ck) % 32 == 0
    assert bucket.pk.selectors == pk_h.selectors
    assert bucket.pk.sigmas == pk_h.sigmas
    assert bucket.vk.selector_comms == vk_h.selector_comms
    assert bucket.vk.sigma_comms == vk_h.sigma_comms
    assert KC.serialize_bucket(bucket.srs, bucket.pk, bucket.vk) \
        == KC.serialize_bucket(srs_h, pk_h, vk_h)


def test_a_restart_from_the_store_commits_through_the_same_layout(served):
    """The commit context of the key the device built and that of the key
    a restart loads from the store: the same padded width, window, group
    width and commitments, so the same programs at the same speed."""
    from distributed_plonk_tpu.backend import msm_jax
    from distributed_plonk_tpu.service import shape_key
    from distributed_plonk_tpu.store import keycache as KC

    be, bucket = served["backend"], served["bucket"]
    _srs, pk_s, vk_s, _meta = KC.load_bucket(served["store"],
                                             shape_key(served["spec"]))
    built, loaded = be._ctx(bucket.pk.ck), be._ctx(pk_s.ck)
    assert built is not loaded
    assert built.padded_n == loaded.padded_n == 2048 + 32
    assert built.c_batch == loaded.c_batch and built.signed == loaded.signed
    assert msm_jax._group_size_batch(built.padded_n, 5, built.c_batch,
                                     built.signed) \
        == msm_jax._group_size_batch(loaded.padded_n, 5, loaded.c_batch,
                                     loaded.signed)
    assert built.table is not None and loaded.table is not None
    polys = pk_s.selectors[:2]
    assert be.commit_many(bucket.pk.ck, polys) \
        == be.commit_many(pk_s.ck, polys) == vk_s.selector_comms[:2] \
        == bucket.vk.selector_comms[:2]


def test_the_host_backend_keeps_the_host_walk():
    """PythonBackend, the oracle's backend, walks the SRS on the host and
    builds the same key as no backend at all."""
    from distributed_plonk_tpu.backend.python_backend import PythonBackend
    from distributed_plonk_tpu.service import JobSpec, build_bucket_keys
    from distributed_plonk_tpu.service.metrics import Metrics
    from distributed_plonk_tpu.store import keycache as KC

    spec = JobSpec.from_wire({"kind": "toy", "gates": 8, "seed": 1})
    metrics = Metrics()
    on_host = build_bucket_keys(spec, backend=PythonBackend(),
                                metrics=metrics)
    snap = metrics.snapshot()
    assert "bucket_builds_srs_device" not in snap["counters"]
    assert snap["histograms"]["bucket_build_srs"]["count"] == 1
    assert KC.serialize_bucket(*on_host) \
        == KC.serialize_bucket(*build_bucket_keys(spec))
