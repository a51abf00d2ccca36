"""The reference's v1 job shape (one Merkle membership proof a circuit, the
job of the benchmark's `merkle-v1-2p13` configuration), served at a height
a CPU test can hold and held to the benchmark's plain reference: the frozen
host oracle's bytes and the independent verifier, which `correct` rests on
in the cell `merkle-v1.one-client`. Bytes and counts, no times."""

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

SHAPE = {"kind": "merkle", "height": 3, "num_proofs": 1}   # n = 2^10
TAU = 0xDEADBEEF


def test_the_configuration_is_the_shape_served_here():
    with open(os.path.join(REPO, "benchmark", "configs",
                           "merkle-v1-2p13.json")) as f:
        conf = json.load(f)
    assert conf["job"] == dict(SHAPE, height=32)
    assert conf["reduced"] == [] and conf["reduced_why"] == {}
    assert conf["check"] == {"oracle_jobs": 1, "srs_tau": "0xDEADBEEF"}
    # the one name in `env` is there for the parent of PR 38 alone (the
    # file's `env_why`): it is no knob of this program, which runs the
    # cell under its defaults
    from distributed_plonk_tpu.analysis import lint
    assert len(conf["env"]) == 1 and set(conf["env"]) == set(conf["env_why"])
    glossary = lint._load_knob_glossary()
    assert not any(lint._knob_documented(k, glossary) for k in conf["env"])
    assert int(conf["check"]["srs_tau"], 16) == TAU
    # the source's own count: num_proofs x (157 x height + 149) constraints
    assert conf["sizes"]["constraints"] == 157 * 32 + 149 == 5173
    assert conf["sizes"]["domain_size"] == 8192 >= 5173 > 4096
    assert conf["sizes"]["srs_points"] == 8192 + 32


def test_a_served_v1_job_equals_the_oracle_and_verifies_plainly():
    """SUBMIT -> RESULT over TCP on the jax backend (XLA:CPU here), one job
    alone in the service as the one-client cell sends them: pool placement,
    four commits of one device call each, all thirteen polynomials from the
    window table (a key of 1,027 points is wide enough for one). The key
    was built on the same backend first: its SRS walk on the device, its
    18 selector and sigma commitments in three calls (8 + 8 + 2) through
    the same commit context."""
    import chip_smoke
    from benchmark.lib import readers, served
    from benchmark.plain import statement
    from benchmark.reference import oracle

    seed = 2 ** 31 + 38
    (job,), metrics, runtime, _mesh = chip_smoke.serve_and_check(
        SHAPE, seeds=(seed,), wait_s=1400, aot_warm=False)
    spec = dict(SHAPE, seed=seed)
    assert runtime["backend"] == "jax" and runtime["domain_size"] == 1024
    assert job["placement"] == "pool" and job["oracle_equal"] is True
    assert not any(k.endswith("_finalize") for k in job["rounds"])
    assert job["proof"] == oracle.oracle_proof(spec)["proof"]
    pub = statement.public_input(spec)
    assert job["header"]["public_input"] == [hex(x) for x in pub]
    assert served.check_served(spec, job["proof"],
                               job["header"]["public_input"], TAU) == {
        "pub_equal": True, "verified": True, "why": ""}
    counters = metrics["counters"]
    assert counters["bucket_builds_srs_device"] == 1
    assert counters["msm_commit_calls"] == 3 + 4
    assert counters["msm_commit_chunks"] == 3 + 4
    assert counters["msm_commit_polys"] == 18 + 13
    assert counters["msm_commit_polys_preweighted"] == 18 + 13
    with open(os.path.join(REPO, "benchmark", "layer_metrics",
                           "msm_chunks_pct.json")) as f:
        chunks_pct = json.load(f)
    assert readers.read_service_metric(chunks_pct, readers.Evidence(
        metrics_open={"counters": {}}, metrics_close=metrics)) == 100.0
