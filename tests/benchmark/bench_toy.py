"""Shared by the benchmark's own tests (a module of its own name, so that no
test imports it as `conftest`, the name tests/conftest.py has): the repo root
on the path, and a toy-sized copy of the benchmark's data in a temporary root, so that the
harness's whole flow (service, window, reference, check) runs on the CPU in
seconds. No timing is asserted anywhere here."""

import json
import os
import shutil
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

TOY_JOB = {"kind": "toy", "gates": 8}   # n = 16, test_chip_smoke.py's size


def write_json(path, obj):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f)


def make_toy_root(tmp, clients=1, backend="python"):
    """A root holding BENCHMARK.json and benchmark/ data files for one toy
    configuration and one toy cell, made from the real manifest by adding
    entries and files only."""
    from benchmark.lib import manifest as M
    man = M.load(REPO)
    root = str(tmp)
    for sub in ("layer_metrics", "traffic", "configs"):
        shutil.copytree(os.path.join(REPO, "benchmark", sub),
                        os.path.join(root, "benchmark", sub))
    conf = M.load_json(os.path.join(REPO, man["configs"][0]["file"]))
    # the host oracle as the service's backend: the harness's flow, the
    # pool worker and the wire are the real ones and a toy prove takes two
    # seconds where XLA:CPU takes a minute to load its programs
    # (tests/test_chip_smoke.py rehearses the jax backend's served flow)
    conf["service"] = dict(conf["service"], backend=backend)
    conf["env"] = {}
    conf.update(name="toy", job=dict(TOY_JOB), reduced=[],
                sizes={"constraints": 10, "domain_size": 16,
                       "quotient_domain_size": 128, "srs_points": 32,
                       "proof_bytes": 944})
    write_json(os.path.join(root, "benchmark/configs/toy.json"), conf)
    write_json(os.path.join(root, "benchmark/traffic/toy-loop.json"),
               {"loop": "closed", "clients": clients, "outstanding": 1,
                "warmup_rounds": 1, "poll_s": 0.01, "wait_timeout_s": 600})
    man["configs"].append({"name": "toy", "source": "tests",
                           "file": "benchmark/configs/toy.json",
                           "reduced": [], "why": "toy"})
    man["workloads"].append({"name": "toy.loop", "config": "toy",
                             "traffic": "toy-loop", "chips": 1, "why": "toy"})
    for m in man["end_to_end"] + man["per_layer"]:
        if "workloads" in m:
            m["workloads"].append("toy.loop")
    # a one-client cell brings its latencies, as a later PR's would
    man["end_to_end"] += [
        {"name": name, "unit": "s", "better": "lower", "bound": 0.05,
         "source": "host_clock", "workloads": ["toy.loop"]}
        for name in ("latency_mean_s", "latency_max_s")]
    write_json(os.path.join(root, "BENCHMARK.json"), man)
    return root


@pytest.fixture
def toy_root(tmp_path):
    """A toy root; and the environment as it was, afterwards: a run sets the
    program's knobs for its whole process (DPT_JAX_TRACE, a configuration's
    `env`), which a test process must not keep for the tests that follow."""
    before = dict(os.environ)
    yield make_toy_root(tmp_path)
    for key in set(os.environ) - set(before):
        del os.environ[key]
    os.environ.update(before)
