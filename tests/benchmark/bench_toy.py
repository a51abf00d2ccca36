"""Shared by the benchmark's own tests (a module of its own name, so that no
test imports it as `conftest`, the name tests/conftest.py has): the repo root
on the path, and a toy-sized copy of the benchmark's data in a temporary root, so that the
harness's whole flow (service, window, reference, check) runs on the CPU in
seconds; and what the manifest of any root is held to, as helpers that take
the root, so that a root extended as a later PR will extend it is held to
the same. No timing is asserted anywhere here."""

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
# a toy job is no merkle job and builds over no template: the toy cell does
# not report this metric, so its list does not gain the cell
NOT_TOY = ("circuit_template_hit_pct",)
REAL_CELL = "merkle-v2cut.backlog"
# ISSUE 26's ten, entered after the first ten
PHASE = ["circuit_build_s", "checkpoint_save_s", "finish_s",
         "pipeline_wait_s"]
DEVICE = ["prove_device_s", "round1_device_s", "round3_device_s"]
LEDGER = ["device_unfed_pct", "unfed_circuit_build_pct",
          "unfed_worker_idle_pct"]
NEW = PHASE + DEVICE + LEDGER


def write_json(path, obj):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f)


def copy_data_files(root):
    """The benchmark's data files, copied under `root` as they are."""
    for sub in ("layer_metrics", "traffic", "configs"):
        shutil.copytree(os.path.join(REPO, "benchmark", sub),
                        os.path.join(root, "benchmark", sub))


def make_toy_root(tmp, clients=1, backend="python"):
    """A root holding BENCHMARK.json and benchmark/ data files for one toy
    configuration and one toy cell, made from the real manifest by adding
    entries and files only."""
    from benchmark.lib import manifest as M
    man = M.load(REPO)
    root = str(tmp)
    copy_data_files(root)
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
        if "workloads" in m and m["name"] not in NOT_TOY:
            m["workloads"].append("toy.loop")
    # a one-client cell brings its latencies, as a later PR's would
    man["end_to_end"] += [
        {"name": name, "unit": "s", "better": "lower", "bound": 0.05,
         "source": "host_clock", "workloads": ["toy.loop"]}
        for name in ("latency_mean_s", "latency_max_s")]
    write_json(os.path.join(root, "BENCHMARK.json"), man)
    return root


def hold_to_the_contract(root):
    """The contract's limits on BENCHMARK.json that need no run."""
    from benchmark.lib import manifest as M
    man = M.load(root)
    cells = [w["name"] for w in man["workloads"]]
    assert M.problems(man) == []
    assert set(man) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(root, "BENCHMARK.json")) < 64 << 10
    assert isinstance(man["run_seconds"], int) and 1 <= man["run_seconds"] <= 51
    assert man["command"][1].startswith(man["paths"][0] + "/")
    for entry, keys in ((man["configs"], {"name", "source", "file", "reduced",
                                          "why"}),
                        (man["workloads"], {"name", "config", "traffic",
                                            "chips", "why"})):
        for e in entry:
            assert set(e) == keys, e
    for m in man["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
    for m in man["per_layer"]:
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
    # every cell reports setup_s, another end-to-end metric and a per-layer one
    for cell in cells:
        e2e = [m["name"] for m in man["end_to_end"]
               if cell in m.get("workloads", cells)]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert any(cell in m["workloads"] for m in man["per_layer"])
    # at most half of the cells, rounded down, or one, may ask for four chips
    four = sum(w["chips"] == 4 for w in man["workloads"])
    assert four <= max(1, len(cells) // 2)


def hold_the_cell_to_its_files(root, cell_name):
    """A cell resolves its configuration, traffic and metric files, and
    they say what its entries say."""
    from benchmark.lib import manifest as M, readers
    man = M.load(root)
    cell = M.Cell(man, root, cell_name)
    entry = [c for c in man["configs"] if c["name"] == cell.config_name][0]
    assert cell.config["name"] == entry["name"]
    assert cell.config["reduced"] == entry["reduced"]
    assert cell.config["chips"] == cell.chips
    assert cell.config["source"] == entry["source"]
    assert cell.traffic["loop"] == "closed" and cell.traffic["clients"] >= 1
    assert cell.job_mix == [(cell.config["job"], 1)]
    e2e = {m["name"] for m in cell.end_to_end}
    assert e2e >= {"setup_s", "proofs_per_s"}
    for spec in cell.per_layer:
        assert spec["kind"] in readers.READERS, spec["name"]
        entry = [m for m in man["per_layer"] if m["name"] == spec["name"]][0]
        assert spec["layer"] == entry["layer"]
        # a metric moves an end-to-end metric that this cell reports
        assert spec["moves"] == entry["moves"] and entry["moves"] in e2e
    # the daemon's defaults, as scripts/serve.py has them
    assert cell.config["service"] == {"backend": "jax", "prover_workers": 2,
                                      "max_batch": 8, "queue_depth": 64}


def hold_the_phase_metrics(root):
    """ISSUE 26's ten metrics are where they were entered, on the cell they
    were entered for, whatever a later PR has appended."""
    from benchmark.lib import manifest as M
    man = M.load(root)
    assert M.problems(man) == []
    cell = M.Cell(man, root, REAL_CELL)
    specs = {s["name"]: s for s in cell.per_layer}
    assert set(NEW) <= set(specs) and len(specs) >= 20
    # appended after the ten that were there, nothing put in the middle
    assert [m["name"] for m in man["per_layer"]][10:20] == NEW
    for name in NEW:
        entry = [m for m in man["per_layer"] if m["name"] == name][0]
        assert REAL_CELL in entry["workloads"]
        assert entry["moves"] == specs[name]["moves"] == "proofs_per_s"
        assert entry["better"] == "lower"
        assert entry["layer"] == specs[name]["layer"]
        assert entry["unit"] == ("%" if name.endswith("_pct") else "s")
        kind = "service_metric" if name in LEDGER else "status_field"
        assert specs[name]["kind"] == kind
        assert entry["source"] == ("program_counter" if name in LEDGER
                                   else "program_span")


def trace_line(out):
    """The `"phase": "trace"` line of a traced run's standard output."""
    lines = [json.loads(l) for l in out.splitlines()
             if l.startswith('{"phase": "trace"')]
    assert len(lines) == 1
    return lines[0]


def hold_the_stretches_to_their_marks(line):
    """Every stretch of a traced run's log line was found marked in its own
    trace, and carries the mark's seconds beside the host clock's. On the
    CPU there is no device plane, so no busy time to hold under them."""
    assert line["stretches"] and line["read_s"] >= 0
    for st in line["stretches"]:
        assert set(st) == {"began_s", "seconds", "mark_s", "xspace_bytes",
                           "op_events", "busy_s", "uncut_busy_s"}
        assert st["mark_s"] is not None and st["mark_s"] > 0
        assert st["seconds"] > 0 and st["xspace_bytes"] > 0
        assert st["busy_s"] is None and st["uncut_busy_s"] is None
        assert st["op_events"] == 0


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
