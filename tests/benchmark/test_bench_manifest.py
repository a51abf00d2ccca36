"""BENCHMARK.json and the data files it names."""

import json
import os

import pytest

from bench_toy import REPO, make_toy_root, write_json
from benchmark.lib import manifest as M, readers

MAN = M.load(REPO)
CELLS = [w["name"] for w in MAN["workloads"]]


def test_manifest_keeps_to_the_contract():
    assert M.problems(MAN) == []
    assert set(MAN) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 64 << 10
    assert isinstance(MAN["run_seconds"], int) and 1 <= MAN["run_seconds"] <= 51
    assert MAN["command"][1].startswith(MAN["paths"][0] + "/")
    for entry, keys in ((MAN["configs"], {"name", "source", "file", "reduced",
                                          "why"}),
                        (MAN["workloads"], {"name", "config", "traffic",
                                            "chips", "why"})):
        for e in entry:
            assert set(e) == keys, e
    for m in MAN["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
    for m in MAN["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
    # every cell reports setup_s, another end-to-end metric and a per-layer one
    for cell in CELLS:
        e2e = [m["name"] for m in MAN["end_to_end"]
               if cell in m.get("workloads", CELLS)]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert any(cell in m.get("workloads", CELLS) for m in MAN["per_layer"])
    # at most half of the cells, rounded down, or one, may ask for four chips
    four = sum(w["chips"] == 4 for w in MAN["workloads"])
    assert four <= max(1, len(CELLS) // 2)


def test_problems_are_found():
    bad = json.loads(json.dumps(MAN))
    bad["workloads"][0]["name"] = "has space"
    bad["end_to_end"][0]["unit"] = "proofs per second"
    bad["per_layer"][0]["moves"] = "nothing"
    found = " ".join(M.problems(bad))
    assert "not a name" in found and "bad unit" in found and "moves no" in found


@pytest.mark.parametrize("cell_name", CELLS)
def test_every_cell_resolves_its_files(cell_name):
    cell = M.Cell(MAN, REPO, cell_name)
    entry = [c for c in MAN["configs"] if c["name"] == cell.config_name][0]
    assert cell.config["name"] == entry["name"]
    assert cell.config["reduced"] == entry["reduced"]
    assert cell.config["chips"] == cell.chips
    assert cell.config["source"] == entry["source"]
    assert cell.traffic["loop"] == "closed" and cell.traffic["clients"] >= 1
    assert cell.job_mix == [(cell.config["job"], 1)]
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s", "proofs_per_s"}
    for spec in cell.per_layer:
        assert spec["kind"] in readers.READERS, spec["name"]
        entry = [m for m in MAN["per_layer"] if m["name"] == spec["name"]][0]
        assert spec["layer"] == entry["layer"]
        assert spec["moves"] == entry["moves"] == "proofs_per_s"
    # the daemon's defaults, as scripts/serve.py has them
    assert cell.config["service"] == {"backend": "jax", "prover_workers": 2,
                                      "max_batch": 8, "queue_depth": 64}


def test_a_later_pr_adds_by_files_and_entries_only(tmp_path):
    """One new configuration, mix, cell (on four chips, with the placement
    knobs chip_smoke.py --chips 4 sets) and per-layer metric of an existing
    kind: files and entries, no edit to what is there."""
    root = make_toy_root(tmp_path)
    man = M.load(root)
    conf = M.load_json(os.path.join(root, "benchmark/configs/toy.json"))
    conf.update(name="toy-mesh", chips=4,
                env={"DPT_PLACE_LARGE_MIN": 16, "DPT_MESH_LEASE": 4})
    write_json(os.path.join(root, "benchmark/configs/toy-mesh.json"), conf)
    write_json(os.path.join(root, "benchmark/traffic/two-kinds.json"),
               {"loop": "closed", "clients": 2, "warmup_rounds": 1,
                "configs": [{"config": "toy-mesh", "weight": 3},
                            {"config": "toy", "weight": 1}]})
    write_json(os.path.join(root, "benchmark/layer_metrics/round5_s.json"),
               {"kind": "status_field", "plus": ["rounds.round5"],
                "layer": "prover rounds", "moves": "proofs_per_s"})
    man["configs"].append({"name": "toy-mesh", "source": "tests", "reduced": [],
                           "file": "benchmark/configs/toy-mesh.json",
                           "why": "x"})
    man["workloads"].append({"name": "toy.mesh4", "config": "toy-mesh",
                             "traffic": "two-kinds", "chips": 4, "why": "x"})
    man["per_layer"].append({"name": "round5_s", "unit": "s", "better": "lower",
                             "source": "program_span",
                             "layer": "prover rounds", "moves": "proofs_per_s",
                             "workloads": ["toy.mesh4"]})
    assert M.problems(man) == []
    cell = M.Cell(man, root, "toy.mesh4")
    assert cell.chips == 4 and cell.config["env"]["DPT_MESH_LEASE"] == 4
    assert [w for _j, w in cell.job_mix] == [3, 1]
    assert [s["name"] for s in cell.per_layer][-1] == "round5_s"
    ev = readers.Evidence(statuses=[{"rounds": {"round5": 1.5}},
                                    {"rounds": {"round5": 2.5}}])
    assert readers.read_all(cell.per_layer, ev)["round5_s"] == {
        "value": 2.0, "unit": "s"}
    with pytest.raises(M.ManifestError):
        M.Cell(man, root, "no-such-cell")
