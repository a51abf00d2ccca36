"""`circuit_template_hit_pct` (ISSUE 27): a data file of the `service_metric`
kind over the counters the witness-only Merkle builder keeps. It reads a
number from two snapshots of a service that has the counters, nothing (and
does not raise) from one that lacks them, as the parent commit does, and
resolves through the manifest once its entry is appended. The entry itself
is not in BENCHMARK.json yet: tests/benchmark/test_bench_phase_metrics.py
pins the list at twenty metrics, and a PR may not edit that file."""

import json
import os

import pytest

from bench_toy import REPO, make_toy_root
from benchmark.lib import manifest as M, readers

NAME = "circuit_template_hit_pct"
ENTRY = {"name": NAME, "unit": "%", "better": "higher",
         "source": "program_counter", "layer": "pool worker",
         "moves": "proofs_per_s", "workloads": ["merkle-v2cut.backlog"]}


def _spec():
    path = os.path.join(REPO, "benchmark", "layer_metrics", NAME + ".json")
    return dict(M.load_json(path), name=NAME, unit="%")


def _evidence(open_counters, close_counters):
    return readers.Evidence(metrics_open={"counters": open_counters},
                            metrics_close={"counters": close_counters})


def test_the_file_is_a_service_metric_of_the_pool_worker():
    spec = _spec()
    assert spec["kind"] == "service_metric"
    assert readers.READERS[spec["kind"]] is readers.read_service_metric
    assert spec["counter"] == "circuit_template_hits"
    assert spec["percent_of"] == "circuit_builds"
    # the layer's name, letter for letter, as the accepted metrics have it
    mates = {m["layer"] for m in M.load(REPO)["per_layer"]}
    assert spec["layer"] in mates and spec["moves"] == "proofs_per_s"


@pytest.mark.parametrize("before,after,want", [
    # the warm-up missed once and hit seven times; the window hit 15 of 15
    ({"circuit_builds": 8, "circuit_template_hits": 7},
     {"circuit_builds": 23, "circuit_template_hits": 22}, 100.0),
    # a shape nobody warmed: the window's first build is the miss
    ({}, {"circuit_builds": 4, "circuit_template_hits": 3}, 75.0),
    ({"circuit_builds": 2, "circuit_template_hits": 2},
     {"circuit_builds": 4, "circuit_template_hits": 2}, 0.0),
])
def test_it_reads_the_growth_between_two_snapshots(before, after, want):
    ev = _evidence(before, after)
    assert readers.read_service_metric(_spec(), ev) == want
    assert readers.read_all([_spec()], ev) == {
        NAME: {"value": want, "unit": "%"}}


@pytest.mark.parametrize("before,after", [
    # the parent commit: a service without the counters
    ({"jobs_completed": 0}, {"jobs_completed": 15}),
    # no merkle job in the window: nothing built, no share of nothing
    ({"circuit_builds": 8, "circuit_template_hits": 7},
     {"circuit_builds": 8, "circuit_template_hits": 7}),
    ({}, {}),
])
def test_it_reads_nothing_where_there_is_nothing(before, after):
    ev = _evidence(before, after)
    assert readers.read_service_metric(_spec(), ev) is None
    assert readers.read_all([_spec()], ev) == {}


def test_it_reads_the_programs_own_counters():
    from distributed_plonk_tpu.circuits import merkle_witness
    from distributed_plonk_tpu.service.jobs import JobSpec, build_circuit
    from distributed_plonk_tpu.service.metrics import Metrics
    shape = {"kind": "merkle", "height": 1, "num_proofs": 2}
    merkle_witness._templates.pop((1, 2, 3), None)
    metrics = Metrics()
    snaps = [metrics.snapshot()]
    for seed in (1, 2, 3):
        build_circuit(JobSpec.from_wire(dict(shape, seed=seed)), metrics)
        snaps.append(json.loads(json.dumps(metrics.snapshot())))
    spec = _spec()
    read = readers.read_service_metric
    assert read(spec, readers.Evidence(metrics_open=snaps[0],
                                       metrics_close=snaps[1])) == 0.0
    assert read(spec, readers.Evidence(metrics_open=snaps[0],
                                       metrics_close=snaps[2])) == 50.0
    assert read(spec, readers.Evidence(metrics_open=snaps[1],
                                       metrics_close=snaps[3])) == 100.0


def test_its_entry_resolves_once_appended(tmp_path):
    root = make_toy_root(tmp_path)
    man = M.load(root)
    assert NAME not in [m["name"] for m in man["per_layer"]]
    man["per_layer"].append(dict(ENTRY))
    assert M.problems(man) == []
    cell = M.Cell(man, root, "merkle-v2cut.backlog")
    assert cell.per_layer[-1] == _spec()
    assert cell.per_layer[-1]["layer"] == ENTRY["layer"]
    # the toy cell does not list it, and so does not load it
    assert NAME not in [s["name"] for s in M.Cell(man, root, "toy.loop").per_layer]
