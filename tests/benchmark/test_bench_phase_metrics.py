"""The ten per-layer metrics that read the program's phases, device-true round
times and the device ledger's fed/unfed account (ISSUE 26): data files of
existing reader kinds. Each loads through the manifest; on a program that has
none of it (the parent commit) each reads nothing and the line leaves it
out; on a toy run with the jax backend (XLA:CPU) every one reads a number,
and with the host oracle, which has no device ledger, the four phase metrics
still do. No time is asserted."""

import time

import pytest

from bench_toy import (DEVICE, LEDGER, NEW, PHASE, REPO,
                       hold_the_phase_metrics,
                       hold_the_stretches_to_their_marks, make_toy_root,
                       trace_line)
from benchmark.lib import harness, manifest as M, readers

SEED = 2 ** 31 + 2601


def test_the_new_metric_files_load_through_the_manifest():
    hold_the_phase_metrics(REPO)


def _specs():
    cell = M.Cell(M.load(REPO), REPO, "merkle-v2cut.backlog")
    return [s for s in cell.per_layer if s["name"] in NEW]


def test_on_a_program_without_phases_they_read_nothing_and_do_not_raise():
    """The parent's STATUS has no `phases` or `device` and its METRICS no
    ledger counters: the traced run's line leaves the new metrics out."""
    old_status = {"wait_s": 1.0, "run_s": 5.0, "rounds": {"round1": 1.0}}
    ev = readers.Evidence(
        statuses=[old_status],
        metrics_open={"counters": {"jobs_completed": 0}},
        metrics_close={"counters": {"jobs_completed": 3}})
    assert readers.read_all(_specs(), ev) == {}
    # pipeline_wait is optional (a job proved alone has none), so its file
    # adds and takes away a phase every job has: a STATUS with no phases at
    # all reads nothing rather than "waited 0 s"
    spec = [s for s in _specs() if s["name"] == "pipeline_wait_s"][0]
    assert spec["plus"][1:] == spec["minus"] == ["phases.circuit_build"]


def test_they_read_the_fields_the_issue_names():
    status = {
        "run_s": 11.0,
        "phases": {"circuit_build": 4.0, "guard_open": 0.1,
                   "checkpoint_save": 1.5, "serialize": 0.01,
                   "journal_done": 0.02, "trace_store": 0.03,
                   "unaccounted": 0.2},
        "device": {"round1": 0.5, "round2": 0.25, "round3": 1.0,
                   "round4": 0.125, "round5": 0.25}}
    piped = dict(status, phases=dict(status["phases"], pipeline_wait=2.0,
                                     self_verify=1.0))
    ev = readers.Evidence(
        statuses=[status, piped],
        metrics_open={"counters": {"device_unfed_s": 1.0,
                                   "phase_clock_s": 10.0,
                                   "device_unfed_s/circuit_build": 0.5,
                                   "device_unfed_s/worker_idle": 0.5}},
        metrics_close={"counters": {"device_unfed_s": 21.0,
                                    "phase_clock_s": 60.0,
                                    "device_unfed_s/circuit_build": 12.5,
                                    "device_unfed_s/worker_idle": 4.5}})
    got = {k: v["value"] for k, v in readers.read_all(_specs(), ev).items()}
    assert got == {
        "circuit_build_s": 4.0, "checkpoint_save_s": 1.5,
        "finish_s": pytest.approx(0.56),        # self_verify optional
        "pipeline_wait_s": 1.0,                 # 0 for the job proved alone
        "prove_device_s": 2.125, "round1_device_s": 0.5,
        "round3_device_s": 1.0, "device_unfed_pct": 40.0,
        "unfed_circuit_build_pct": 60.0, "unfed_worker_idle_pct": 20.0}


def _traced_toy_run(tmp_path, backend):
    import os
    before = dict(os.environ)
    try:
        root = make_toy_root(tmp_path, backend=backend)
        return harness.run_cell(root, "toy.loop", SEED, 0.2, 1,
                                time.monotonic(), require_tpu=False,
                                ref_workers=0, stretch_s=0.02)
    finally:
        for key in set(os.environ) - set(before):
            del os.environ[key]
        os.environ.update(before)


def test_on_the_jax_backend_every_new_metric_reads_a_number(tmp_path, capfd):
    res = _traced_toy_run(tmp_path, "jax")
    assert res["correct"] is True
    # XLA:CPU's threads fill these traces, and the mark is found in each
    hold_the_stretches_to_their_marks(trace_line(capfd.readouterr().out))
    for name in NEW:
        assert name in res["metrics"], name    # None would leave it out
        assert res["metrics"][name]["value"] >= 0
    assert res["metrics"]["device_unfed_pct"]["value"] <= 100.0
    # and the ten that were there keep reading what they read
    assert {"queue_wait_s", "worker_run_s", "host_outside_rounds_s",
            "round1_s", "round3_s", "compiles_in_window"} <= set(res["metrics"])


def test_on_the_host_oracle_the_phase_metrics_still_read_one(tmp_path):
    res = _traced_toy_run(tmp_path, "python")
    assert res["correct"] is True
    assert set(PHASE) <= set(res["metrics"])
    # no device ledger: nothing to read, so nothing on the line
    assert not set(DEVICE + LEDGER) & set(res["metrics"])
