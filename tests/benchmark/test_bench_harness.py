"""The harness's whole flow at a toy size on the CPU: a sound run comes out
correct with the contract's result object, and with the timed path broken
underneath `correct` comes out false, once for each fault a cell of served
proofs can have. The look for a chip is skipped here (`require_tpu=False`)
and tested on its own; nothing is timed."""

import json
import os
import subprocess
import sys
import time

import pytest

from bench_toy import (  # noqa: F401 - toy_root is a fixture
    REPO, hold_the_stretches_to_their_marks, make_toy_root, toy_root,
    trace_line)
from benchmark.lib import faults, harness, manifest as M

SEED = 2 ** 31 + 77


def _run(root, trace=0, seed=SEED):
    return harness.run_cell(root, "toy.loop", seed, 0.2, trace,
                            time.monotonic(), require_tpu=False, ref_workers=0,
                            stretch_s=0.02)


def test_sound_run_is_correct_and_prints_the_contracts_object(toy_root, capfd):
    res = _run(toy_root)
    assert list(res) == ["correct", "attempted", "failed", "metrics", "device",
                         "checks"]      # the numbers compared come last
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 1
    assert set(res["metrics"]) == {"proofs_per_s", "latency_mean_s",
                                   "latency_max_s", "setup_s"}
    for m in res["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    assert set(res["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    assert all(c["value"] <= c["limit"] or name in ("oracle_compared",
                                                    "answered")
               for name, c in res["checks"].items())
    assert res["checks"]["oracle_compared"] == {"value": 1, "limit": 1}
    out, err = capfd.readouterr()
    err = err.strip().splitlines()
    # the oracle's prove, fanned out by the width rule, ended before the
    # window opened, and its line says so on the one monotonic clock
    oracle = [json.loads(l) for l in out.splitlines()
              if '"phase": "oracle"' in l]
    assert len(oracle) == 1
    jobs = M.load_json(os.path.join(toy_root, "benchmark", "configs",
                                    "toy.json"))["check"]["oracle_jobs"]
    assert oracle[0]["oracle_workers"] == max(
        1, (len(os.sched_getaffinity(0)) - 2) // jobs)
    assert oracle[0]["ended_before_window_s"] >= 0
    assert err[-1] == "correct: true"
    assert err[-2].startswith("check answered: ")
    json.dumps(res)
    # the second run of a cell in a checkout builds no key bucket
    store = os.path.join(toy_root, "benchmark", ".state", "toy.loop", "store")
    assert os.path.isdir(store)
    res2 = _run(toy_root, seed=SEED + 1)
    assert res2["correct"] is True
    out = capfd.readouterr().out
    warm = [json.loads(l) for l in out.splitlines() if '"phase": "warm"' in l]
    assert warm[-1]["counters"].get("bucket_disk_hits") == 1
    assert "bucket_misses" not in warm[-1]["counters"]


def test_four_independent_clients_are_waited_for_and_all_compared(tmp_path):
    """The backlog mix at a toy size: nothing in flight at the close, and
    the first job of every client byte-compared with the oracle."""
    before = dict(os.environ)
    try:
        res = _run(make_toy_root(tmp_path, clients=4))
    finally:
        for key in set(os.environ) - set(before):
            del os.environ[key]
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 4
    assert res["checks"]["oracle_compared"] == {"value": 4, "limit": 4}
    assert res["checks"]["answered"]["value"] == res["attempted"]


@pytest.mark.parametrize("fault, failing", [
    ("answer_altered", "verify_failures"),
    ("blinding_reused", "oracle_byte_diffs"),
    ("answer_missing", "unanswered"),
])
def test_a_broken_timed_path_is_not_correct(toy_root, fault, failing, capfd):
    with faults.planted(fault):
        if fault == "answer_missing":
            # the warm-up itself cannot finish: the run dies with no result
            with pytest.raises(RuntimeError, match="warm-up"):
                _run(toy_root)
            return
        res = _run(toy_root)
    assert res["correct"] is False
    bad = {k for k, c in res["checks"].items()
           if k not in ("oracle_compared", "answered")
           and c["value"] > c["limit"]}
    assert failing in bad
    if fault == "blinding_reused":
        assert bad == {"oracle_byte_diffs"}     # it verifies all the same
        assert res["failed"] == 0
    else:
        assert res["failed"] == res["attempted"]
        assert "proofs_per_s" not in res["metrics"]
    assert capfd.readouterr().err.strip().splitlines()[-1] == "correct: false"


def test_an_answer_that_never_comes_is_not_correct(toy_root):
    """The fault planted after the warm-up, as benchmark/control.py does."""
    ses = harness.Session(toy_root, "toy.loop", require_tpu=False,
                          ref_workers=0)
    try:
        futs = ses.oracle(SEED)
        ses.open(SEED)
        with faults.planted("answer_missing"):
            win = ses.window(SEED, 0.2)[0]
        correct, checks, good = ses.judge(win, futs)
        assert not correct and not good
        assert checks["unanswered"]["value"] >= 1
        assert not checks["oracle_compared"]["holds"]
        win = ses.window(SEED, 0.2)[0]          # and sound again afterwards
        assert ses.judge(win, futs)[0] is True
    finally:
        ses.close()


def test_the_mark_comes_back_in_every_stretch_of_a_real_trace():
    """A real ProfilerSession around the harness's own `_take`: the host
    annotation reaches the trace, once, outside any device plane."""
    from benchmark.lib import tracered
    st = harness.TraceStretches(time.monotonic, SEED, 0.3, stretch_s=0.01)
    for _ in st.offsets:
        st._take()
    traces = st.read()
    assert len(traces) == len(st.taken) == 3
    for (t0, t1, xspace), tr in zip(st.taken, traces):
        assert t1 > t0 and len(xspace) > 0
        marks = [e for e in tr.events if e.name == tracered.MARK]
        assert len(marks) == 1
        assert not tracered.DEVICE_PLANE_RE.match(marks[0].plane)
        assert tr.window_s == marks[0].dur_ns / 1e9 > 0
        assert tracered.window(tr.events) == (
            marks[0].start_ns, marks[0].start_ns + marks[0].dur_ns)
        # nothing ran on a device here: the stretch is idle for its length
        assert tracered.busy(tr.events) is None
        assert harness.TraceStretches.nothing_ran(xspace) is True
    assert tracered.busy_over([(tr.events, tr.window_s)
                               for tr in traces]) is None


def test_traced_run_reports_the_per_layer_metrics_it_can_read(toy_root, capfd):
    res = _run(toy_root, trace=1)
    assert res["correct"] is True
    hold_the_stretches_to_their_marks(trace_line(capfd.readouterr().out))
    cell = M.Cell(M.load(toy_root), toy_root, "toy.loop")
    names = {s["name"] for s in cell.per_layer}
    assert set(res["metrics"]) <= names
    # the host readings are there; with no device plane in a CPU trace the
    # trace readers find nothing and return nothing, never 0
    assert {"queue_wait_s", "worker_run_s", "host_outside_rounds_s",
            "round1_s", "round3_s", "compiles_in_window"} <= set(res["metrics"])
    assert not {"device_op_mean_us", "device_idle_pct"} & set(res["metrics"])
    assert res["metrics"]["compiles_in_window"]["value"] == 0
    # one client, one job outstanding: no prove was ever pipelined and the
    # program never created the counter; the line says 0, not nothing
    assert res["metrics"]["pipelined_jobs_pct"] == {"value": 0.0, "unit": "%"}
    assert "busy_s" not in res["device"]
    # an empty trace is one in which nothing ran; a large one is not read
    assert harness.TraceStretches.nothing_ran(b"") is True
    assert harness.TraceStretches.nothing_ran(bytes(1 << 20)) is False


def test_the_look_for_a_chip_refuses_the_cpu(monkeypatch, capfd):
    man = M.load(REPO)
    cell = M.Cell(man, REPO, man["workloads"][0]["name"])
    with pytest.raises(SystemExit) as exc:
        harness.look_for_chip(cell, True)
    assert exc.value.code == 2
    assert "DPT_PALLAS_INTERPRET" in capfd.readouterr().err
    monkeypatch.delenv("DPT_PALLAS_INTERPRET")
    with pytest.raises(SystemExit):
        harness.look_for_chip(cell, True)
    assert "no TPU" in capfd.readouterr().err


@pytest.mark.parametrize("args, why", [
    (["--workload", "merkle-v2cut.backlog"], "no TPU"),
    (["--workload", "no-such-cell"], "no workload"),
])
def test_the_command_prints_no_result_without_its_chip(args, why):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("DPT_PALLAS_INTERPRET", None)
    done = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"), *args,
         "--seed", str(SEED), "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300, env=env, cwd=REPO)
    assert done.returncode not in (0, None)
    assert why in done.stderr
    assert '"correct"' not in done.stdout


def test_the_control_script_sees_sound_windows_pass_and_controls_fail(
        toy_root, capfd):
    sys.path.insert(0, os.path.join(REPO, "benchmark"))
    import control
    rc = control.main(["--workload", "toy.loop", "--seeds", "5,6",
                       "--seconds", "0.2", "--faults",
                       "answer_altered,blinding_reused,answer_missing"],
                      root=toy_root, require_tpu=False, ref_workers=0)
    lines = [json.loads(l) for l in capfd.readouterr().out.splitlines()
             if l.startswith('{"window"')]
    assert rc == 0 and len(lines) == 2 * (1 + 3 + 1)
    assert all(l["as_expected"] for l in lines)
    assert [l["correct"] for l in lines if "correct" in l] == \
        [True, False, False, False] * 2
