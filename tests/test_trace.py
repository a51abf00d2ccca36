"""Tracer unit tests + per-round prove instrumentation + merge/export."""

import json
import math

from distributed_plonk_tpu.trace import (NULL_TRACER, Tracer, merge_traces,
                                         msm_flops, ntt_flops,
                                         to_chrome_trace)


def test_tracer_spans_nest_and_total():
    tr = Tracer()
    with tr.span("round1"):
        with tr.span("ifft", polys=5):
            pass
    with tr.span("round2"):
        pass
    spans = [e["span"] for e in tr.events]
    assert spans == ["round1/ifft", "round1", "round2"]
    assert tr.events[0]["polys"] == 5
    tot = tr.totals(depth=1)
    assert set(tot) == {"round1", "round2"}
    data = json.loads(tr.to_json())
    assert len(data["events"]) == 3


def test_spans_carry_ids_timestamps_and_parents():
    """The PR 9 satellite fix: spans without start times could not be
    ordered or reconstructed — every event now carries ts/sid/parent."""
    tr = Tracer(proc="p")
    with tr.span("outer") as outer_sid:
        with tr.span("inner"):
            pass
    inner, outer = tr.events
    assert len(tr.trace_id) == 32 and len(inner["sid"]) == 16
    assert inner["parent"] == outer_sid == outer["sid"]
    assert "parent" not in outer          # root span
    # start order is reconstructable: outer started first, and the
    # inner span lies within the outer's [ts, ts+dur] window
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur_s"] <= outer["ts"] + outer["dur_s"] + 1e-3
    d = tr.dump()
    assert d["proc"] == "p" and d["pid"] and d["host"]


def test_overlapping_spans_reconstruct():
    """Concurrent spans (the PR 6 overlapped canaries, pool concurrency)
    are distinguishable by their timestamps, not just durations."""
    import threading
    tr = Tracer()
    gate = threading.Barrier(2)

    def one(name):
        with tr.span(name):
            gate.wait(timeout=5)

    ts = [threading.Thread(target=one, args=(f"job{i}",)) for i in range(2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    a, b = sorted(tr.events, key=lambda e: e["ts"])
    # both ran simultaneously: the second started before the first ended
    assert b["ts"] < a["ts"] + a["dur_s"]
    assert a["tid"] != b["tid"]


def test_context_inject_extract_links_processes():
    parent = Tracer(proc="client")
    with parent.span("request") as sid:
        ctx = parent.context()
    assert ctx == {"trace_id": parent.trace_id, "parent_id": sid}
    child = Tracer.from_context(ctx, proc="server")
    assert child.trace_id == parent.trace_id
    with child.span("serve"):
        pass
    assert child.events[0]["parent"] == sid
    # explicit parent override (the per-frame linkage receivers use)
    with child.span("serve2", parent="ab" * 8):
        pass
    assert child.events[1]["parent"] == "ab" * 8
    # synthetic spans inherit the remote parent too (the queue-wait
    # event must not fall out of the client's tree)
    child.add_event("queued", ts=1.0, dur_s=0.1)
    assert child.events[2]["parent"] == sid
    # garbage context degrades to a fresh root trace, never an error
    fresh = Tracer.from_context(None)
    assert len(fresh.trace_id) == 32


def test_merge_applies_offsets_and_sorts():
    a = Tracer(proc="dispatcher")
    with a.span("fleet"):
        pass
    b = Tracer.from_context(a.context(), proc="worker")
    with b.span("kernel"):
        pass
    # pretend worker's clock runs 100s ahead: offset correction must
    # pull its spans back onto the dispatcher's timeline
    b_dump = b.dump()
    for ev in b_dump["events"]:
        ev["ts"] += 100.0
    merged = merge_traces([a.dump(), b_dump], offsets=[0.0, 100.0])
    assert merged["trace_id"] == a.trace_id
    assert [p["proc"] for p in merged["processes"]] == ["dispatcher",
                                                       "worker"]
    ts = [e["ts"] for e in merged["events"]]
    assert ts == sorted(ts)
    assert max(ts) - min(ts) < 10  # the 100s skew was corrected away
    assert {e["proc"] for e in merged["events"]} == {"dispatcher", "worker"}


def test_chrome_trace_schema():
    tr = Tracer(proc="x")
    with tr.span("a", polys=3):
        with tr.span("b"):
            pass
    ct = to_chrome_trace(merge_traces([tr.dump()]))
    meta = [e for e in ct["traceEvents"] if e["ph"] == "M"]
    assert meta and meta[0]["name"] == "process_name"
    xs = [e for e in ct["traceEvents"] if e["ph"] == "X"]
    assert len(xs) == 2
    for e in xs:
        for key in ("name", "cat", "ts", "dur", "pid", "tid", "args"):
            assert key in e, (key, e)
        assert e["ts"] >= 0 and e["dur"] >= 0
    assert any(e["args"].get("polys") == 3 for e in xs)
    assert ct["otherData"]["trace_id"] == tr.trace_id
    json.dumps(ct)  # the export must be pure JSON


def test_synthetic_events_and_flops_models():
    tr = Tracer()
    sid = tr.add_event("service/queued", ts=123.0, dur_s=0.5, job_id="j1")
    assert tr.events[0]["ts"] == 123.0 and tr.events[0]["sid"] == sid
    assert ntt_flops(1) == 0
    assert ntt_flops(8) == 4 * 3 * (3 * 32 * 32 * 2)
    assert ntt_flops(8, 2) == 2 * ntt_flops(8)
    assert msm_flops(10) == 10 * 32 * 11 * (3 * 48 * 48 * 2)


def test_null_tracer_noop():
    with NULL_TRACER.span("x") as sid:
        assert sid is None
    assert NULL_TRACER.totals() == {}
    assert NULL_TRACER.context() is None
    assert NULL_TRACER.dump() == {}


def test_prove_emits_round_spans(proven):
    import random
    from distributed_plonk_tpu.prover import prove
    from distributed_plonk_tpu.backend.python_backend import PythonBackend

    ckt, pk, vk, proof = proven
    tr = Tracer()
    proof2 = prove(random.Random(1), ckt, pk, PythonBackend(), tracer=tr)
    # same rng seed => identical proof; tracing must not perturb the prover
    assert proof2.wires_poly_comms == proof.wires_poly_comms
    tot = tr.totals(depth=1)
    assert set(tot) == {"round1", "round2", "round3", "round4", "round5"}
    assert all(v >= 0 for v in tot.values())
    sub = [e["span"] for e in tr.events]
    assert "round3/quotient_evals" in sub and "round1/commit_wires" in sub
    # kernel spans carry the flops/bytes attribution the MFU gauges read
    commits = [e for e in tr.events if e["span"] == "round1/commit_wires"]
    assert commits[0]["flops"] > 0 and commits[0]["data_bytes"] > 0
    # one timeline: every span under the one trace id, ts-ordered spans
    # reconstruct the round sequence
    rounds = [e for e in tr.events if e["span"].startswith("round")
              and "/" not in e["span"]]
    assert [e["span"] for e in sorted(rounds, key=lambda e: e["ts"])] == \
        ["round1", "round2", "round3", "round4", "round5"]


# --- metrics export (service/metrics.py satellites) --------------------------

def test_histogram_snapshot_reports_samples_and_clamps():
    from distributed_plonk_tpu.service.metrics import Histogram
    h = Histogram()
    h.record(1.0)
    h.record(2.0)
    snap = h.snapshot()
    # the old int(p*len) indexed the max for ANY p >= 0.5 at 2 samples;
    # nearest-rank gives the median
    assert snap["p50_s"] == 1.0
    assert snap["p99_s"] == 2.0
    assert snap["samples"] == 2 and snap["count"] == 2
    one = Histogram()
    one.record(3.0)
    s1 = one.snapshot()
    assert s1["p50_s"] == s1["p99_s"] == 3.0 and s1["samples"] == 1
    # past the reservoir cap, samples < count (percentiles are estimates)
    big = Histogram()
    for i in range(3000):
        big.record(float(i))
    sb = big.snapshot()
    assert sb["count"] == 3000 and sb["samples"] == 2048
    assert math.isclose(sb["p50_s"], 1500.0, rel_tol=0.2)


def test_prometheus_exposition():
    from distributed_plonk_tpu.service.metrics import Metrics
    m = Metrics()
    m.inc("jobs_completed", 3)
    m.gauge("queue_depth", 7)
    m.observe("job_run", 0.5)
    m.observe("prove_round/round1", 0.25)
    text = m.to_prometheus(extra_gauges={"queue_high_water": 9})
    assert "# TYPE dpt_jobs_completed_total counter" in text
    assert "dpt_jobs_completed_total 3" in text
    assert "dpt_queue_depth 7" in text
    assert "dpt_queue_high_water 9" in text
    assert 'dpt_job_run_seconds{quantile="0.5"} 0.5' in text
    assert "dpt_prove_round_round1_seconds_count 1" in text
    assert "dpt_uptime_s" in text
    # exposition-format discipline: every line is `name value` or a
    # comment; names are [a-zA-Z0-9_:] only
    import re
    for line in text.strip().splitlines():
        if line.startswith("#"):
            continue
        name = line.split(None, 1)[0]
        assert re.fullmatch(r"[a-zA-Z_][a-zA-Z0-9_]*(\{[^}]*\})?", name), line


def test_observe_kernels_mfu_gauges():
    from distributed_plonk_tpu.service.metrics import DEVICE_PEAKS, Metrics
    m = Metrics()
    events = [{"span": "round1/commit_wires", "dur_s": 2.0, "flops": 4e9},
              {"span": "round1", "dur_s": 1.0}]        # no flops: skipped
    m.observe_kernels(events, device_kind="TPU v5 lite")
    g = m.snapshot()["gauges"]
    assert g["kernel_commit_wires_gflops"] == 2.0
    peak = DEVICE_PEAKS["TPU v5 lite"]["bf16_tflops"] * 1e12
    assert g["mfu_commit_wires_pct"] == round(100.0 * 4e9 / (2.0 * peak), 4)
    assert not any(k.endswith("round1_gflops") for k in g)
    # a device kind with no published peak (or no device at all) still
    # reports throughput but never an MFU against a made-up denominator
    for kind in ("some future chip", None):
        m = Metrics()
        m.observe_kernels(events, device_kind=kind)
        g = m.snapshot()["gauges"]
        assert g["kernel_commit_wires_gflops"] == 2.0
        assert not any(k.startswith("mfu_") for k in g)


def test_obs_lint_catches_undocumented_metric():
    from distributed_plonk_tpu.analysis.lint import lint_source
    doc = ("Glossary:\n"
           "    jobs_completed   terminal outcomes\n"
           "    faults_injected_*  chaos family\n"
           "    store_hits       scoped store metric\n")
    src = ("class A:\n"
           "    def f(self):\n"
           "        self.metrics.inc('jobs_completed')\n"
           "        self.metrics.inc('faults_injected_kill')\n"
           "        self.metrics.inc('hits')\n"            # store_hits
           "        self.metrics.observe('ghost_seconds', 1)\n")
    found = lint_source(src, kinds=("obs",), glossary_doc=doc)
    assert len(found) == 1 and found[0].code == "OBS01"
    assert "ghost_seconds" in found[0].message
    # prose in the DESCRIPTION column must not document a metric: only
    # the name column (before the >=2-space gap) counts
    prose = ("class B:\n"
             "    def f(self):\n"
             "        self.metrics.inc('outcomes')\n"
             "        self.metrics.inc('terminal')\n")
    doc2 = "Glossary:\n    jobs_completed   terminal outcomes\n"
    assert len(lint_source(prose, kinds=("obs",), glossary_doc=doc2)) == 2
    # pragma suppression works like every other lint
    src_ok = src.replace("self.metrics.observe('ghost_seconds', 1)",
                         "self.metrics.observe('ghost_seconds', 1)"
                         "  # analysis: ok(test-only)")
    assert lint_source(src_ok, kinds=("obs",), glossary_doc=doc) == []
