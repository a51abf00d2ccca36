"""End-to-end observability tests (ISSUE 9 acceptance surface).

Three planes, all over real processes:
- a REAL serve.py subprocess with --obs-port: Prometheus /metrics (round
  histograms + MFU gauges), /healthz, and /trace/<job_id> — the merged
  chrome trace carries spans from >= 2 processes (client + service)
  under ONE trace id with monotonic timestamps;
- a 3-process worker fleet (the chaos-harness topology): a distributed
  prove under a dispatcher tracer yields one trace:<job_id> store
  artifact whose chrome export holds dispatcher AND worker spans under a
  single trace id, offset-corrected;
- wire-level back-compat: frames WITHOUT the TRACED flag parse exactly
  as before (an old client keeps working against a new worker).
"""

import json
import os
import random
import subprocess
import sys
import time
import urllib.error
import urllib.request

import pytest

from conftest import free_port_block
from distributed_plonk_tpu.runtime import protocol
from distributed_plonk_tpu.runtime.dispatcher import (Dispatcher,
                                                      RemoteBackend,
                                                      WorkerHandle)
from distributed_plonk_tpu.runtime.netconfig import NetworkConfig
from distributed_plonk_tpu.trace import (Tracer, merge_traces,
                                         to_chrome_trace)

REPO = os.path.normpath(os.path.join(os.path.dirname(__file__), ".."))
RNG = random.Random(0x0B5)


def _assert_chrome_schema(ct):
    """Schema-validate a chrome trace-event export (the satellite's
    explicit check): metadata rows name processes, every span row is a
    complete event with the required keys and sane values."""
    assert set(ct) >= {"traceEvents", "displayTimeUnit", "otherData"}
    meta = [e for e in ct["traceEvents"] if e.get("ph") == "M"]
    xs = [e for e in ct["traceEvents"] if e.get("ph") == "X"]
    assert meta and xs
    for e in meta:
        assert e["name"] == "process_name" and "name" in e["args"]
    for e in xs:
        for key in ("name", "cat", "ts", "dur", "pid", "tid", "args"):
            assert key in e, (key, e)
        assert e["ts"] >= 0 and e["dur"] >= 0
        assert isinstance(e["pid"], int)
    json.dumps(ct)  # must be pure JSON
    return xs


def _spawn_workers(tmp_path, n, port_base, trace_cap=None):
    base = free_port_block(n, port_base)
    cfg = NetworkConfig([f"127.0.0.1:{base + i}" for i in range(n)])
    cfg_path = str(tmp_path / "network.json")
    cfg.save(cfg_path)
    env = dict(os.environ)
    if trace_cap is not None:
        env["DPT_WORKER_TRACE_CAP"] = str(trace_cap)
    procs = [subprocess.Popen(
        [sys.executable, "-m", "distributed_plonk_tpu.runtime.worker",
         str(i), cfg_path, "--backend", "python"], cwd=REPO, env=env)
        for i in range(n)]
    deadline = time.time() + 30
    pending = set(range(n))
    while pending and time.time() < deadline:
        for i in sorted(pending):
            h, p = cfg.workers[i]
            if WorkerHandle(h, p).probe(timeout_ms=2000) is not None:
                pending.discard(i)
        if pending:
            time.sleep(0.2)
    assert not pending, f"workers {sorted(pending)} did not come up"
    return cfg, procs


def _kill_all(procs):
    for p in procs:
        if p.poll() is None:
            p.kill()
    for p in procs:
        p.wait(timeout=10)


# --- fleet plane: the acceptance criterion -----------------------------------

def test_fleet_prove_produces_merged_trace_artifact(tmp_path, proven):
    """3-process chaos-harness topology: a fully distributed prove under
    a dispatcher tracer -> ONE trace:<job_id> store artifact whose
    chrome export contains dispatcher AND worker spans under a single
    trace id, with monotonic offset-corrected timestamps."""
    from distributed_plonk_tpu.prover import prove
    from distributed_plonk_tpu.store import ArtifactStore
    from distributed_plonk_tpu.store import keycache as KC

    ckt, pk, vk, proof_host = proven
    cfg, procs = _spawn_workers(tmp_path, 3, 30500)
    d = None
    try:
        tracer = Tracer(proc="dispatcher")
        d = Dispatcher(cfg, tracer=tracer)
        proof = prove(random.Random(1), ckt, pk,
                      RemoteBackend(d, dist_fft_min=ckt.n))
        assert proof.opening_proof == proof_host.opening_proof

        merged = d.collect_trace()
        assert merged["trace_id"] == tracer.trace_id
        procs_by_name = {p["proc"]: p for p in merged["processes"]}
        assert "dispatcher" in procs_by_name
        worker_procs = [p for p in merged["processes"]
                        if p["proc"].startswith("worker/")]
        assert len(worker_procs) >= 2, merged["processes"]
        assert len({p["pid"] for p in merged["processes"]}) >= 3

        spans = [e["span"] for e in merged["events"]]
        assert any(s.startswith("fleet/") for s in spans)        # dispatcher
        assert any(s.startswith("serve/") for s in spans)        # workers
        # fan-out rpc spans run on executor threads (path has no fleet/
        # prefix — the stack is thread-local) but still chain to their
        # fleet span via the explicit parent — the TREE survives the hop
        by_sid = {e["sid"]: e for e in merged["events"]}
        rpcs = [e for e in merged["events"]
                if e["span"] in ("rpc/msm", "rpc/fft_init", "rpc/fft1",
                                 "rpc/fft2_prepare", "rpc/fft2")]
        assert rpcs
        for e in rpcs:
            parent = by_sid.get(e.get("parent"))
            assert parent is not None and \
                parent["span"].startswith("fleet/"), e
        assert any(s.endswith("/msm") and "flops" in e
                   for s, e in zip(spans, merged["events"]))
        # peer exchange legs landed in the SAME trace (worker->worker
        # context propagation through FFT2_PREPARE)
        assert any(s == "serve/fft_exchange" for s in spans), \
            sorted(set(spans))

        # monotonic, offset-corrected: merged order is by corrected ts,
        # and every worker span lies inside the dispatcher's prove window
        ts = [e["ts"] for e in merged["events"]]
        assert ts == sorted(ts)
        disp = [e for e in merged["events"] if e["proc"] == "dispatcher"]
        lo = min(e["ts"] for e in disp) - 5.0
        hi = max(e["ts"] + e["dur_s"] for e in disp) + 5.0
        assert all(lo <= e["ts"] <= hi for e in merged["events"])

        # one content-addressed artifact per job, like proofs
        store = ArtifactStore(str(tmp_path / "store"))
        digest = KC.store_trace(store, "job-fleet-1", merged)
        assert digest
        reloaded = KC.load_trace(store, "job-fleet-1")
        assert reloaded["trace_id"] == tracer.trace_id
        xs = _assert_chrome_schema(to_chrome_trace(reloaded))
        assert len({e["pid"] for e in xs}) >= 3

        # TRACE_DUMP is fetch-and-forget: a second collect holds only
        # the dispatcher's own spans
        again = d.collect_trace()
        assert [p["proc"] for p in again["processes"]] == ["dispatcher"]
    finally:
        if d is not None:
            for w in d.workers:
                w.close()
            d.pool.shutdown(wait=False)
        _kill_all(procs)


# --- wire plane: back-compat -------------------------------------------------

def test_wire_backcompat_and_trace_dump(tmp_path):
    ctx = {"trace_id": "ab" * 16, "parent_id": "cd" * 8}
    tag, payload = protocol.wrap_traced(protocol.NTT, b"body", ctx)
    assert tag == protocol.NTT | protocol.TRACED
    assert protocol.strip_context(tag, payload) == (protocol.NTT, ctx,
                                                   b"body")
    # a no-context frame passes through strip_context untouched
    assert protocol.strip_context(protocol.NTT, b"body") == \
        (protocol.NTT, None, b"body")
    assert protocol.wrap_traced(protocol.NTT, b"body", None) == \
        (protocol.NTT, b"body")
    assert protocol.tag_name(protocol.MSM | protocol.TRACED) == "MSM"

    from distributed_plonk_tpu import poly as P
    from distributed_plonk_tpu.constants import R_MOD
    cfg, procs = _spawn_workers(tmp_path, 1, 31200)
    try:
        n = 16
        values = [RNG.randrange(R_MOD) for _ in range(n)]
        want = P.fft(P.Domain(n), values)

        # old client: tracer-less dispatcher sends flag-less frames
        plain = Dispatcher(cfg)
        assert plain.ntt(values) == want
        snap = plain.workers[0].probe()
        assert snap["traces"] == 0        # nothing buffered for it
        for w in plain.workers:
            w.close()
        plain.pool.shutdown(wait=False)

        # new client: same worker, traced frames, dump comes back
        d = Dispatcher(cfg, tracer=Tracer(proc="d2"))
        assert d.ntt(values) == want
        assert d.workers[0].probe()["traces"] == 1
        merged = d.collect_trace()
        assert {e["proc"] for e in merged["events"]} == {"d2", "worker/0"}
        # unknown trace id answers {} (worker restarted / LRU-dropped)
        raw = d.workers[0].call(
            protocol.TRACE_DUMP,
            protocol.encode_json({"trace_id": "ff" * 16}), traced=False)
        assert protocol.decode_json(raw) == {}
        for w in d.workers:
            w.close()
        d.pool.shutdown(wait=False)
    finally:
        _kill_all(procs)


# --- durability: the trace identity is part of the journal contract ----------

def test_trace_id_survives_service_restart(tmp_path):
    """The SUBMIT reply told the client a trace id; a crash + recovery
    must keep answering to it (the journal SUBMIT record carries it), or
    the client's spans orphan from the recovered job's timeline."""
    from distributed_plonk_tpu.service import ProofService

    ctx = {"trace_id": "5a" * 16, "parent_id": "6b" * 8}
    spec = {"kind": "toy", "gates": 16, "seed": 21, "job_key": "tr-k",
            "trace_ctx": ctx}
    svc = ProofService(port=0, prover_workers=1,
                       journal_dir=str(tmp_path / "j"),
                       store_dir=str(tmp_path / "s"))
    # crash BEFORE starting the scheduler: the job is journaled but
    # never proved — recovery must resume it under the adopted identity
    job, _ = svc.submit_ex(spec)
    assert job.trace_id == ctx["trace_id"]
    svc.crash()

    svc2 = ProofService(port=0, prover_workers=1,
                        journal_dir=str(tmp_path / "j"),
                        store_dir=str(tmp_path / "s")).start()
    try:
        job2, deduped = svc2.submit_ex(spec)
        assert deduped and job2.id == job.id
        assert job2.trace_id == ctx["trace_id"]
        assert job2.trace_parent == ctx["parent_id"]
        assert job2.done_event.wait(timeout=120) and job2.state == "done"
        # the stored artifact answers to the same id
        from distributed_plonk_tpu.store import keycache as KC
        merged = KC.load_trace(svc2.store, job2.id)
        assert merged["trace_id"] == ctx["trace_id"]
        # ...and the prover spans chain up to the client's parent span
        roots = [e for e in merged["events"]
                 if e.get("parent") == ctx["parent_id"]]
        assert roots, merged["events"][:3]
    finally:
        svc2.shutdown()


# --- service plane: serve.py subprocess + obs HTTP ---------------------------

@pytest.fixture()
def serve_proc(tmp_path):
    """A REAL serve.py subprocess with --obs-port; yields (addr, obs,
    proc)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("DPT_FAULTS", None)
    proc = subprocess.Popen(
        [sys.executable, os.path.join(REPO, "scripts", "serve.py"),
         "--backend", "python",
         "--port", "0", "--obs-port", "0", "--workers", "1",
         "--store-dir", str(tmp_path / "store"),
         "--allow-remote-shutdown"],
        stdout=subprocess.PIPE, env=env, text=True, cwd=REPO)
    banner = json.loads(proc.stdout.readline())
    try:
        yield banner["listening"], banner["obs"], proc
    finally:
        if proc.poll() is None:
            proc.kill()
        proc.wait(timeout=10)


def _get(url, timeout=10):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.read()


def test_serve_subprocess_obs_endpoints_and_merged_trace(serve_proc):
    from distributed_plonk_tpu.service import ServiceClient

    addr, obs, proc = serve_proc
    host, port = addr.rsplit(":", 1)
    base = f"http://{obs}"

    client_tr = Tracer(proc="test-client")
    with ServiceClient(host, int(port)) as c:
        with client_tr.span("client/prove_request") as root:
            r = c.submit({"kind": "toy", "gates": 16, "seed": 11},
                         trace_ctx={"trace_id": client_tr.trace_id,
                                    "parent_id": root})
            assert r["trace_id"] == client_tr.trace_id  # adopted, not stamped
            st = c.wait(r["job_id"], timeout_s=180)
        assert st["state"] == "done"
        assert st["trace_spans"] >= 6
        job_id = r["job_id"]

        # /healthz: the readiness-probe shape
        h = json.loads(_get(base + "/healthz"))
        assert h["ok"] is True and h["queue_depth"] == 0

        # /metrics: Prometheus text exposition with round latency
        # histograms and kernel throughput gauges; the host oracle has
        # no chip peak to be a share of, so it publishes no MFU gauge
        text = _get(base + "/metrics").decode()
        assert "# TYPE dpt_jobs_completed_total counter" in text
        assert "dpt_jobs_completed_total 1" in text
        assert 'dpt_prove_round_round1_seconds{quantile="0.5"}' in text
        assert "dpt_mfu_" not in text
        assert "dpt_kernel_commit_wires_gflops" in text
        assert "dpt_queue_depth 0" in text

        # /trace/<job_id>: chrome trace of the server-side timeline
        ct = json.loads(_get(base + f"/trace/{job_id}"))
        xs = _assert_chrome_schema(ct)
        assert ct["otherData"]["trace_id"] == client_tr.trace_id
        names = [e["name"] for e in xs]
        assert "service/queued" in names and "round1" in names

        # the raw merged dump + the client's own spans = one timeline
        # from >= 2 PROCESSES under one trace id (context propagation
        # across the wire is what makes them correlate)
        raw = json.loads(_get(base + f"/trace/{job_id}?raw=1"))
        combined = merge_traces([client_tr.dump(), raw])
        assert combined["trace_id"] == client_tr.trace_id
        pids = {e["pid"] for e in combined["events"]}
        assert len(pids) >= 2, combined["processes"]
        ts = [e["ts"] for e in combined["events"]]
        assert ts == sorted(ts)
        # parent linkage survives the hop: the prover-side spans chain up
        # to the client's root span id
        roots = [e for e in combined["events"]
                 if e.get("parent") == client_tr.events[0]["sid"]]
        assert roots, "no server span parented to the client's root"

        # unknown paths/jobs answer 404, never crash the service
        for bad in ("/trace/nope", "/bogus"):
            with pytest.raises(urllib.error.HTTPError) as ei:
                _get(base + bad)
            assert ei.value.code == 404
        c.shutdown_server()
    proc.wait(timeout=30)
