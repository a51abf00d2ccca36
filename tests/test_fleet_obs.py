"""Fleet observability plane tests (ISSUE 15 acceptance surface).

Five planes, all jax-free (python-backend workers over real TCP):
- structured-log units: ring semantics, trace filtering, file sink, and
  the LOG01 subsystem-glossary lint;
- fleet metrics: METRICS_FETCH scrape of a live fleet, per-worker
  labelled Prometheus rendering, breaker/suspect awareness;
- wire back-compat: the new METRICS_FETCH/LOG_FETCH/PROFILE tags degrade
  to empty results against an old worker and never kill serving, and a
  new worker answers an unknown tag with ERR on a connection that keeps
  working;
- the ONE-PANE acceptance criterion: a live 3-worker SUPERVISED fleet
  prove with a mid-FFT worker kill yields, from one ObsServer, the
  aggregated dpt_fleet_* series, the /fleet snapshot, a merged
  trace:<job_id> artifact carrying dispatcher/supervisor/worker
  structured log events under the prove's trace id, and a fetchable
  profile:<id> artifact — proof bytes byte-identical throughout;
- the perf-regression gate: normalize/compare units plus the committed
  trajectory staying green (the ci.sh benchcheck contract).
"""

import json
import os
import random
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import pytest

from distributed_plonk_tpu.obs import fleet as OF
from conftest import free_port_block
from distributed_plonk_tpu.obs import log as olog
from distributed_plonk_tpu.runtime import native, protocol
from distributed_plonk_tpu.runtime.dispatcher import (Dispatcher,
                                                      RemoteBackend,
                                                      WorkerHandle)
from distributed_plonk_tpu.runtime.netconfig import NetworkConfig
from distributed_plonk_tpu.trace import Tracer

REPO = os.path.normpath(os.path.join(os.path.dirname(__file__), ".."))
SCRIPTS = os.path.join(REPO, "scripts")
RNG = random.Random(0x0B515)


def _spawn_workers(tmp_path, n, port_base):
    base = free_port_block(n, port_base)
    cfg = NetworkConfig([f"127.0.0.1:{base + i}" for i in range(n)])
    cfg_path = str(tmp_path / "network.json")
    cfg.save(cfg_path)
    procs = [subprocess.Popen(
        [sys.executable, "-m", "distributed_plonk_tpu.runtime.worker",
         str(i), cfg_path, "--backend", "python"], cwd=REPO)
        for i in range(n)]
    deadline = time.time() + 60
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


def test_free_port_block_skips_a_block_with_a_taken_port():
    """The fleet tests' ports lie in the ephemeral range: a block is
    probed before the workers are told to listen on it, and one with a
    port somebody holds is passed over (one tier-1 run of PR 25 lost seven
    tests to 'cannot listen on 127.0.0.1:34556')."""
    import socket
    first = free_port_block(3, 36100)
    held = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    try:
        held.bind(("127.0.0.1", first + 1))
        held.listen(1)
        second = free_port_block(3, 36100)
        assert second > first + 1       # the whole block moved on
        for port in range(second, second + 3):
            probe = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
            probe.bind(("127.0.0.1", port))
            probe.close()
    finally:
        held.close()


def _kill_all(procs):
    for p in procs:
        if p.poll() is None:
            p.kill()
    for p in procs:
        p.wait(timeout=10)


def _shutdown(d):
    for w in d.workers:
        try:
            w.call(protocol.SHUTDOWN, traced=False)
        except Exception:
            pass
        w.close()
    d.pool.shutdown(wait=False)


def _get(url, timeout=15):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.read()


# --- structured-log plane ----------------------------------------------------

def test_log_buffer_ring_filter_sink(tmp_path):
    buf = olog.LogBuffer(cap=4, proc="t")
    for i in range(6):
        buf.emit("service", "retry", job_id=f"j{i}",
                 trace_id="aa" if i % 2 else None)
    out = buf.fetch()
    assert out["seq"] == 6
    assert [e["seq"] for e in out["events"]] == [3, 4, 5, 6]  # ring cap 4
    # trace filter + since_seq tailing
    assert all(e["trace_id"] == "aa"
               for e in buf.fetch(trace_id="aa")["events"])
    assert [e["seq"] for e in buf.fetch(since_seq=5)["events"]] == [6]
    assert len(buf.fetch(limit=2)["events"]) == 2
    # file sink: one JSON object per line, events recorded after open
    path = buf.open_sink(str(tmp_path / "logs"), proc="t2")
    assert path and os.path.exists(path)
    buf.emit("service", "shed", level="warn", job_id="jx", reason="ttl")
    buf.close_sink()
    lines = [json.loads(line) for line in open(path)]
    assert lines and lines[-1]["event"] == "shed"
    assert lines[-1]["subsystem"] == "service"
    # the glossary the LOG01 lint enforces is parseable and non-trivial
    subs = olog.documented_subsystems()
    assert {"dispatcher", "supervisor", "worker", "service",
            "membership", "integrity", "obs"} <= subs


def test_log01_lint_subsystem_glossary():
    from distributed_plonk_tpu.analysis.lint import lint_source
    bad = ("from distributed_plonk_tpu.obs import log as olog\n"
           "def f():\n"
           "    olog.emit('totally_new_subsystem', 'boom')\n")
    findings = lint_source(bad, kinds=("log",))
    assert any(f.code == "LOG01" for f in findings), findings
    good = bad.replace("totally_new_subsystem", "dispatcher")
    assert not lint_source(good, kinds=("log",))
    # derived subsystems are out of scope (families are a design choice)
    derived = ("def f(name):\n"
               "    emit(name, 'x')\n")
    assert not lint_source(derived, kinds=("log",))
    # the live tree is CLEAN against its own glossary (the ci.sh gate)
    from distributed_plonk_tpu.analysis.lint import run_lints
    assert not [f for f in run_lints() if f.code == "LOG01"]


# --- fleet metrics plane -----------------------------------------------------

def test_metrics_fetch_scrape_render_and_suspect_awareness(tmp_path):
    from distributed_plonk_tpu import poly as P
    from distributed_plonk_tpu.constants import R_MOD

    cfg, procs = _spawn_workers(tmp_path, 2, 33500)
    d = Dispatcher(cfg)
    try:
        values = [RNG.randrange(R_MOD) for _ in range(16)]
        assert d.ntt(values) == P.fft(P.Domain(16), values)
        entries = d.fleet_metrics()
        assert [e["index"] for e in entries] == [0, 1]
        assert all(e["reachable"] for e in entries)
        snaps = [e["snapshot"] for e in entries]
        assert all(s is not None for s in snaps)
        # the NTT the fleet just served shows up in exactly one worker's
        # served counters, with kernel gauges beside it
        served = sum(s["counters"].get("served_ntt", 0) for s in snaps)
        assert served == 1
        assert any("kernel_ntt_gflops" in s["gauges"] for s in snaps)
        assert all("index" in s and "uptime_s" in s for s in snaps)
        # labelled Prometheus rendering: one series per worker
        text = OF.render_prom(entries)
        assert 'dpt_fleet_up{worker="0"' in text
        assert 'dpt_fleet_up{worker="1"' in text
        assert "dpt_fleet_served_ntt_total{" in text
        # suspect-aware: a quarantined worker is REPORTED, never dialed
        d.tracker.mark_suspect(1)
        entries = d.fleet_metrics()
        assert entries[1]["suspect"] and not entries[1]["usable"]
        assert entries[1]["snapshot"] is None
        assert entries[0]["snapshot"] is not None
        text = OF.render_prom(entries)
        assert 'dpt_fleet_suspect{worker="1"' in text
        # aggregates fold into a shared registry
        from distributed_plonk_tpu.service.metrics import Metrics
        m = Metrics()
        OF.aggregate(entries, m)
        snap = m.snapshot()
        assert snap["gauges"]["fleet_width"] == 2
        assert snap["gauges"]["fleet_suspects"] == 1
        assert snap["counters"]["fleet_scrapes"] == 1
    finally:
        _shutdown(d)
        _kill_all(procs)


# --- wire back-compat --------------------------------------------------------

def _stub_old_worker():
    """A pre-ISSUE-15 worker: framed transport, answers PING/HEALTH,
    ERRs on everything else — exactly how an old daemon meets the new
    tags. Returns (host, port, closer)."""
    listener = native.Listener("127.0.0.1", 0)
    port = native.listener_port(listener)

    def serve_conn(conn):
        try:
            while True:
                try:
                    tag, _payload = conn.recv()
                except ConnectionError:
                    return
                tag &= ~protocol.TRACED
                if tag == protocol.PING:
                    conn.send(protocol.OK)
                elif tag == protocol.HEALTH:
                    conn.send(protocol.OK, json.dumps(
                        {"uptime_s": 1.0, "served": 0,
                         "now": time.time()}).encode())
                else:
                    conn.send(protocol.ERR, b"unknown tag")
        finally:
            conn.close()

    def accept_loop():
        while True:
            try:
                conn = listener.accept()
            except Exception:
                return
            if conn.fd < 0:
                return
            threading.Thread(target=serve_conn, args=(conn,),
                             daemon=True).start()

    threading.Thread(target=accept_loop, daemon=True).start()
    return "127.0.0.1", port, listener.close


def test_unknown_tags_degrade_and_never_kill_serving(tmp_path):
    from distributed_plonk_tpu import poly as P
    from distributed_plonk_tpu.constants import R_MOD

    cfg, procs = _spawn_workers(tmp_path, 1, 34200)
    sh, sp, close_stub = _stub_old_worker()
    mixed = NetworkConfig([f"{cfg.workers[0][0]}:{cfg.workers[0][1]}",
                           f"{sh}:{sp}"])
    d = Dispatcher(mixed)
    try:
        # new dispatcher vs OLD worker: every new tag degrades to an
        # empty/unsupported result — never an exception, never a breaker
        entries = d.fleet_metrics()
        assert entries[1]["reachable"] and entries[1].get("unsupported")
        assert entries[1]["snapshot"] is None
        assert entries[0]["snapshot"] is not None
        logs = d.fetch_logs(worker=1)
        assert logs == [{"worker": 1, "events": [], "seq": 0}]
        meta, blob = d.profile_worker(1)
        assert meta["format"] == "unsupported" and blob == b""
        assert d.tracker.usable(1)  # ERR replies are not failures
        # ...and serving still works: an NTT routed AT the old worker
        # rotates onto the new one and answers correctly
        values = [RNG.randrange(R_MOD) for _ in range(16)]
        assert d.ntt(values, worker=1) == P.fft(P.Domain(16), values)

        # the reverse: a NEW worker answers an unknown tag with ERR and
        # the connection keeps serving (an old dispatcher keeps working)
        h, p = cfg.workers[0]
        conn = native.connect(h, p)
        try:
            conn.send(99, b"")
            rtag, rbody = conn.recv()
            assert rtag == protocol.ERR and b"unknown tag" in rbody
            conn.send(protocol.NTT,
                      protocol.encode_ntt_request(values, False, False))
            rtag, rbody = conn.recv()
            assert rtag == protocol.OK
            assert protocol.decode_scalars(rbody) == \
                P.fft(P.Domain(16), values)
        finally:
            conn.close()
    finally:
        close_stub()
        _shutdown(d)
        _kill_all(procs)


# --- service plane: ObsServer endpoints over an attached fleet ---------------

def test_service_fleet_obs_endpoints(tmp_path):
    from distributed_plonk_tpu.service import ProofService
    from distributed_plonk_tpu.service.server import ObsServer

    olog.reset()
    cfg, procs = _spawn_workers(tmp_path, 2, 34900)
    d = Dispatcher(cfg)
    svc = ProofService(port=0, prover_workers=1,
                       store_dir=str(tmp_path / "store"),
                       backend_factory=lambda: RemoteBackend(
                           d, dist_fft_min=64)).start()
    svc.attach_fleet(d, interval_s=0.3)
    obs = ObsServer(svc).start()
    base = f"http://{obs.host}:{obs.port}"
    try:
        job = svc.submit_local({"kind": "toy", "gates": 16, "seed": 5})
        assert job.done_event.wait(timeout=180) and job.state == "done"
        svc.fleet.scrape_once()  # deterministic: don't race the interval

        # /metrics: service exposition + labelled per-worker series
        text = _get(base + "/metrics").decode()
        assert "dpt_jobs_completed_total 1" in text
        assert 'dpt_fleet_up{worker="0"' in text
        assert 'dpt_fleet_up{worker="1"' in text
        assert "dpt_fleet_served_msm_total{" in text
        assert "dpt_fleet_width 2" in text

        # /healthz: LB truth now carries the fleet summary
        h = json.loads(_get(base + "/healthz"))
        assert h["ok"] is True
        assert h["fleet"] == {"epoch": 0, "width": 2, "usable": 2,
                              "suspects": 0, "breakers_open": 0}

        # /fleet: every member named with breaker/suspect state
        fl = json.loads(_get(base + "/fleet"))
        assert fl["width"] == 2 and len(fl["members"]) == 2
        for m in fl["members"]:
            assert {"index", "addr", "usable", "suspect", "left",
                    "reachable", "snapshot"} <= set(m)
            assert m["reachable"] and m["snapshot"]

        # /logs: the service process's ring over HTTP
        lg = json.loads(_get(base + "/logs?limit=50"))
        assert "events" in lg and "seq" in lg

        # /profile/capture -> /profile/<id>: on-demand capture stored as
        # a content-addressed artifact and served back
        cap = json.loads(_get(base + "/profile/capture?worker=0&ms=60"))
        assert cap["profile_id"] and cap["format"] == "pystacks-json"
        blob = _get(base + "/profile/" + cap["profile_id"])
        prof = json.loads(blob)
        assert prof["format"] == "pystacks-json" and prof["samples"] >= 1
        from distributed_plonk_tpu.store import keycache as KC
        assert svc.store.get_entry(
            KC.profile_store_key(cap["profile_id"])) is not None
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(base + "/profile/deadbeef00000000")
        assert ei.value.code == 404

        # the operator console renders one pane from these endpoints
        out = subprocess.run(
            [sys.executable, os.path.join(SCRIPTS, "console.py"),
             "--obs", f"{obs.host}:{obs.port}", "--once", "--logs", "5"],
            capture_output=True, text=True, timeout=60, cwd=REPO)
        assert out.returncode == 0, out.stderr
        assert "fleet    epoch=0 width=2" in out.stdout
        assert "[ 0]" in out.stdout and "[ 1]" in out.stdout
    finally:
        obs.close()
        svc.shutdown()
        _shutdown(d)
        _kill_all(procs)


# --- THE acceptance criterion: one pane over a supervised fleet prove --------

@pytest.mark.tier2
def test_supervised_fleet_prove_one_pane(tmp_path):
    """Live 3-worker supervised fleet prove with a mid-FFT1 worker kill:
    one ObsServer yields the aggregated per-worker series, the /fleet
    snapshot, a merged trace:<job_id> artifact whose structured logs
    carry dispatcher AND supervisor AND worker events under the prove's
    trace id, and a fetchable profile:<id> — proof bytes byte-identical
    to the host oracle."""
    import random as _random
    from distributed_plonk_tpu.backend.python_backend import PythonBackend
    from distributed_plonk_tpu.prover import prove
    from distributed_plonk_tpu.proof_io import serialize_proof
    from distributed_plonk_tpu.runtime.faults import FaultInjector, Rule
    from distributed_plonk_tpu.runtime.health import LivenessTracker
    from distributed_plonk_tpu.runtime.supervisor import WorkerSupervisor
    from distributed_plonk_tpu.service import ProofService
    from distributed_plonk_tpu.service.jobs import (JobSpec, build_circuit,
                                                    build_bucket_keys)
    from distributed_plonk_tpu.service.metrics import Metrics
    from distributed_plonk_tpu.service.server import ObsServer

    olog.reset()
    spec_obj = {"kind": "toy", "gates": 16, "seed": 7}
    spec = JobSpec.from_wire(spec_obj)
    ckt = build_circuit(spec)
    pk = build_bucket_keys(spec)[1]
    want = serialize_proof(prove(_random.Random(spec.seed), ckt, pk,
                                 PythonBackend()))

    metrics = Metrics()
    faults = FaultInjector(
        [Rule("kill", tag=protocol.FFT1, worker=1, nth=1, plane="proc")],
        metrics=metrics)
    d = Dispatcher(NetworkConfig([]), metrics=metrics, faults=faults,
                   tracer=Tracer(proc="dispatcher"))
    d.tracker = LivenessTracker(0, breaker_k=2, probe_base_s=0.05,
                                probe_max_s=0.5, metrics=metrics)
    mserver = d.enable_membership()
    sup = WorkerSupervisor("127.0.0.1", mserver.port, n=3,
                           backend="python", metrics=metrics, cwd=REPO,
                           probe_interval_s=0.1, backoff_base_s=0.05,
                           backoff_max_s=0.5).start()
    faults.proc_kill_cb = sup.proc_killer(d)
    svc = ProofService(port=0, prover_workers=1, max_retries=4,
                       store_dir=str(tmp_path / "store"),
                       backend_factory=lambda: RemoteBackend(
                           d, dist_fft_min=ckt.n)).start()
    svc.attach_fleet(d, interval_s=0.5)
    obs = ObsServer(svc).start()
    base = f"http://{obs.host}:{obs.port}"
    try:
        deadline = time.time() + 90
        while time.time() < deadline:
            if len(d.workers) == 3 and len(d.tracker.usable_set()) == 3:
                break
            time.sleep(0.1)
        assert len(d.tracker.usable_set()) == 3, "fleet never came up"
        for w in d.workers:
            w.RECONNECT_TRIES = 2
            w.BACKOFF_BASE_S = 0.01
            w.BACKOFF_MAX_S = 0.05

        job = svc.submit_local(spec_obj)
        assert job.done_event.wait(timeout=240) and job.state == "done", \
            (job.state, job.error)
        assert job.proof_bytes == want  # byte-identical through the kill
        assert metrics.snapshot()["counters"].get(
            "faults_injected_kill", 0) == 1

        # wait for the heal (respawn + rejoin) so the supervisor's log
        # events exist before the timeline is collected
        deadline = time.time() + 120
        while time.time() < deadline:
            ctr = metrics.snapshot()["counters"]
            if ctr.get("worker_respawns", 0) >= 1 \
                    and len(d.tracker.usable_set()) == 3:
                break
            time.sleep(0.1)
        assert metrics.snapshot()["counters"].get(
            "worker_respawns", 0) >= 1

        # ONE artifact: service spans + fleet spans + structured logs
        merged = svc.merge_fleet_trace(job.id)
        assert merged["trace_id"] == job.trace_id
        subsystems = {e["subsystem"] for e in merged["logs"]}
        assert {"dispatcher", "supervisor", "worker"} <= subsystems, \
            subsystems
        assert all(e.get("trace_id") == job.trace_id
                   for e in merged["logs"])
        # the incident reads off the artifact: the replan the kill forced
        assert any(e["subsystem"] == "dispatcher"
                   and e["event"] in ("fft_replan", "fft_degraded",
                                      "range_adopted")
                   for e in merged["logs"])
        assert any(e["subsystem"] == "supervisor"
                   and e["event"] == "respawn" for e in merged["logs"])
        # worker spans made it into the same timeline
        procs_ = {e.get("proc") for e in merged["events"]}
        assert any(str(p).startswith("worker/") for p in procs_), procs_

        # ...and it is served at /trace/<job_id> (raw + chrome forms)
        raw = json.loads(_get(base + f"/trace/{job.id}?raw=1"))
        assert raw["trace_id"] == job.trace_id
        assert {e["subsystem"] for e in raw["logs"]} >= \
            {"dispatcher", "supervisor", "worker"}
        ct = json.loads(_get(base + f"/trace/{job.id}"))
        instants = [e for e in ct["traceEvents"] if e.get("ph") == "i"]
        assert any(e["name"] == "supervisor/respawn" for e in instants)

        # aggregated per-worker series + fleet snapshot from the SAME
        # ObsServer
        svc.fleet.scrape_once()
        text = _get(base + "/metrics").decode()
        for i in range(3):
            assert f'dpt_fleet_up{{worker="{i}"' in text
        assert "dpt_fleet_width 3" in text
        fl = json.loads(_get(base + "/fleet"))
        assert fl["width"] == 3 and fl["epoch"] >= 4  # 3 joins + rejoin
        assert all("suspect" in m and "usable" in m
                   for m in fl["members"])
        h = json.loads(_get(base + "/healthz"))
        assert h["fleet"]["width"] == 3 and h["fleet"]["epoch"] == \
            fl["epoch"]

        # a fetchable on-demand profile artifact, linked from the plane
        cap = json.loads(_get(base + "/profile/capture?worker=0&ms=60"))
        assert cap["profile_id"]
        assert _get(base + "/profile/" + cap["profile_id"])
    finally:
        obs.close()
        svc.shutdown()
        sup.stop()
        d.shutdown()
        d.pool.shutdown(wait=False)


# --- serve.py daemon: --log-dir sink + enriched healthz ----------------------

def test_serve_subprocess_log_dir_and_shed_event(tmp_path):
    from distributed_plonk_tpu.service import ServiceClient

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("DPT_FAULTS", None)
    proc = subprocess.Popen(
        [sys.executable, os.path.join(SCRIPTS, "serve.py"),
         "--backend", "python",
         "--port", "0", "--obs-port", "0", "--workers", "1",
         "--log-dir", str(tmp_path / "logs"),
         "--allow-remote-shutdown"],
        stdout=subprocess.PIPE, env=env, text=True, cwd=REPO)
    try:
        banner = json.loads(proc.stdout.readline())
        assert banner["log_file"] and os.path.exists(banner["log_file"])
        host, port = banner["listening"].rsplit(":", 1)
        base = f"http://{banner['obs']}"
        with ServiceClient(host, int(port)) as c:
            # a ttl that lapses before the prove starts: shed verdict ->
            # a structured log event in the ring (served at /logs) AND
            # the JSONL file sink
            r = c.submit({"kind": "toy", "gates": 16, "seed": 3,
                          "ttl_s": 1e-6})
            deadline = time.time() + 60
            while time.time() < deadline:
                st = c.status(r["job_id"])
                if st["state"] in ("shed", "done", "failed"):
                    break
                time.sleep(0.1)
            assert st["state"] == "shed", st
            lg = json.loads(_get(base + "/logs"))
            shed = [e for e in lg["events"] if e["event"] == "shed"]
            assert shed and shed[0]["subsystem"] == "service"
            assert shed[0]["job_id"] == r["job_id"]
            # healthz without a fleet: explicit null, not a lie
            h = json.loads(_get(base + "/healthz"))
            assert h["fleet"] is None
            c.shutdown_server()
        proc.wait(timeout=30)
        lines = [json.loads(line) for line in open(banner["log_file"])]
        assert any(e["event"] == "shed" for e in lines)
    finally:
        if proc.poll() is None:
            proc.kill()
        proc.wait(timeout=10)


# --- perf-regression gate ----------------------------------------------------

def _bench_record():
    sys.path.insert(0, SCRIPTS)
    import bench_record
    return bench_record


def test_bench_record_normalize_and_compare(tmp_path):
    BR = _bench_record()
    line = {"metric": "prove_2p13_wall_clock", "value": 3.8, "unit": "s",
            "proofs_per_s": 1.4, "analysis_clean": True,
            "fleet_heal_s": 2.3, "degraded_reason": "nope",
            "ntt_stage_breakdown": {"radix4_stage_s": 0.01},
            "baseline_basis": "prose is dropped"}
    rec = BR.normalize("bench", line, run=9)
    assert rec["schema"] == BR.SCHEMA and rec["basis"] == "chip"
    assert rec["keys"]["headline/prove_2p13_wall_clock"] == 3.8
    assert rec["keys"]["ntt_stage_breakdown/radix4_stage_s"] == 0.01
    assert "baseline_basis" not in rec["keys"]  # strings dropped
    assert BR.normalize("bench", dict(line, degraded=True))["basis"] == \
        "degraded"

    # direction + tolerance: a 60% proofs_per_s drop fails, 20% passes,
    # heal time may grow inside tolerance, booleans flipping false fail
    prev = BR.normalize("bench", line)
    worse = BR.normalize("bench", dict(line, proofs_per_s=0.5))
    regs = BR.compare(prev, worse)
    assert [r["key"] for r in regs] == ["proofs_per_s"]
    ok = BR.normalize("bench", dict(line, proofs_per_s=1.2,
                                    fleet_heal_s=4.0))
    assert BR.compare(prev, ok) == []
    flipped = BR.normalize("bench", dict(line, analysis_clean=False))
    assert any(r["key"] == "analysis_clean" and r["change"] ==
               "flipped false" for r in BR.compare(prev, flipped))
    # unwatched / new keys never gate
    novel = BR.normalize("bench", dict(line, brand_new_number=1))
    assert BR.compare(prev, novel) == []

    # trajectory append/load round trip + basis-aware pairing
    repo = str(tmp_path)
    assert BR.append(prev, repo=repo)
    assert BR.append(BR.normalize("bench", dict(line, degraded=True)),
                     repo=repo)
    records = BR.load_trajectory(repo)
    assert [r["basis"] for r in records] == ["chip", "degraded"]
    assert BR.latest_of_basis(records, "chip") is records[0]


def test_bench_compare_committed_trajectory_green():
    """The ci.sh benchcheck contract: the committed perf history (legacy
    BENCH_r*.json + trajectory.jsonl) gates green, loudly and
    non-flakily (no measurement runs)."""
    out = subprocess.run(
        [sys.executable, os.path.join(SCRIPTS, "bench_compare.py")],
        capture_output=True, text=True, timeout=120, cwd=REPO)
    assert out.returncode == 0, (out.stdout, out.stderr)
    verdict = json.loads(out.stdout.strip().splitlines()[-1])
    assert verdict["ok"] is True and verdict["regressions"] == []
    assert verdict["records"] >= 4  # the legacy file normalized too
    # and a regressing line IS caught (the gate has teeth): a watched
    # key of the last committed record, three orders of magnitude down
    bad = json.dumps({"metric": "prove_2p13_wall_clock", "value": 3.9,
                      "unit": "s", "proofs_per_s": 0.0001})
    out = subprocess.run(
        [sys.executable, os.path.join(SCRIPTS, "bench_compare.py"),
         "--line", bad],
        capture_output=True, text=True, timeout=120, cwd=REPO)
    assert out.returncode == 1
    assert "REGRESSION" in out.stderr
