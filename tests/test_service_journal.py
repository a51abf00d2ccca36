"""Durable service plane tests: crash-safe job journal, restart recovery,
idempotent submission, TTL shedding, graceful drain (ISSUE 7 acceptance).

The centerpiece is the restart sweep: a REAL scripts/serve.py process is
killed with os._exit at each journal transition (SUBMIT / START / each
ROUND / DONE) via the fault injector's journal plane, restarted on the
same journal+store dirs, and must finish every job with proof bytes
byte-identical to an uninterrupted local prove — resuming from the last
checkpoint (no completed round is ever proved twice). Everything runs on
the python host-oracle backend (jax-free) at tiny toy domains; this
module is part of `ci.sh chaos` and the fast tier.
"""

import os
import random
import signal
import subprocess
import sys
import threading
import time

import pytest

from distributed_plonk_tpu.backend.python_backend import PythonBackend
from distributed_plonk_tpu.proof_io import serialize_proof
from distributed_plonk_tpu.prover import prove
from distributed_plonk_tpu.runtime.faults import FaultInjector, Rule
from distributed_plonk_tpu.service import (BucketCache, Metrics,
                                           ProofService, Rejected,
                                           ServiceClient)
from distributed_plonk_tpu.service.jobs import (JobSpec, build_bucket_keys,
                                                build_circuit)
from distributed_plonk_tpu.service.journal import (DONE, ROUND, SHED, START,
                                                   SUBMIT, JobJournal)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SERVE = os.path.join(REPO, "scripts", "serve.py")


def reference_proof(spec_obj):
    """Uninterrupted local prove: the byte-identity oracle."""
    spec = JobSpec.from_wire(spec_obj)
    _, pk, _vk = build_bucket_keys(spec)
    return serialize_proof(prove(random.Random(spec.seed),
                                 build_circuit(spec), pk, PythonBackend()))


# --- journal unit tests ------------------------------------------------------

def _mk_journal(tmp_path, **kw):
    return JobJournal(str(tmp_path / "j"), metrics=Metrics(), **kw)


def test_journal_roundtrip_and_replay(tmp_path):
    j = _mk_journal(tmp_path)
    j.append(SUBMIT, "job-1", spec={"kind": "toy", "gates": 8, "seed": 1},
             key="k1", deadline=None, ts=123.0)
    j.append(START, "job-1", worker="w0g1")
    j.append(ROUND, "job-1", round=1)
    j.append(ROUND, "job-1", round=2)
    j.append(SUBMIT, "job-2", spec={"kind": "toy", "gates": 8, "seed": 2},
             key=None, deadline=9e9, ts=124.0)
    j.append(SHED, "job-2", reason="ttl expired in queue")
    j.close()

    j2 = _mk_journal(tmp_path)
    assert list(j2.state) == ["job-1", "job-2"]
    st1, st2 = j2.state["job-1"], j2.state["job-2"]
    assert st1["phase"] == "round" and st1["round"] == 2
    assert st1["key"] == "k1"
    assert st2["phase"] == "shed" and "ttl expired" in st2["reason"]
    j2.close()


def test_journal_compaction_bounds_the_log(tmp_path):
    j = _mk_journal(tmp_path, compact_every=10**9, retain_terminal=2)
    for i in range(8):
        jid = f"job-{i}"
        j.append(SUBMIT, jid, spec={"kind": "toy", "gates": 8, "seed": i},
                 key=None, deadline=None, ts=float(i))
        j.append(DONE, jid, proof_hex="ab", pub=["0x1"], retries=0)
    j.append(SUBMIT, "job-live", spec={"kind": "toy", "gates": 8, "seed": 9},
             key=None, deadline=None, ts=9.0)
    j.append(ROUND, "job-live", round=3)
    j.compact()
    # terminal jobs beyond retain_terminal dropped, live job never dropped
    assert "job-live" in j.state and j.state["job-live"]["round"] == 3
    terminal = [jid for jid in j.state if jid != "job-live"]
    assert terminal == ["job-6", "job-7"]
    j.close()
    # the compacted file replays to the same state
    j2 = _mk_journal(tmp_path)
    assert set(j2.state) == {"job-6", "job-7", "job-live"}
    j2.close()


@pytest.mark.parametrize("damage", ["torn", "bitflip", "garbage_tail"])
def test_journal_damaged_tail_truncate_and_continue(tmp_path, damage):
    j = _mk_journal(tmp_path)
    j.append(SUBMIT, "job-1", spec={"kind": "toy", "gates": 8, "seed": 1},
             key=None, deadline=None, ts=1.0)
    j.append(ROUND, "job-1", round=1)
    j.append(ROUND, "job-1", round=2)
    j.close()
    path = j.path
    with open(path, "rb") as f:
        raw = f.read()
    lines = raw.split(b"\n")
    if damage == "torn":          # power cut mid-append: half a record
        raw = raw[:len(raw) - len(lines[-2]) // 2 - 1]
    elif damage == "bitflip":     # bit rot inside the last record
        idx = len(raw) - len(lines[-2]) // 2
        raw = raw[:idx] + bytes([raw[idx] ^ 0xFF]) + raw[idx + 1:]
    else:                         # appended garbage, no newline
        raw += b"\x00\xffnot a record"
    with open(path, "wb") as f:
        f.write(raw)

    j2 = _mk_journal(tmp_path)   # replay must truncate, never crash
    st = j2.state["job-1"]
    assert st["round"] in (1, 2)  # damaged suffix dropped, prefix kept
    snap = j2.metrics.snapshot()["counters"]
    assert snap["journal_torn_records"] == 1
    # the journal keeps working after surgery: append + clean replay
    j2.append(ROUND, "job-1", round=3)
    j2.close()
    j3 = _mk_journal(tmp_path)
    assert j3.state["job-1"]["round"] == 3
    assert "journal_torn_records" not in j3.metrics.snapshot()["counters"]
    j3.close()


def test_journal_sealed_writes_nothing(tmp_path):
    j = _mk_journal(tmp_path)
    j.append(SUBMIT, "job-1", spec={"kind": "toy", "gates": 8, "seed": 1},
             key=None, deadline=None, ts=1.0)
    j.seal()
    assert j.append(ROUND, "job-1", round=1) is False
    j2 = _mk_journal(tmp_path)
    assert j2.state["job-1"]["phase"] == "submit"
    j2.close()


# --- restart sweep: service killed at every journal transition ---------------

def _spawn_serve(port, journal_dir, store_dir, faults=None, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("DPT_FAULTS", None)
    if faults:
        env["DPT_FAULTS"] = faults
    env.update(env_extra or {})
    p = subprocess.Popen(
        [sys.executable, SERVE, "--backend", "python",
         "--port", str(port), "--workers", "1",
         "--journal-dir", journal_dir, "--store-dir", store_dir, "--chaos"],
        stdout=subprocess.PIPE, env=env, text=True, cwd=REPO)
    assert "listening" in p.stdout.readline()
    return p


def _port(offset):
    return 24100 + (os.getpid() % 400) * 12 + offset


SWEEP_SPEC = {"kind": "toy", "gates": 60, "seed": 5}  # n=128: 4 rounds saved
SWEEP_PHASES = ["SUBMIT", "START", "ROUND1", "ROUND2", "ROUND3", "ROUND4",
                "DONE"]
# tier-1 kills the service mid-prove; the other six transitions (each
# a ~10 s subprocess restart) are tier2 (scripts/ci.sh chaos runs all seven)
_TIER1_PHASES = ("ROUND3",)


@pytest.mark.parametrize("phase", [
    p if p in _TIER1_PHASES else pytest.param(p, marks=pytest.mark.tier2)
    for p in SWEEP_PHASES])
def test_service_killed_at_each_journal_transition(tmp_path, phase):
    """The ISSUE-7 acceptance sweep: os._exit at one exact journal
    occurrence, restart on the same dirs, byte-identical completion with
    no proving repeated past the last checkpointed round."""
    port = _port(SWEEP_PHASES.index(phase))
    jdir, sdir = str(tmp_path / "journal"), str(tmp_path / "store")
    os.makedirs(sdir, exist_ok=True)
    spec = dict(SWEEP_SPEC, job_key=f"sweep-{phase}")

    p = _spawn_serve(port, jdir, sdir, faults=f"kill:at=journal:tag={phase}")
    try:
        with ServiceClient("127.0.0.1", port) as c:
            c.submit(spec)
    except (ConnectionError, OSError):
        pass  # SUBMIT-phase kill dies before the reply frame
    assert p.wait(timeout=120) == 1  # died via os._exit(1), not cleanly

    p2 = _spawn_serve(port, jdir, sdir)
    try:
        with ServiceClient("127.0.0.1", port) as c:
            # duplicate submit dedups onto the recovered job — also how a
            # client whose SUBMIT reply was lost in the crash finds its id
            r = c.submit(spec)
            assert r["dedup"] is True, r
            st = c.wait(r["job_id"], timeout_s=180)
            assert st["state"] == "done", st
            _hdr, blob = c.result(r["job_id"])
            m = c.metrics()
    finally:
        p2.terminate()
        p2.wait(timeout=30)

    assert blob == reference_proof(spec), \
        f"recovered proof bytes diverged (killed at {phase})"
    ctr, hists = m["counters"], m["histograms"]
    if phase == "DONE":
        # finished before the kill: served from the proof artifact,
        # nothing proved in the restarted service
        assert ctr.get("jobs_completed", 0) == 0
        assert ctr.get("jobs_recovered_finished", 0) == 1
    else:
        assert ctr.get("jobs_recovered", 0) == 1
        if phase.startswith("ROUND"):
            # resumed past the checkpoint: the completed rounds are NOT
            # proved again (round1 histogram would exist if they were)
            assert ctr.get("checkpoint_resumes", 0) >= 1
            assert "prove_round/round1" not in hists, \
                f"round 1 re-proved after {phase} kill"


@pytest.mark.tier2
def test_sigterm_graceful_drain_then_resume(tmp_path):
    """SIGTERM: admission stops, the drain deadline forces a mid-prove
    checkpoint park, exit code 0; restart resumes byte-identically."""
    port = _port(8)
    jdir, sdir = str(tmp_path / "journal"), str(tmp_path / "store")
    os.makedirs(sdir, exist_ok=True)
    spec = {"kind": "toy", "gates": 300, "seed": 8, "job_key": "drain-1"}

    p = _spawn_serve(port, jdir, sdir,
                     env_extra={"DPT_DRAIN_TIMEOUT_S": "0.05"})
    with ServiceClient("127.0.0.1", port) as c:
        jid = c.submit(spec)["job_id"]
        # wait until it is actually proving so the drain has work to park
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            if c.status(jid)["state"] == "running":
                break
            time.sleep(0.02)
    p.send_signal(signal.SIGTERM)
    assert p.wait(timeout=60) == 0  # graceful drain exits 0
    out = p.stdout.read()
    assert '"drained": "SIGTERM"' in out

    p2 = _spawn_serve(port, jdir, sdir)
    try:
        with ServiceClient("127.0.0.1", port) as c:
            r = c.submit(spec)
            assert r["dedup"] is True
            assert c.wait(r["job_id"], timeout_s=240)["state"] == "done"
            _hdr, blob = c.result(r["job_id"])
    finally:
        p2.terminate()
        p2.wait(timeout=30)
    assert blob == reference_proof(spec)


def test_serve_rejects_bad_journal_dir(tmp_path):
    """--journal-dir fail-fast: a path that cannot take the journal must
    stop the daemon before it accepts jobs it cannot make durable."""
    not_a_dir = tmp_path / "file"
    not_a_dir.write_text("x")
    p = subprocess.run(
        [sys.executable, SERVE, "--journal-dir", str(not_a_dir)],
        capture_output=True, text=True, cwd=REPO, timeout=60,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0
    assert "--journal-dir" in p.stderr


# --- in-process recovery paths ----------------------------------------------

TOY = {"kind": "toy", "gates": 8}


def test_dedup_across_restart_serves_artifact_without_reprove(tmp_path):
    jdir, sdir = str(tmp_path / "j"), str(tmp_path / "s")
    spec = dict(TOY, seed=3, job_key="dd-1")
    svc = ProofService(port=0, prover_workers=1, journal_dir=jdir,
                       store_dir=sdir).start()
    try:
        job = svc.submit_local(spec)
        assert job.done_event.wait(120) and job.state == "done"
        want = job.proof_bytes
        # in-flight dedup too
        j2, dd = svc.submit_ex(spec)
        assert dd and j2.id == job.id
    finally:
        svc.shutdown()

    svc2 = ProofService(port=0, prover_workers=1, journal_dir=jdir,
                        store_dir=sdir).start()
    try:
        j3, dd3 = svc2.submit_ex(spec)
        assert dd3 and j3.id == job.id and j3.state == "done"
        assert j3.proof_bytes == want == reference_proof(spec)
        ctr = svc2.metrics.snapshot()["counters"]
        assert ctr.get("jobs_completed", 0) == 0      # no re-prove
        assert ctr["jobs_recovered_finished"] == 1
        assert ctr["dedup_hits"] == 1
        # the finished proof is a normal store artifact: STORE_FETCHable
        from distributed_plonk_tpu.store import load_proof
        blob, pub, _meta = load_proof(svc2.store, job.id)
        assert blob == want
    finally:
        svc2.shutdown()


def test_crash_midprove_recovers_without_reproving_rounds(tmp_path):
    """In-process twin of the subprocess sweep (and of bench.py's
    service_restart_recovery_ok canary): crash() at journal ROUND2."""
    jdir, sdir = str(tmp_path / "j"), str(tmp_path / "s")
    spec = {"kind": "toy", "gates": 60, "seed": 5, "job_key": "crash-1"}
    box = {}
    faults = FaultInjector([Rule("kill", tag="ROUND2", plane="journal")],
                           kill_cb=lambda _label: box["svc"].crash())
    svc = ProofService(port=0, prover_workers=1, journal_dir=jdir,
                       store_dir=sdir, chaos=True, faults=faults)
    box["svc"] = svc
    svc.start()
    job = svc.submit_local(spec)
    deadline = time.monotonic() + 120
    while not svc._stopped.is_set():
        assert time.monotonic() < deadline, "service never crashed"
        time.sleep(0.02)
    assert job.state != "done"

    svc2 = ProofService(port=0, prover_workers=1, journal_dir=jdir,
                        store_dir=sdir).start()
    try:
        j2, dd = svc2.submit_ex(spec)
        assert dd and j2.done_event.wait(180) and j2.state == "done"
        m = svc2.metrics.snapshot()
        assert m["counters"]["checkpoint_resumes"] >= 1
        assert "prove_round/round1" not in m["histograms"]
        assert j2.proof_bytes == reference_proof(spec)
    finally:
        svc2.shutdown()


@pytest.mark.tier2
def test_ttl_shed_verdict_journaled_and_queryable(tmp_path):
    jdir = str(tmp_path / "j")
    svc = ProofService(port=0, prover_workers=1, journal_dir=jdir).start()
    try:
        big = svc.submit_local(dict(TOY, gates=300, seed=1))
        tiny = svc.submit_local(dict(TOY, seed=2, ttl_s=0.05,
                                     job_key="shed-1"))
        assert tiny.done_event.wait(240)
        assert tiny.state == "shed" and "ttl expired" in tiny.error
        assert big.done_event.wait(240) and big.state == "done"
        assert svc.metrics.snapshot()["counters"]["jobs_shed"] == 1
        # the wire view of a shed verdict
        assert tiny.status()["state"] == "shed"
    finally:
        svc.shutdown()
    # verdict survives a restart (journaled SHED record)
    svc2 = ProofService(port=0, prover_workers=1, journal_dir=jdir).start()
    try:
        j2 = svc2.get_job(tiny.id)
        assert j2.state == "shed" and "ttl expired" in j2.error
        # dedup maps the key to the shed verdict, not a fresh prove
        j3, dd = svc2.submit_ex(dict(TOY, seed=2, ttl_s=0.05,
                                     job_key="shed-1"))
        assert dd and j3.state == "shed"
    finally:
        svc2.shutdown()


def test_ttl_expired_during_outage_is_shed_at_recovery(tmp_path):
    """The deadline is the ORIGINAL submission's: a job whose TTL lapsed
    while the service was down is shed at recovery, not resumed — and a
    restart must never silently extend a TTL."""
    jdir = str(tmp_path / "j")
    svc = ProofService(port=0, prover_workers=1, journal_dir=jdir)
    # no start(): the job sits queued, then the 'process' dies
    job = svc.submit_local(dict(TOY, seed=4, ttl_s=0.1, job_key="out-1"))
    svc.crash()
    time.sleep(0.2)  # the outage outlives the TTL

    svc2 = ProofService(port=0, prover_workers=1, journal_dir=jdir)
    svc2._recover()
    j2 = svc2.get_job(job.id)
    assert j2.state == "shed" and "during restart" in j2.error
    assert svc2.metrics.snapshot()["counters"]["jobs_shed"] == 1
    assert svc2.queue.depth() == 0
    svc2.crash()


def test_rejected_submit_never_resurrects(tmp_path):
    """A queue_full rejection is journaled terminally: replay must not
    re-enqueue a job whose client was told 'no'."""
    jdir = str(tmp_path / "j")
    svc = ProofService(port=0, prover_workers=1, queue_depth=1,
                       journal_dir=jdir)
    # no start(): the scheduler must not drain the queue mid-test
    svc.submit_local(dict(TOY, seed=1))
    with pytest.raises(Rejected):
        svc.submit_local(dict(TOY, seed=2, job_key="rej-1"))
    svc.crash()

    svc2 = ProofService(port=0, prover_workers=1, journal_dir=jdir)
    svc2._recover()   # start() would also kick the scheduler off
    rejected = [j for j in svc2.jobs.values() if j.job_key == "rej-1"]
    assert rejected and rejected[0].state == "shed"
    assert "rejected" in rejected[0].error
    assert svc2.queue.depth() == 1  # only the admitted job came back
    # the refused job_key is FREE after restart, exactly as on the live
    # path: a retry is a fresh admission, not a dedup onto the verdict
    j_retry, dd = svc2.submit_ex(dict(TOY, seed=2, job_key="rej-1"))
    assert not dd and j_retry.state == "queued"
    svc2.crash()


def test_recovery_force_enqueues_past_depth_cap(tmp_path):
    """Recovery re-admits what the previous process admitted, even past
    this process's queue depth — a restart must never shed valid work."""
    jdir = str(tmp_path / "j")
    svc = ProofService(port=0, prover_workers=1, queue_depth=8,
                       journal_dir=jdir)
    for i in range(6):
        svc.submit_local(dict(TOY, seed=10 + i))
    svc.crash()
    svc2 = ProofService(port=0, prover_workers=1, queue_depth=2,
                        journal_dir=jdir)
    svc2._recover()
    assert svc2.queue.depth() == 6
    assert svc2.metrics.snapshot()["counters"]["jobs_recovered"] == 6
    svc2.crash()


# --- bucket-cache per-key latch (ROADMAP remainder) --------------------------

def test_bucket_latch_cold_miss_does_not_stall_other_shapes():
    """The PR-6 remainder this PR closes: one shape's slow cold load
    (unreachable peer, long build) must not block other shapes' lookups.
    Timing-bound: B resolves while A is still stuck in its load."""
    cache = BucketCache(Metrics())
    spec_a = JobSpec.from_wire(dict(TOY, gates=8, seed=0))
    spec_b = JobSpec.from_wire(dict(TOY, gates=12, seed=0))
    stall = threading.Event()
    entered = threading.Event()
    real = cache._load_or_build

    def slow_load(spec, key):
        if spec.params["gates"] == 8:
            entered.set()
            assert stall.wait(30)
        return real(spec, key)

    cache._load_or_build = slow_load
    t = threading.Thread(target=cache.get, args=(spec_a,), daemon=True)
    t.start()
    assert entered.wait(10)
    t0 = time.monotonic()
    cache.get(spec_b)               # must not wait for A's latch
    elapsed = time.monotonic() - t0
    stall.set()
    t.join(timeout=60)
    assert elapsed < 5, \
        f"shape B stalled {elapsed:.1f}s behind shape A's cold load"


def test_bucket_latch_concurrent_same_shape_builds_once():
    cache = BucketCache(Metrics())
    spec = JobSpec.from_wire(dict(TOY, gates=8, seed=0))
    builds = []
    real = cache._load_or_build

    def counting_load(s, key):
        builds.append(key)
        time.sleep(0.1)             # widen the race window
        return real(s, key)

    cache._load_or_build = counting_load
    results = []
    threads = [threading.Thread(
        target=lambda: results.append(cache.get(spec)), daemon=True)
        for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert len(builds) == 1, f"duplicated key setup: {builds}"
    assert len(results) == 4 and all(r is results[0] for r in results)
    ctr = cache.metrics.snapshot()["counters"]
    assert ctr.get("bucket_latch_waits", 0) == 3
