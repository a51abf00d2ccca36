"""Fault-domain tests: chaos sweep over every distributed protocol phase,
cross-host checkpoint resume via STORE_FETCH, circuit breaker open /
re-admission, fault-injection layer, FFT2 replay-cache bound.

The acceptance surface of the fleet fault domain (ISSUE 6): a worker
killed at ANY phase of a distributed prove — MSM, FFT_INIT, FFT1, the
EXCHANGE all-to-all, FFT2_PREPARE, FFT2 — still yields proof bytes
IDENTICAL to the host oracle's, and a worker restarted on a fresh host
resumes a prove from a store-fetched checkpoint without rebuilding keys.
"""

import os
import random
import subprocess
import sys
import time

import pytest

from conftest import free_port_block
from distributed_plonk_tpu import curve as C
from distributed_plonk_tpu.constants import R_MOD
from distributed_plonk_tpu.runtime import protocol
from distributed_plonk_tpu.runtime.dispatcher import (Dispatcher,
                                                      RemoteBackend,
                                                      WorkerHandle)
from distributed_plonk_tpu.runtime.faults import FaultInjector, Rule
from distributed_plonk_tpu.runtime.health import LivenessTracker
from distributed_plonk_tpu.runtime.netconfig import NetworkConfig
from distributed_plonk_tpu.service.metrics import Metrics

RNG = random.Random(0xFA17)
REPO = os.path.normpath(os.path.join(os.path.dirname(__file__), ".."))

# Deflake discipline (ISSUE 12): these tests share the machine with the
# rest of tier-1 — worker subprocess startup and 5 s HEALTH probes that
# are instant in isolation can blow fixed windows under load. EVERY wait
# in this module is event-driven against a generous deadline (the happy
# path still exits in milliseconds), never a fixed sleep or a one-shot
# probe.
_LOAD_BUDGET_S = float(os.environ.get("DPT_TEST_WAIT_S", "120"))


def _wait_for(cond, timeout_s=None, interval=0.05, msg=""):
    """Poll `cond` until truthy; returns its value. AssertionError with
    `msg` on deadline — the event-driven replacement for fixed sleeps."""
    deadline = time.monotonic() + (timeout_s or _LOAD_BUDGET_S)
    while True:
        got = cond()
        if got:
            return got
        if time.monotonic() >= deadline:
            raise AssertionError(f"timed out waiting for {msg or cond}")
        time.sleep(interval)


def _probe_until(handle, timeout_s=None, probe_ms=5000):
    """Fresh-connection HEALTH snapshot, retried: one probe can time out
    under tier-1 load without the worker being down."""
    return _wait_for(lambda: handle.probe(timeout_ms=probe_ms),
                     timeout_s=timeout_s, interval=0.2,
                     msg=f"probe of {handle.host}:{handle.port}")


@pytest.fixture(autouse=True)
def _fast_failure_knobs(monkeypatch):
    """Tight backoff so recovery paths run in test time, not wall-clock
    minutes (the knobs are class attributes latched from env at import)."""
    monkeypatch.setattr(WorkerHandle, "RECONNECT_TRIES", 2)
    monkeypatch.setattr(WorkerHandle, "BACKOFF_BASE_S", 0.01)
    monkeypatch.setattr(WorkerHandle, "BACKOFF_MAX_S", 0.05)
    monkeypatch.setattr(WorkerHandle, "TIMEOUT_MS", 120000)


class Fleet:
    """N worker processes whose members can be killed and restarted by
    index — the process-level chaos plane the FaultInjector's kill_cb
    plugs into."""

    def __init__(self, tmp_path, n, port_base, backend="python"):
        self.n = n
        self.backend = backend
        base = free_port_block(n, port_base)
        self.cfg = NetworkConfig(
            [f"127.0.0.1:{base + i}" for i in range(n)])
        self.cfg_path = str(tmp_path / "network.json")
        self.cfg.save(self.cfg_path)
        self.procs = [None] * n
        for i in range(n):
            self.start(i)

    def start(self, i):
        self.procs[i] = subprocess.Popen(
            [sys.executable, "-m", "distributed_plonk_tpu.runtime.worker",
             str(i), self.cfg_path, "--backend", self.backend], cwd=REPO)

    def kill(self, i):
        p = self.procs[i]
        if p is not None and p.poll() is None:
            p.kill()
            p.wait(timeout=10)

    def restart(self, i):
        self.kill(i)
        self.start(i)

    def wait_up(self, timeout_s=None):
        """Block until every worker answers a fresh-connection probe.
        Budget covers loaded-machine subprocess startup (interpreter +
        imports can take tens of seconds when tier-1 owns the cores)."""
        deadline = time.monotonic() + (timeout_s or _LOAD_BUDGET_S)
        pending = set(range(self.n))
        while pending and time.monotonic() < deadline:
            for i in sorted(pending):
                h, p = self.cfg.workers[i]
                if WorkerHandle(h, p).probe(timeout_ms=5000) is not None:
                    pending.discard(i)
            if pending:
                time.sleep(0.2)
        assert not pending, f"workers {sorted(pending)} did not come up"

    def close(self):
        for i in range(self.n):
            if self.procs[i] is not None and self.procs[i].poll() is None:
                self.procs[i].kill()
        for p in self.procs:
            if p is not None:
                p.wait(timeout=10)


@pytest.fixture(scope="module")
def fleet(tmp_path_factory):
    f = Fleet(tmp_path_factory.mktemp("faults"), 3, 29000)
    try:
        f.wait_up()
        yield f
    finally:
        f.close()


def _dispatcher(fleet, metrics=None, faults=None, breaker_k=2):
    d = Dispatcher(fleet.cfg, metrics=metrics, faults=faults)
    # fast breaker/probe windows; re-point the handles at the new tracker
    d.tracker = LivenessTracker(fleet.n, breaker_k=breaker_k,
                                probe_base_s=0.05, probe_max_s=0.5,
                                metrics=d.metrics)
    for w in d.workers:
        w.tracker = d.tracker
    return d


def _close(d):
    """Drop dispatcher connections WITHOUT shutting the shared fleet down."""
    for w in d.workers:
        w.close()
    d.pool.shutdown(wait=False)


# --- the chaos sweep ---------------------------------------------------------

# (label, tag the rule matches on, rule-target worker, process to kill):
# killing worker 1 while the dispatcher talks to worker 0 at FFT2_PREPARE
# is the EXCHANGE case — the death is only observable through the peer
# all-to-all plane, and failure attribution needs the fleet probe
_SWEEP = [
    ("msm", protocol.MSM, 1, 1),
    ("fft_init", protocol.FFT_INIT, 1, 1),
    ("fft1", protocol.FFT1, 1, 1),
    ("exchange", protocol.FFT2_PREPARE, 0, 1),
    ("fft2_prepare", protocol.FFT2_PREPARE, 1, 1),
    ("fft2", protocol.FFT2, 1, 1),
]


@pytest.mark.parametrize("label,tag,rule_worker,victim",
                         _SWEEP, ids=[s[0] for s in _SWEEP])
def test_chaos_sweep_byte_identical_proof(fleet, proven, label, tag,
                                          rule_worker, victim):
    """Kill a worker at one exact protocol phase of a fully distributed
    prove (sharded 4-step FFTs + distributed MSM): the fleet recovers —
    range adoption for MSM, probe + replan (or quorum degradation) for the
    FFT — and the proof bytes match the host oracle exactly."""
    ckt, pk, vk, proof_host = proven
    fleet.restart(victim)  # clean slate from any earlier phase
    fleet.wait_up()
    metrics = Metrics()
    faults = FaultInjector(
        [Rule("kill", tag=tag, worker=rule_worker, nth=1)],
        kill_cb=lambda _w: fleet.kill(victim), metrics=metrics)
    d = _dispatcher(fleet, metrics=metrics, faults=faults)
    try:
        proof = prove_remote(ckt, pk, d)
        assert proof.opening_proof == proof_host.opening_proof, label
        assert proof.shifted_opening_proof == proof_host.shifted_opening_proof
        assert proof.wires_poly_comms == proof_host.wires_poly_comms
        assert proof.split_quot_poly_comms == proof_host.split_quot_poly_comms
        snap = metrics.snapshot()["counters"]
        assert snap.get("faults_injected_kill", 0) == 1, label
        # at least one recovery event must have fired somewhere
        recoveries = sum(snap.get(k, 0) for k in (
            "fleet_range_adoptions", "fleet_fft_replans",
            "fleet_fft_degraded", "fleet_reconnects"))
        assert recoveries >= 1, (label, snap)
    finally:
        _close(d)
    fleet.restart(victim)
    fleet.wait_up()


def prove_remote(ckt, pk, d):
    from distributed_plonk_tpu.prover import prove
    return prove(random.Random(1), ckt, pk,
                 RemoteBackend(d, dist_fft_min=ckt.n))


def test_fft_quorum_degradation(fleet, proven):
    """With every worker but one dead, fft_dist degrades to the
    single-worker NTT path and still returns oracle bytes."""
    from distributed_plonk_tpu import poly as P
    fleet.wait_up()
    metrics = Metrics()
    d = _dispatcher(fleet, metrics=metrics, breaker_k=1)
    try:
        n = 64
        values = [RNG.randrange(R_MOD) for _ in range(n)]
        fleet.kill(1)
        fleet.kill(2)
        got = d.fft_dist(values, inverse=True)
        assert got == P.ifft(P.Domain(n), values)
        snap = metrics.snapshot()["counters"]
        assert snap.get("fleet_fft_degraded", 0) >= 1
    finally:
        _close(d)
    fleet.restart(1)
    fleet.restart(2)
    fleet.wait_up()


# --- circuit breaker + re-admission ------------------------------------------

def test_breaker_open_adoption_and_readmission(fleet):
    fleet.wait_up()
    metrics = Metrics()
    d = _dispatcher(fleet, metrics=metrics, breaker_k=1)
    try:
        n = 48
        bases = [C.g1_mul(C.G1_GEN, RNG.randrange(1, R_MOD))
                 for _ in range(n)]
        scalars = [RNG.randrange(R_MOD) for _ in range(n)]
        want = C.g1_msm(bases, scalars)
        d.init_bases(bases)
        assert d.msm(scalars) == want

        fleet.kill(2)
        assert d.msm(scalars) == want          # range 2 adopted
        assert d._adopted.get(2) is not None
        assert not d.tracker.usable(2)         # breaker open
        snap = metrics.snapshot()["counters"]
        assert snap.get("fleet_breaker_opens", 0) >= 1
        assert snap.get("fleet_range_adoptions", 0) >= 1

        # breaker-open worker fast-fails without dialing
        from distributed_plonk_tpu.runtime.dispatcher import WorkerUnavailable
        with pytest.raises(WorkerUnavailable):
            d.workers[2].call(protocol.PING)

        # worker returns on the same port: next due probe re-admits it and
        # re-provisions its own range (the adoption redirect is dropped).
        # Event-driven: one half-open probe can time out under load (the
        # 5 s budget is not a liveness verdict on a loaded box), so keep
        # forcing the window until the re-admission actually lands — the
        # MSM result must be correct on EVERY iteration either way.
        fleet.restart(2)
        fleet.wait_up()

        def _readmitted():
            d.tracker.force_probe(2)
            assert d.msm(scalars) == want
            return d.tracker.usable(2) and 2 not in d._adopted
        _wait_for(_readmitted, msg="worker 2 re-admission")
        snap = metrics.snapshot()["counters"]
        assert snap.get("fleet_readmissions", 0) >= 1
        # and the re-admitted worker actually serves again
        assert d.msm(scalars) == want
        stats = _probe_until(d.workers[2])
        assert stats["served"] >= 1
    finally:
        _close(d)


def test_drop_and_corrupt_frames_recovered(fleet):
    """A dropped frame is resent over a fresh stream (idempotent worker
    handlers); a tag-corrupted frame draws a loud ERR and the recovery
    path recomputes — results stay exact in both cases."""
    from distributed_plonk_tpu import poly as P
    fleet.wait_up()
    metrics = Metrics()
    faults = FaultInjector(
        [Rule("drop", tag=protocol.NTT, nth=1),
         Rule("corrupt", tag=protocol.MSM, nth=1)], metrics=metrics)
    d = _dispatcher(fleet, metrics=metrics, faults=faults)
    try:
        n = 32
        domain = P.Domain(n)
        values = [RNG.randrange(R_MOD) for _ in range(n)]
        assert d.ntt(values) == P.fft(domain, values)

        bases = [C.g1_mul(C.G1_GEN, RNG.randrange(1, R_MOD))
                 for _ in range(n)]
        scalars = [RNG.randrange(R_MOD) for _ in range(n)]
        d.init_bases(bases)
        assert d.msm(scalars) == C.g1_msm(bases, scalars)

        snap = metrics.snapshot()["counters"]
        assert snap.get("faults_injected_drop", 0) == 1
        assert snap.get("faults_injected_corrupt", 0) == 1
        assert snap.get("fleet_reconnects", 0) >= 1
    finally:
        _close(d)


def test_failed_base_push_never_serves_stale_bases(fleet):
    """Regression (the intermittent wrong-proof behind the fleet-TCP
    flakes): when one worker's INIT_BASES push fails during a
    re-provisioning, that worker still holds the PREVIOUS provisioning's
    set under the same id — an MSM routed to it would succeed with the
    wrong bases. The dispatcher must remember the failed push and route
    that range through the adoption path (fresh bases re-pushed), never
    trust the stale owner."""
    fleet.wait_up()
    metrics = Metrics()
    # worker 2's SECOND INIT_BASES frame draws an ERR (tag corrupted):
    # the first provisioning lands everywhere, the second one fails for
    # worker 2 only — leaving its set-2 bases stale
    faults = FaultInjector(
        [Rule("corrupt", tag=protocol.INIT_BASES, worker=2, nth=2)],
        metrics=metrics)
    d = _dispatcher(fleet, metrics=metrics, faults=faults)
    try:
        n = 30
        bases1 = [C.g1_mul(C.G1_GEN, RNG.randrange(1, R_MOD))
                  for _ in range(n)]
        scalars1 = [RNG.randrange(R_MOD) for _ in range(n)]
        d.init_bases(bases1)
        assert d.msm(scalars1) == C.g1_msm(bases1, scalars1)
        assert d._unprovisioned == set()

        bases2 = [C.g1_mul(C.G1_GEN, RNG.randrange(1, R_MOD))
                  for _ in range(n)]
        scalars2 = [RNG.randrange(R_MOD) for _ in range(n)]
        d.init_bases(bases2)
        assert d._unprovisioned == {2}
        # stale-owner routing would return a WRONG point here; the
        # adoption path re-pushes range 2's new bases and stays exact
        assert d.msm(scalars2) == C.g1_msm(bases2, scalars2)
        snap = metrics.snapshot()["counters"]
        assert snap.get("faults_injected_corrupt", 0) == 1
        assert snap.get("fleet_range_adoptions", 0) >= 1
        assert 2 not in d._unprovisioned
        # later msms keep routing through the adopter, still exact
        assert d.msm(scalars2) == C.g1_msm(bases2, scalars2)
    finally:
        _close(d)


def test_liveness_tracker_unit():
    t = LivenessTracker(2, breaker_k=3, probe_base_s=0.01, probe_max_s=0.05)
    assert t.usable(0)
    t.record_failure(0)
    t.record_failure(0)
    assert t.usable(0)            # 2 < K
    assert t.record_failure(0)    # K-th opens
    assert not t.usable(0)
    assert not t.probe_due(0)     # backoff window not yet elapsed
    time.sleep(0.06)
    assert t.probe_due(0)
    assert not t.probe_due(0)     # half-open: one owner per window
    assert t.record_ok(0)         # probe success re-admits
    assert t.usable(0)
    # success resets the consecutive count
    t.record_failure(0)
    t.record_failure(0)
    t.record_ok(0)
    t.record_failure(0)
    t.record_failure(0)
    assert t.usable(0)
    # an unrelated worker is untouched throughout
    assert t.usable(1)


# --- store-backed checkpoints + cross-host resume ----------------------------

def test_cross_host_resume_via_store_fetch(tmp_path, proven):
    """Host A dies mid-prove with its checkpoint in the artifact store; a
    'replacement host' (fresh store) STORE_FETCHes the snapshot + bucket
    keys over the wire and finishes the prove — byte-identical to an
    uninterrupted run, with zero key building on the new host."""
    from distributed_plonk_tpu.backend.python_backend import PythonBackend
    from distributed_plonk_tpu.checkpoint import StoreCheckpoint
    from distributed_plonk_tpu.prover import prove
    from distributed_plonk_tpu.service import ProofService
    from distributed_plonk_tpu.store import ArtifactStore, fetch_into

    ckt, pk, vk, proof_host = proven
    store_a = ArtifactStore(str(tmp_path / "host_a"))

    class _DieAfterRound2(StoreCheckpoint):
        def save(self, round_no, *a, **kw):
            super().save(round_no, *a, **kw)
            if round_no == 2:
                raise RuntimeError("host A lost power")

    with pytest.raises(RuntimeError, match="lost power"):
        prove(random.Random(1), ckt, pk, PythonBackend(),
              checkpoint=_DieAfterRound2(store_a, "job-xh"))
    assert "ckpt:job-xh" in store_a.keys()

    # host A's store is served over the wire by its (restarted) service
    svc = ProofService(port=0, store_dir=str(tmp_path / "host_a")).start()
    try:
        store_b = ArtifactStore(str(tmp_path / "host_b"))
        blob = fetch_into(store_b, "127.0.0.1", svc.port, "ckpt:job-xh")
        assert blob is not None
        assert "ckpt:job-xh" in store_b.keys()
        # a missing key is a clean miss, not an exception
        assert fetch_into(store_b, "127.0.0.1", svc.port, "nope") is None
    finally:
        svc.shutdown()

    # replacement host resumes at round 3 and matches the golden bytes
    resumed = StoreCheckpoint(store_b, "job-xh")
    assert resumed.load(_fingerprint(pk, ckt))["round"] == 2
    proof = prove(random.Random(1), ckt, pk, PythonBackend(),
                  checkpoint=resumed)
    assert proof.opening_proof == proof_host.opening_proof
    assert proof.wires_evals == proof_host.wires_evals
    assert resumed.load(_fingerprint(pk, ckt)) is None  # cleared on success


def _fingerprint(pk, ckt):
    from distributed_plonk_tpu.checkpoint import workload_fingerprint
    return workload_fingerprint(pk.vk, ckt.public_input())


def test_bucket_keys_from_peer_no_rebuild(tmp_path, monkeypatch):
    """A fresh service with an empty store and a warm peer serves a seen
    shape WITHOUT building keys: the bucket blob arrives via STORE_FETCH
    (key build forbidden by monkeypatch on the new host)."""
    import json
    from distributed_plonk_tpu.service import (ProofService, ServiceClient)
    from distributed_plonk_tpu.service import jobs as J

    spec = {"kind": "toy", "gates": 16, "seed": 5}
    svc_a = ProofService(port=0, prover_workers=1,
                         store_dir=str(tmp_path / "a")).start()
    try:
        with ServiceClient("127.0.0.1", svc_a.port) as c:
            jid = c.submit(spec)["job_id"]
            st = c.wait(jid, timeout_s=120)
            assert st["state"] == "done"

        # host B: empty store, peer = host A. Building keys is forbidden.
        def _forbidden(*a, **kw):
            raise AssertionError("key build on the warm-peer path")
        monkeypatch.setattr(J, "build_bucket_keys", _forbidden)
        svc_b = ProofService(port=0, prover_workers=1,
                             store_dir=str(tmp_path / "b"),
                             store_peers=[("127.0.0.1", svc_a.port)]).start()
        try:
            with ServiceClient("127.0.0.1", svc_b.port) as c:
                jid = c.submit(dict(spec, seed=6))["job_id"]
                st = c.wait(jid, timeout_s=120)
                assert st["state"] == "done", json.dumps(st)
                m = c.metrics()
            assert m["counters"].get("bucket_peer_hits", 0) == 1
            assert m["counters"].get("bucket_misses", 0) == 0
        finally:
            svc_b.shutdown()
    finally:
        svc_a.shutdown()


def test_corrupt_checkpoint_detected_then_clean_restart(tmp_path):
    """corrupt_ckpt injection flips a byte under the just-saved snapshot;
    a kill at the same round forces a resume attempt. The store's SHA-256
    rejects the snapshot, the retry restarts from round 1 (not garbage),
    and the proof still verifies."""
    import json
    from distributed_plonk_tpu.runtime.faults import FaultInjector, Rule
    from distributed_plonk_tpu.service import ProofService, ServiceClient
    from distributed_plonk_tpu.service.jobs import build_bucket_keys, JobSpec
    from distributed_plonk_tpu.proof_io import deserialize_proof
    from distributed_plonk_tpu.verifier import verify

    faults = FaultInjector([Rule("corrupt_ckpt", tag=2, nth=1)])
    svc = ProofService(port=0, prover_workers=1, chaos=True,
                       store_dir=str(tmp_path / "s"), faults=faults).start()
    try:
        with ServiceClient("127.0.0.1", svc.port) as c:
            jid = c.submit({"kind": "toy", "gates": 60, "seed": 9})["job_id"]
            deadline = time.monotonic() + 60
            killed = False
            while time.monotonic() < deadline and not killed:
                st = c.status(jid)
                if st["state"] in ("done", "failed"):
                    break
                if st["state"] == "running":
                    try:
                        c.kill_worker(job_id=jid, at_round=2)
                        killed = True
                    except Exception:
                        break
                time.sleep(0.005)
            st = c.wait(jid, timeout_s=120)
            assert st["state"] == "done", json.dumps(st)
            header, blob = c.result(jid)
            m = c.metrics()
        spec = JobSpec.from_wire(header["spec"])
        vk = build_bucket_keys(spec)[2]
        pub = [int(x, 16) for x in header["public_input"]]
        assert verify(vk, pub, deserialize_proof(blob),
                      rng=random.Random(1))
        if killed and st["retries"]:
            # the retry hit the corrupted snapshot: detected, not resumed
            assert m["counters"].get("faults_ckpt_corrupted", 0) >= 1
            assert m["counters"].get("checkpoint_resumes", 0) == 0
    finally:
        svc.shutdown()


# --- FFT2 replay cache bound -------------------------------------------------

def test_fft_task_cache_capped():
    from distributed_plonk_tpu.runtime.worker import _evict_fft_tasks

    class T:
        def __init__(self, created, done_at=None):
            self.created = created
            self.done_at = done_at

    now = 1000.0
    tasks = {}
    # 40 completed (oldest done first) + 40 in-flight
    for i in range(40):
        tasks[i] = T(created=now - 100 + i, done_at=now - 50 + i)
    for i in range(40, 80):
        tasks[i] = T(created=now - 100 + i)
    _evict_fft_tasks(tasks, cap=64, now=now)
    assert len(tasks) == 63  # room for the incoming task
    # completed tasks evicted FIRST, oldest-done first
    done_left = [tid for tid, t in tasks.items() if t.done_at is not None]
    assert done_left == list(range(17, 40))
    # all in-flight survive while completed ones can cover the excess
    assert all(tid in tasks for tid in range(40, 80))
    # when completed can't cover it, oldest in-flight go next
    _evict_fft_tasks(tasks, cap=10, now=now)
    assert len(tasks) == 9
    assert all(t.done_at is None for t in tasks.values())
    assert sorted(tasks) == list(range(71, 80))
    # TTL purge still applies (done TTL is the short one)
    _evict_fft_tasks(tasks, cap=64, now=now + 10000)
    assert not tasks


def test_fft_task_cap_live(fleet):
    """A live worker holds at most DPT_FFT_TASK_CAP resident tasks no
    matter how many FFT_INITs land (HEALTH exposes the table size)."""
    fleet.wait_up()
    d = _dispatcher(fleet)
    try:
        col_ranges = [(0, 1), (1, 2), (2, 4)]
        for t in range(70):
            d.workers[0].call(
                protocol.FFT_INIT,
                protocol.encode_fft_init(10_000 + t, False, False,
                                         16, 4, 4, 0, 2, col_ranges))
        # retried probe: a single 5 s HEALTH round trip can time out
        # under tier-1 load without the worker being down
        snap = _probe_until(d.workers[0])
        assert snap["fft_tasks"] <= 64
    finally:
        _close(d)
