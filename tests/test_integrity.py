"""Result-integrity plane tests (ISSUE 13): silent wrong answers are
detected at the phase boundary, attributed to the lying worker,
quarantined, and healed — with proofs byte-identical to the host oracle
and no corrupted proof ever served.

Acceptance surface: `corrupt:at=data` injected at each of {MSM partial,
FFT panel, round-4 eval} on a 3-worker fleet is detected, attributed to
the injected worker index, and quarantined; the quarantine flows through
LEAVE -> supervisor respawn -> challenge-gated rejoin back to a
full-width fleet; DPT_SELF_VERIFY blocks a corrupted proof from the
journal DONE record and the client; and with the plane OFF everything is
bit-for-bit the pre-integrity behavior with zero new counters.

Wait discipline: event-driven waits against generous deadlines, never
fixed sleeps (this module runs inside ci.sh chaos and tier-1 under
load).
"""

import os
import random
import subprocess
import sys
import time

import pytest

from conftest import free_port_block
from distributed_plonk_tpu import curve as C
from distributed_plonk_tpu import poly as P
from distributed_plonk_tpu.constants import R_MOD
from distributed_plonk_tpu.runtime import integrity as I
from distributed_plonk_tpu.runtime import protocol
from distributed_plonk_tpu.runtime.dispatcher import (Dispatcher,
                                                      RemoteBackend,
                                                      WorkerHandle)
from distributed_plonk_tpu.runtime.faults import FaultInjector, Rule
from distributed_plonk_tpu.runtime.health import LivenessTracker
from distributed_plonk_tpu.runtime.integrity import FleetIntegrity
from distributed_plonk_tpu.runtime.netconfig import NetworkConfig
from distributed_plonk_tpu.runtime.supervisor import WorkerSupervisor
from distributed_plonk_tpu.service.metrics import Metrics

RNG = random.Random(0x5DC)
REPO = os.path.normpath(os.path.join(os.path.dirname(__file__), ".."))
_LOAD_BUDGET_S = float(os.environ.get("DPT_TEST_WAIT_S", "120"))


@pytest.fixture(autouse=True)
def _fast_failure_knobs(monkeypatch):
    monkeypatch.setattr(WorkerHandle, "RECONNECT_TRIES", 2)
    monkeypatch.setattr(WorkerHandle, "BACKOFF_BASE_S", 0.01)
    monkeypatch.setattr(WorkerHandle, "BACKOFF_MAX_S", 0.05)
    monkeypatch.setattr(WorkerHandle, "TIMEOUT_MS", 120000)


def _wait_for(cond, timeout_s=None, interval=0.05, msg=""):
    deadline = time.monotonic() + (timeout_s or _LOAD_BUDGET_S)
    while True:
        got = cond()
        if got:
            return got
        if time.monotonic() >= deadline:
            raise AssertionError(f"timed out waiting for {msg or cond}")
        time.sleep(interval)


# --- unit layer: the check math against the poly oracle ----------------------

def test_transform_identity_all_modes():
    """The closed-form expected output evaluation matches the oracle
    transform's actual power sum for every (inverse, coset) mode, and a
    single flipped element is caught and attributed to its panel."""
    rng = random.Random(11)
    n = 64
    dom = P.Domain(n)
    x = [rng.randrange(R_MOD) for _ in range(n)]
    t = rng.randrange(2, R_MOD)
    r_dim = 1 << ((n.bit_length() - 1) // 2)
    c_dim = n // r_dim
    transforms = {
        (False, False): P.fft, (False, True): P.coset_fft,
        (True, False): P.ifft, (True, True): P.coset_ifft,
    }
    for (inverse, coset), fn in transforms.items():
        y = fn(dom, x)
        assert I.power_sum(y, t) == I.expected_output_eval(
            x, t, inverse, coset), (inverse, coset)
        # per-panel expectation partitions the total
        bounds = [0, r_dim // 3, r_dim]
        parts = [I.expected_panel_eval(x, t, a, b, r_dim, c_dim,
                                       inverse, coset)
                 for a, b in zip(bounds[:-1], bounds[1:])]
        assert sum(parts) % R_MOD == I.power_sum(y, t)
        # flip one element inside panel 0: only panel 0's sum moves
        bad = list(y)
        bad[0] = (bad[0] + 1) % R_MOD  # flat index 0 -> k1=0 (panel 0)
        assert I.cols_power_sum(bad, t, 0, r_dim // 3, r_dim) != parts[0]
        assert I.cols_power_sum(bad, t, r_dim // 3, r_dim, r_dim) \
            == parts[1]
    # rows partition the input power sum (the input-side partial)
    rb = [0, c_dim // 2, c_dim]
    s = sum(I.rows_power_sum(x, t, a, b, c_dim)
            for a, b in zip(rb[:-1], rb[1:])) % R_MOD
    assert s == I.power_sum(x, t)


def test_g1_sanity_checks():
    p = C.g1_mul(C.G1_GEN, 12345)
    assert I.g1_on_curve(p) and I.g1_in_subgroup(p)
    assert I.g1_in_subgroup(None)  # infinity is a fine partial
    off = (p[0], (p[1] + 1) % C.Q_MOD)  # one flipped coordinate
    assert not I.g1_on_curve(off)
    assert not I.g1_in_subgroup(off)


def test_tracker_suspect_is_sticky():
    t = LivenessTracker(2, breaker_k=3, probe_base_s=0.01,
                        probe_max_s=0.05)
    assert t.mark_suspect(0)
    assert not t.mark_suspect(0)       # idempotent
    assert not t.usable(0)
    assert not t.record_ok(0)          # a probe answer does NOT re-admit
    assert not t.usable(0)
    time.sleep(0.06)
    assert not t.probe_due(0)          # no half-open probes for suspects
    assert t.snapshot()[0]["suspect"]
    t.clear_suspect(0)                 # only the challenge gate absolves
    assert t.usable(0)
    assert t.usable(1)                 # neighbor untouched throughout


def test_faults_data_and_proof_planes_parse():
    f = FaultInjector([Rule.parse("corrupt:at=data:tag=MSM:worker=1"),
                       Rule.parse("corrupt:at=proof:rate=1")])
    assert not f.on_data(0, protocol.MSM)    # wrong worker
    assert not f.on_data(1, protocol.NTT)    # wrong tag
    assert f.on_data(1, protocol.MSM)        # fires exactly once
    assert not f.on_data(1, protocol.MSM)
    assert f.on_proof("job")                 # rate=1: every proof
    assert f.on_proof("job")
    # data/proof rules never leak onto the wire plane
    assert f.on_send(1, protocol.MSM, b"") == protocol.MSM


# --- live fleet: detection + attribution per phase ---------------------------

class EnvFleet:
    """N worker subprocesses with PER-WORKER environment — how the
    data-plane chaos (`corrupt:at=data`, parsed by each worker from its
    own DPT_FAULTS) is armed on exactly one fleet member."""

    def __init__(self, tmp_path, n, port_base, envs=None):
        self.n = n
        base = free_port_block(n, port_base)
        self.cfg = NetworkConfig(
            [f"127.0.0.1:{base + i}" for i in range(n)])
        self.cfg_path = str(tmp_path / "network.json")
        self.cfg.save(self.cfg_path)
        self.procs = [None] * n
        self.envs = envs or {}
        for i in range(n):
            self.start(i)

    def start(self, i, faults=None):
        env = dict(os.environ)
        env.pop("DPT_FAULTS", None)
        spec = faults if faults is not None else self.envs.get(i)
        if spec:
            env["DPT_FAULTS"] = spec
        self.procs[i] = subprocess.Popen(
            [sys.executable, "-m", "distributed_plonk_tpu.runtime.worker",
             str(i), self.cfg_path], cwd=REPO, env=env)

    def kill(self, i):
        p = self.procs[i]
        if p is not None and p.poll() is None:
            p.kill()
            p.wait(timeout=10)

    def restart(self, i, faults=None):
        self.kill(i)
        self.start(i, faults=faults)

    def wait_up(self, timeout_s=None):
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
    f = EnvFleet(tmp_path_factory.mktemp("sdc"), 3, 34000)
    try:
        f.wait_up()
        yield f
    finally:
        f.close()


def _dispatcher(fleet, metrics=None, dup_rate=1.0, integrity=True):
    metrics = metrics or Metrics()
    integ = FleetIntegrity(metrics=metrics, msm_dup_rate=dup_rate,
                           rng=random.Random(0xD0)) if integrity else False
    d = Dispatcher(fleet.cfg, metrics=metrics, integrity=integ)
    d.tracker = LivenessTracker(fleet.n, breaker_k=2, probe_base_s=0.05,
                                probe_max_s=0.5, metrics=metrics)
    for w in d.workers:
        w.tracker = d.tracker
    return d, metrics


def _close(d):
    for w in d.workers:
        w.close()
    d.pool.shutdown(wait=False)


def test_wrong_msm_partial_detected_and_attributed(fleet):
    """Worker 1 silently serves a wrong (on-curve, in-subgroup) MSM
    partial: duplicate execution catches it, the third worker's vote
    attributes it, worker 1 is quarantined, and the fold is EXACT."""
    fleet.restart(1, faults="corrupt:at=data:tag=MSM")
    fleet.wait_up()
    d, metrics = _dispatcher(fleet)
    try:
        n = 48
        bases = [C.g1_mul(C.G1_GEN, RNG.randrange(1, R_MOD))
                 for _ in range(n)]
        scalars = [RNG.randrange(R_MOD) for _ in range(n)]
        d.init_bases(bases)
        assert d.msm(scalars) == C.g1_msm(bases, scalars)
        assert d.tracker.is_suspect(1)
        assert not d.tracker.usable(1)
        snap = metrics.snapshot()["counters"]
        assert snap.get("integrity_failures", 0) >= 1
        assert snap.get("integrity_msm_dups", 0) >= 1
        assert snap.get("workers_quarantined", 0) == 1
        # the quarantined fleet keeps serving exact results (survivors)
        assert d.msm(scalars) == C.g1_msm(bases, scalars)
        # HEALTH surfaces both sides: the dispatcher verdict and the
        # worker's own injected-SDC count
        health = d.health()
        assert health[1]["suspect"] is True
        assert health[1]["sdc_injected"] >= 1
        assert health[0]["suspect"] is False
    finally:
        _close(d)
    fleet.restart(1)
    fleet.wait_up()


def test_adopted_range_goes_through_integrity_check(fleet):
    """The recovery path is checked like the primary path (the PR 12
    stale-base class must be caught there too): worker 1 dies, its range
    is adopted by worker 2 — which serves WRONG partials — and the
    duplicate-execution sampler catches the adopted range, quarantines
    worker 2, and recomputes on the one remaining healthy worker."""
    fleet.restart(2, faults="corrupt:at=data:tag=MSM:rate=1")
    fleet.wait_up()
    d, metrics = _dispatcher(fleet)
    try:
        n = 30
        bases = [C.g1_mul(C.G1_GEN, RNG.randrange(1, R_MOD))
                 for _ in range(n)]
        scalars = [RNG.randrange(R_MOD) for _ in range(n)]
        want = C.g1_msm(bases, scalars)
        d.init_bases(bases)
        fleet.kill(1)  # range 1's adoption rotation starts at worker 2
        assert d.msm(scalars) == want
        assert d.tracker.is_suspect(2)
        snap = metrics.snapshot()["counters"]
        assert snap.get("fleet_range_adoptions", 0) >= 1
        assert snap.get("integrity_failures", 0) >= 1
        assert snap.get("workers_quarantined", 0) == 1
        # still exact with one worker dead and one quarantined
        assert d.msm(scalars) == want
    finally:
        _close(d)
    fleet.restart(1)
    fleet.restart(2)
    fleet.wait_up()


def test_wrong_fft_panel_detected_and_attributed(fleet):
    """Worker 1's FFT2 result panel suffers SDC: the gathered output
    fails the Schwartz-Zippel identity, per-panel bisection names worker
    1, it is quarantined, and the replan on survivors returns EXACT
    bytes."""
    fleet.restart(1, faults="corrupt:at=data:tag=FFT2")
    fleet.wait_up()
    d, metrics = _dispatcher(fleet)
    try:
        n = 256
        values = [RNG.randrange(R_MOD) for _ in range(n)]
        assert d.fft_dist(values, inverse=True, coset=True) \
            == P.coset_ifft(P.Domain(n), values)
        assert d.tracker.is_suspect(1)
        snap = metrics.snapshot()["counters"]
        assert snap.get("integrity_failures", 0) >= 1
        assert snap.get("workers_quarantined", 0) == 1
        assert snap.get("fleet_fft_replans", 0) >= 1
    finally:
        _close(d)
    fleet.restart(1)
    fleet.wait_up()


def test_wrong_round4_eval_detected_and_attributed(fleet):
    """Worker 1 serves a wrong partial Horner sum: duplicate execution
    disagrees, the host referee attributes it, and the served value is
    the exact one."""
    fleet.restart(1, faults="corrupt:at=data:tag=EVAL")
    fleet.wait_up()
    d, metrics = _dispatcher(fleet)
    try:
        coeffs = [RNG.randrange(R_MOD) for _ in range(200)]
        z = RNG.randrange(R_MOD)
        assert d.eval_poly(coeffs, z) == P.poly_eval(coeffs, z)
        assert d.tracker.is_suspect(1)
        snap = metrics.snapshot()["counters"]
        assert snap.get("integrity_eval_dups", 0) >= 1
        assert snap.get("integrity_failures", 0) >= 1
        assert snap.get("workers_quarantined", 0) == 1
        # eval_many keeps serving exact values on the survivors
        got = d.eval_many([(coeffs, z), (coeffs[: 60], z)])
        assert got == [P.poly_eval(coeffs, z), P.poly_eval(coeffs[:60], z)]
    finally:
        _close(d)
    fleet.restart(1)
    fleet.wait_up()


def test_ntt_offload_checked_and_rerouted(fleet):
    """The whole-poly NTT offload (round-robin / quorum-degraded path)
    is checked too: a worker serving a wrong NTT is quarantined and the
    rotation serves the exact result from the next worker."""
    fleet.restart(0, faults="corrupt:at=data:tag=NTT")
    fleet.wait_up()
    d, metrics = _dispatcher(fleet)
    try:
        n = 64
        values = [RNG.randrange(R_MOD) for _ in range(n)]
        assert d.ntt(values, worker=0) == P.fft(P.Domain(n), values)
        assert d.tracker.is_suspect(0)
        assert metrics.snapshot()["counters"].get(
            "workers_quarantined", 0) == 1
    finally:
        _close(d)
    fleet.restart(0)
    fleet.wait_up()


def test_challenge_rejects_still_corrupt_worker(fleet):
    """The known-answer challenge gate: a worker that still serves
    wrong NTTs fails it (stays quarantined); a clean worker passes."""
    fleet.restart(2, faults="corrupt:at=data:tag=NTT:rate=1")
    fleet.wait_up()
    d, metrics = _dispatcher(fleet)
    try:
        h2, p2 = fleet.cfg.workers[2]
        assert d.run_challenge(h2, p2) is False
        h0, p0 = fleet.cfg.workers[0]
        assert d.run_challenge(h0, p0) is True
        snap = metrics.snapshot()["counters"]
        assert snap.get("integrity_challenges", 0) == 2
        assert snap.get("integrity_challenges_failed", 0) == 1
    finally:
        _close(d)
    fleet.restart(2)
    fleet.wait_up()


def test_integrity_off_parity(fleet):
    """DPT_INTEGRITY off: legacy wire behavior (no FFT2 piggyback
    requested), exact results, and ZERO integrity counters — the plane
    costs nothing when disabled."""
    fleet.wait_up()
    d, metrics = _dispatcher(fleet, integrity=False)
    try:
        assert d.integrity is None
        n = 64
        values = [RNG.randrange(R_MOD) for _ in range(n)]
        assert d.fft_dist(values, inverse=True) \
            == P.ifft(P.Domain(n), values)
        bases = [C.g1_mul(C.G1_GEN, RNG.randrange(1, R_MOD))
                 for _ in range(16)]
        scalars = [RNG.randrange(R_MOD) for _ in range(16)]
        d.init_bases(bases)
        assert d.msm(scalars) == C.g1_msm(bases, scalars)
        ctr = metrics.snapshot()["counters"]
        assert not any(k.startswith(("integrity", "workers_quarantined"))
                       for k in ctr), ctr
    finally:
        _close(d)


# --- quarantine lifecycle end to end -----------------------------------------

def test_quarantine_leave_respawn_challenge_rejoin(proven, tmp_path):
    """THE lifecycle canary: a supervised 3-worker fleet with one member
    silently corrupting MSM partials. Mid-prove the integrity plane
    detects + attributes it, quarantines it (LEAVE, reason=integrity),
    the supervisor SIGKILLs the alive-but-lying process, the respawn
    re-JOINs through the known-answer challenge, and the fleet heals to
    full width — with BOTH proves byte-identical to the host oracle."""
    from distributed_plonk_tpu.prover import prove

    ckt, pk, vk, proof_host = proven
    metrics = Metrics()
    d = Dispatcher(NetworkConfig([]), metrics=metrics,
                   integrity=FleetIntegrity(metrics=metrics,
                                            msm_dup_rate=1.0,
                                            rng=random.Random(0xE7)))
    d.tracker = LivenessTracker(0, breaker_k=2, probe_base_s=0.05,
                                probe_max_s=0.5, metrics=metrics)
    mserver = d.enable_membership()
    corrupt_spawns = []

    def spawn_cmd(i, slot):
        cmd = [sys.executable, "-m",
               "distributed_plonk_tpu.runtime.worker",
               "--join", f"127.0.0.1:{mserver.port}",
               "--listen", f"127.0.0.1:{slot.port}",
               "--backend", "python"]
        if i == 1 and not corrupt_spawns:
            # only the FIRST incarnation lies; the respawn is clean and
            # must pass the challenge gate
            corrupt_spawns.append(time.monotonic())
            cmd = ["env", "DPT_FAULTS=corrupt:at=data:tag=MSM:rate=1"] \
                + cmd
        return cmd

    sup = WorkerSupervisor("127.0.0.1", mserver.port, n=3,
                           metrics=metrics, cwd=REPO,
                           spawn_cmd=spawn_cmd).start()
    sup.attach_registry(d.membership)
    try:
        _wait_for(lambda: len(d.workers) == 3
                  and len(d.tracker.usable_set()) == 3, msg="fleet up")
        corrupt_idx = d.membership._find("127.0.0.1", sup.slots[1].port)
        assert corrupt_idx is not None

        proof = prove(random.Random(1), ckt, pk,
                      RemoteBackend(d, dist_fft_min=ckt.n))
        assert proof.opening_proof == proof_host.opening_proof
        assert proof.shifted_opening_proof \
            == proof_host.shifted_opening_proof
        assert proof.wires_poly_comms == proof_host.wires_poly_comms
        assert proof.split_quot_poly_comms \
            == proof_host.split_quot_poly_comms

        snap = metrics.snapshot()["counters"]
        assert snap.get("workers_quarantined", 0) >= 1
        assert snap.get("integrity_failures", 0) >= 1
        assert snap.get("membership_leaves", 0) >= 1

        # heal: supervisor kills the liar, respawn rejoins via the
        # challenge, fleet returns to full width SCHEDULABLE
        _wait_for(lambda: len(d.tracker.usable_set()) == 3,
                  msg="challenge-gated heal to full width")
        snap = metrics.snapshot()["counters"]
        assert snap.get("worker_respawns", 0) >= 1
        assert snap.get("membership_rejoins", 0) >= 1
        assert snap.get("integrity_challenges", 0) >= 1
        assert not d.tracker.is_suspect(corrupt_idx)
        assert (("127.0.0.1", sup.slots[1].port)
                not in d.membership.quarantined)

        # the healed, full-width fleet still proves byte-identically
        proof2 = prove(random.Random(1), ckt, pk,
                       RemoteBackend(d, dist_fft_min=ckt.n))
        assert proof2.opening_proof == proof_host.opening_proof
    finally:
        sup.stop()
        try:
            d.shutdown()
        finally:
            d.pool.shutdown(wait=False)


# --- verify-before-serve ------------------------------------------------------

@pytest.mark.parametrize("blocked", [0, 1], ids=["sound", "corrupt"])
def test_self_verify_blocks_corrupt_proof(tmp_path, blocked):
    """Verify-before-serve on the served path (host oracle backend). A
    sound job costs ONE pairing check, which shows as a phase of its
    STATUS. A proof corrupted between prove and serve (at=proof chaos) is
    BLOCKED — never journaled DONE, never handed to the client — and the
    re-prove serves a verifying proof."""
    import json
    from distributed_plonk_tpu.service import ProofService, ServiceClient
    from distributed_plonk_tpu.service.jobs import (JobSpec,
                                                    build_bucket_keys)
    from distributed_plonk_tpu.proof_io import deserialize_proof
    from distributed_plonk_tpu.verifier import verify

    faults = FaultInjector(
        [Rule.parse("corrupt:at=proof:nth=1")] if blocked else [])
    svc = ProofService(port=0, prover_workers=1, chaos=True,
                       faults=faults, self_verify="1",
                       journal_dir=str(tmp_path / "j"),
                       store_dir=str(tmp_path / "s")).start()
    try:
        with ServiceClient("127.0.0.1", svc.port) as c:
            jid = c.submit({"kind": "toy", "gates": 16, "seed": 5})["job_id"]
            st = c.wait(jid, timeout_s=_LOAD_BUDGET_S)
            assert st["state"] == "done", json.dumps(st)
            assert st["retries"] == blocked  # a blocked attempt re-proves
            assert st["phases"]["self_verify"] > 0
            header, blob = c.result(jid)
            m = c.metrics()
        ctr = m["counters"]
        assert ctr.get("proofs_blocked", 0) == blocked
        assert ctr.get("self_verify_failures", 0) == blocked
        assert ctr.get("self_verify_checks", 0) == 1 + blocked
        assert m["histograms"]["self_verify_s"]["count"] == 1 + blocked
        # what WAS served verifies
        spec = JobSpec.from_wire(header["spec"])
        vk = build_bucket_keys(spec)[2]
        pub = [int(x, 16) for x in header["public_input"]]
        assert verify(vk, pub, deserialize_proof(blob),
                      rng=random.Random(1))
        # the journal's DONE record is the GOOD proof: a restart serves
        # verifying bytes without re-proving
        svc.shutdown()
        svc2 = ProofService(port=0, prover_workers=1,
                            journal_dir=str(tmp_path / "j"),
                            store_dir=str(tmp_path / "s")).start()
        try:
            job = svc2.get_job(jid)
            assert job is not None and job.state == "done"
            assert job.proof_bytes == blob
        finally:
            svc2.shutdown()
    finally:
        svc.shutdown()


@pytest.mark.tier2
def test_self_verify_off_and_auto_parity(tmp_path):
    """DPT_SELF_VERIFY=0 (and the default auto mode on pool-placed
    local proves) adds ZERO checks and zero counters; proof bytes are
    the exact bytes an always-verify service serves."""
    from distributed_plonk_tpu.service import ProofService

    spec = {"kind": "toy", "gates": 16, "seed": 9}

    def run(self_verify):
        svc = ProofService(port=0, prover_workers=1,
                           self_verify=self_verify).start()
        try:
            job = svc.submit_local(dict(spec))
            assert job.done_event.wait(timeout=_LOAD_BUDGET_S)
            assert job.state == "done"
            return job.proof_bytes, svc.metrics.snapshot()
        finally:
            svc.shutdown()

    bytes_off, m_off = run("0")
    bytes_auto, m_auto = run("auto")
    bytes_on, m_on = run("1")
    assert bytes_off == bytes_on == bytes_auto
    for m in (m_off, m_auto):
        assert not any(k.startswith(("self_verify", "proofs_blocked"))
                       for k in m["counters"]), m["counters"]
        assert "self_verify_s" not in m["histograms"]
    assert m_on["counters"].get("self_verify_checks", 0) == 1
