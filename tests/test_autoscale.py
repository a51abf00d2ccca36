"""Closed-loop autoscaler suite (ISSUE 16).

Three layers, cheapest first:

  1. SLO-class queue units (jax-free, in-process): class-priority pop
     order, all-standard parity with the pre-class sort, per-class
     default TTLs (DPT_TTL_<CLASS>_S) vs the per-job ttl_s override,
     steal_lowest victim selection, and the full-queue flagship-preempts-
     batch admission path.
  2. Control-law units against FAKE sensors/actuators with an injected
     clock — hysteresis streaks, cooldown windows, min/max bounds, the
     lease-resize rule, pressure sheds, dry-run's ZERO-actuator-calls
     pin, and DPT_AUTOSCALE=0 attaching nothing (bit-parity).
  3. The live supervised-fleet canary: a real 1-worker fleet behind a
     fleet-backed ProofService with the actuating controller attached —
     a job ramp must scale UP (supervisor.add_slot, warm membership
     join), every proof must verify byte-identical to a local
     uninterrupted prove, and the idle tail must scale DOWN through
     retire_slot (drain -> LEAVE -> SIGTERM: zero respawns, zero flaps,
     zero mid-prove kills).
"""

import random
import time

import pytest

from distributed_plonk_tpu.runtime.dispatcher import (Dispatcher,
                                                      RemoteBackend,
                                                      WorkerHandle)
from distributed_plonk_tpu.runtime.health import LivenessTracker
from distributed_plonk_tpu.runtime.netconfig import NetworkConfig
from distributed_plonk_tpu.runtime.supervisor import WorkerSupervisor
from distributed_plonk_tpu.service import ProofService, ServiceClient
from distributed_plonk_tpu.service import autoscale as AS
from distributed_plonk_tpu.service.jobs import (Job, JobSpec, SLO_RANK,
                                                build_bucket_keys,
                                                build_circuit,
                                                class_default_ttl,
                                                shape_key)
from distributed_plonk_tpu.service.metrics import Metrics
from distributed_plonk_tpu.service.queue import JobQueue, Rejected

import os

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


def _job(slo=None, priority=0, seed=1, ttl_s=None):
    wire = {"kind": "toy", "gates": 16, "seed": seed, "priority": priority}
    if slo is not None:
        wire["slo"] = slo
    if ttl_s is not None:
        wire["ttl_s"] = ttl_s
    return Job(JobSpec.from_wire(wire))


# --- SLO-class queue ----------------------------------------------------------

def test_class_priority_pop_order():
    q = JobQueue(max_depth=8)
    batch = _job(slo="batch", priority=9, seed=1)
    standard = _job(slo="standard", priority=0, seed=2)
    flagship = _job(slo="flagship", priority=0, seed=3)
    for j in (batch, standard, flagship):
        q.submit(j)
    # class outranks priority: flagship(prio 0) before batch(prio 9)
    order = [q.pop_batch(max_batch=1)[0] for _ in range(3)]
    assert [j.slo for j in order] == ["flagship", "standard", "batch"]
    assert order == [flagship, standard, batch]


def test_all_standard_stream_keeps_classless_order():
    """A stream with no slo fields sorts exactly as the pre-class queue:
    priority desc, then FIFO — the parity contract."""
    q = JobQueue(max_depth=8)
    js = [_job(priority=p, seed=i) for i, p in enumerate((0, 2, 1, 2))]
    for j in js:
        q.submit(j)
    got = [q.pop_batch(max_batch=1)[0] for _ in range(4)]
    assert got == [js[1], js[3], js[2], js[0]]
    assert all(j.slo == "standard" for j in got)


def test_depth_by_class():
    q = JobQueue(max_depth=8)
    for slo in ("batch", "batch", "flagship", None):
        q.submit(_job(slo=slo))
    assert q.depth_by_class() == {"batch": 2, "flagship": 1, "standard": 1}


def test_steal_lowest_evicts_worst_lower_class():
    q = JobQueue(max_depth=8)
    b1 = _job(slo="batch", seed=1)
    b2 = _job(slo="batch", seed=2)       # same rank/prio, later seq: worst
    s1 = _job(slo="standard", seed=3)
    for j in (b1, b2, s1):
        q.submit(j)
    assert q.steal_lowest(SLO_RANK["flagship"]) is b2
    assert q.steal_lowest(SLO_RANK["standard"]) is b1
    # only the standard job left: nothing below standard remains
    assert q.steal_lowest(SLO_RANK["standard"]) is None
    assert q.steal_lowest(SLO_RANK["batch"]) is None
    assert q.depth() == 1


def test_per_class_default_ttl_env(monkeypatch):
    monkeypatch.setenv("DPT_TTL_BATCH_S", "7.5")
    monkeypatch.delenv("DPT_TTL_STANDARD_S", raising=False)
    assert class_default_ttl("batch") == 7.5
    assert class_default_ttl("standard") is None
    t0 = time.time()
    j = _job(slo="batch")
    assert j.deadline_ts is not None and j.deadline_ts >= t0 + 7.0
    # classless/standard: no default deadline (parity with pre-class)
    assert _job().deadline_ts is None
    # the per-job ttl_s override beats the class default
    j2 = _job(slo="batch", ttl_s=1.0)
    assert j2.deadline_ts is not None and j2.deadline_ts < t0 + 5.0
    # unparseable / non-positive envs fail safe to no deadline
    monkeypatch.setenv("DPT_TTL_BATCH_S", "nope")
    assert class_default_ttl("batch") is None
    monkeypatch.setenv("DPT_TTL_BATCH_S", "0")
    assert class_default_ttl("batch") is None


def test_flagship_preempts_batch_on_full_queue():
    """Admission shed-lowest-class-first: a full queue refusing a
    flagship SUBMIT evicts the worst queued batch job (journaled SHED)
    and admits the flagship in its place; an all-standard stream keeps
    the historical plain rejection."""
    svc = ProofService(port=0, prover_workers=1, queue_depth=2)
    # never started: submissions just land in the queue
    b1, _ = svc.submit_ex({"kind": "toy", "gates": 16, "seed": 1,
                           "slo": "batch"})
    b2, _ = svc.submit_ex({"kind": "toy", "gates": 16, "seed": 2,
                           "slo": "batch"})
    f, _ = svc.submit_ex({"kind": "toy", "gates": 16, "seed": 3,
                          "slo": "flagship"})
    assert b2.state == "shed" and b1.state == "queued"
    assert f.state == "queued"
    ctr = svc.metrics.snapshot()["counters"]
    assert ctr.get("slo_preempt_sheds", 0) == 1
    assert ctr.get("slo_sheds_batch", 0) == 1
    # standard outranks batch too: the remaining batch job gets evicted
    s, _ = svc.submit_ex({"kind": "toy", "gates": 16, "seed": 4})
    assert b1.state == "shed" and s.state == "queued"
    # but with no lower class left, standard-vs-standard keeps the
    # historical plain rejection (an all-standard stream never preempts)
    with pytest.raises(Rejected):
        svc.submit_ex({"kind": "toy", "gates": 16, "seed": 5})
    assert f.state == "queued" and s.state == "queued"


# --- control-law units (fake sensors/actuators, injected clock) ---------------

class _FakeActuators:
    def __init__(self, workers=1):
        self.workers = workers
        self.calls = []

    def worker_count(self):
        return self.workers

    def add_worker(self):
        self.calls.append("add")
        self.workers += 1
        return self.workers - 1

    def retire_worker(self):
        self.calls.append("retire")
        self.workers -= 1
        return self.workers

    def lease_capacity(self, frac):
        self.calls.append(("lease", frac))
        return max(1, int(8 * frac))

    def shed_lowest(self, below_rank):
        self.calls.append(("shed", below_rank))
        return "batch"


def _controller(mode="1", workers=1, **kw):
    box = {"t": 0.0,
           "sensors": {"queue_depth": 0, "queue_by_class": {},
                       "max_depth": 64, "busy_workers": 0}}
    act = _FakeActuators(workers=workers)
    defaults = dict(mode=mode, tick_s=0.01, min_workers=1, max_workers=3,
                    up_queue_per_worker=2, up_ticks=2, down_ticks=3,
                    up_cooldown_s=10, down_cooldown_s=10,
                    shed_watermark=0.9)
    defaults.update(kw)
    asc = AS.Autoscaler(sensors=lambda: dict(box["sensors"]),
                        actuators=act, metrics=Metrics(),
                        clock=lambda: box["t"], **defaults)
    return asc, act, box


def _tick(asc, box, dt=1.0):
    box["t"] += dt
    return asc.tick()


def test_scale_up_needs_hysteresis_streak():
    asc, act, box = _controller()
    box["sensors"].update(queue_depth=8, busy_workers=1)
    assert _tick(asc, box) == []          # streak 1 of 2: no decision
    ds = _tick(asc, box)                  # streak 2: scale up
    assert [d["action"] for d in ds] == ["scale_up"] and ds[0]["applied"]
    assert act.calls == ["add"] and act.workers == 2


def test_scale_up_cooldown_and_ceiling():
    asc, act, box = _controller(up_cooldown_s=10, max_workers=2)
    box["sensors"].update(queue_depth=8, busy_workers=1)
    _tick(asc, box)
    assert [d["action"] for d in _tick(asc, box)] == ["scale_up"]
    # breach persists: cooldown (10s) blocks the next up...
    assert _tick(asc, box, dt=1.0) == []
    assert _tick(asc, box, dt=1.0) == []
    # ...and once it elapses, the ceiling (max_workers=2) does
    assert _tick(asc, box, dt=20.0) == []
    assert act.calls == ["add"] and act.workers == 2


def test_scale_down_idle_streak_and_floor():
    asc, act, box = _controller(workers=2, down_ticks=3, down_cooldown_s=0)
    for _ in range(2):
        assert _tick(asc, box) == []      # idle streaks 1, 2
    ds = _tick(asc, box)                  # streak 3: retire
    assert [d["action"] for d in ds] == ["scale_down"] and ds[0]["applied"]
    assert act.calls == ["retire"] and act.workers == 1
    # at the floor (min_workers=1) the idle streak never retires again
    for _ in range(5):
        assert _tick(asc, box) == []
    assert act.workers == 1


def test_lease_resize_tracks_batch_dominance():
    asc, act, box = _controller()
    box["sensors"].update(queue_depth=4, busy_workers=1,
                          queue_by_class={"batch": 4})
    ds = _tick(asc, box)
    assert ("lease", 0.5) in act.calls
    assert any(d["action"] == "lease_resize" for d in ds)
    # a queued flagship restores full capacity on the next tick
    box["sensors"].update(queue_by_class={"batch": 3, "flagship": 1})
    _tick(asc, box)
    assert ("lease", 1.0) in act.calls


def test_pressure_shed_at_watermark():
    asc, act, box = _controller(shed_watermark=0.9)
    box["sensors"].update(queue_depth=60, busy_workers=1, max_depth=64)
    ds = _tick(asc, box)
    assert any(d["action"] == "shed" and d["applied"] for d in ds)
    assert ("shed", SLO_RANK["flagship"]) in act.calls


def test_dry_mode_records_decisions_with_zero_actuator_calls():
    asc, act, box = _controller(mode="dry")
    box["sensors"].update(queue_depth=60, busy_workers=1, max_depth=64)
    all_ds = []
    for _ in range(4):
        all_ds += _tick(asc, box)
    acts = {d["action"] for d in all_ds}
    assert "scale_up" in acts and "shed" in acts
    assert all(d["applied"] is False for d in all_ds)
    assert act.calls == []                # THE dry contract: zero calls
    st = asc.state()
    assert st["mode"] == "dry" and st["last_decisions"]


def test_off_mode_attaches_nothing(monkeypatch):
    class _Svc:
        autoscaler = None
    svc = _Svc()
    monkeypatch.delenv("DPT_AUTOSCALE", raising=False)
    assert AS.attach(svc) is None                 # env default: off
    assert AS.attach(svc, mode="0") is None       # explicit off
    assert svc.autoscaler is None
    # unknown values fail SAFE (off), never actuating
    monkeypatch.setenv("DPT_AUTOSCALE", "bananas")
    assert AS.mode_from_env() == "0"
    monkeypatch.setenv("DPT_AUTOSCALE", "dry")
    assert AS.mode_from_env() == "dry"
    monkeypatch.setenv("DPT_AUTOSCALE", "1")
    assert AS.mode_from_env() == "1"


def test_state_shape_for_obs_endpoint():
    asc, _act, box = _controller()
    box["sensors"].update(queue_depth=2, busy_workers=1,
                          queue_by_class={"standard": 2})
    _tick(asc, box)
    st = asc.state()
    assert st["bounds"] == {"min_workers": 1, "max_workers": 3}
    assert st["queue"]["depth"] == 2
    assert st["queue"]["by_class"] == {"standard": 2}
    assert st["workers"] == 1
    assert {"up", "down"} <= set(st["streaks"])
    assert {"up_remaining_s", "down_remaining_s"} <= set(st["cooldowns"])


# --- live fleet: retire + the closed-loop canary ------------------------------

def _member_dispatcher(metrics):
    d = Dispatcher(NetworkConfig([]), metrics=metrics)
    d.tracker = LivenessTracker(0, breaker_k=2, probe_base_s=0.05,
                                probe_max_s=0.5, metrics=metrics)
    return d, d.enable_membership()


def _supervised(n, metrics):
    d, mserver = _member_dispatcher(metrics)
    sup = WorkerSupervisor("127.0.0.1", mserver.port, n=n,
                           backend="python", metrics=metrics,
                           cwd=REPO).start()
    sup.attach_registry(d.membership)
    _wait_for(lambda: len(d.workers) >= n
              and len(d.tracker.usable_set()) >= n,
              msg=f"fleet width {n}")
    return d, sup


def _shutdown(d, sup):
    sup.stop()
    try:
        d.shutdown()
    finally:
        d.pool.shutdown(wait=False)


def _reference(spec_wire, _pk_cache={}):
    """Local uninterrupted prove: the byte-identity oracle."""
    from distributed_plonk_tpu.backend.python_backend import PythonBackend
    from distributed_plonk_tpu.proof_io import serialize_proof
    from distributed_plonk_tpu.prover import prove
    s = JobSpec.from_wire(spec_wire)
    key = shape_key(s)
    if key not in _pk_cache:
        _pk_cache[key] = build_bucket_keys(s)[1]
    return serialize_proof(prove(random.Random(s.seed), build_circuit(s),
                                 _pk_cache[key], PythonBackend()))


def test_retire_slot_graceful_drain_then_leave():
    """retire_slot is not a flap: the process exits via drain+LEAVE+
    SIGTERM, the watch loop never respawns it, the membership width
    shrinks, and worker_retires (not worker_respawns) counts it."""
    fm = Metrics()
    d, sup = _supervised(2, fm)
    try:
        assert sup.retire_slot(1) is True
        assert sup.retire_slot(1) is False       # idempotent
        assert sup.active_count() == 1
        snap = sup.snapshot()[1]
        assert snap["retired"] and not snap["failed"]
        _wait_for(lambda: not sup.snapshot()[1]["alive"],
                  msg="retired worker exit")
        _wait_for(lambda: len(d.tracker.usable_set()) == 1,
                  msg="membership width 1")
        # no respawn ever follows a retire (watch a couple of periods)
        time.sleep(1.0)
        ctr = fm.snapshot()["counters"]
        assert ctr.get("worker_retires", 0) == 1
        assert ctr.get("worker_respawns", 0) == 0
        assert ctr.get("worker_flap_capped", 0) == 0
    finally:
        _shutdown(d, sup)


@pytest.mark.tier2
def test_closed_loop_canary_scales_up_and_retires():
    """The live acceptance canary: ramp -> add_slot (warm join) -> every
    proof byte-verified -> idle -> drain-then-LEAVE retire back to the
    floor. Zero respawns and zero flaps: the scale actions are never
    mid-prove kills."""
    fm = Metrics()
    d, sup = _supervised(1, fm)
    svc = None
    try:
        svc = ProofService(
            port=0, prover_workers=1, chaos=True, max_retries=4,
            allow_remote_shutdown=True, self_verify="1",
            backend_factory=lambda: RemoteBackend(d, dist_fft_min=64),
        ).start()
        asc = svc.attach_autoscaler(
            supervisor=sup, mode="1", tick_s=0.1, min_workers=1,
            max_workers=2, up_queue_per_worker=2, up_ticks=2,
            down_ticks=3, up_cooldown_s=0.2, down_cooldown_s=0.2)
        assert asc is svc.autoscaler and asc.actuating
        with ServiceClient("127.0.0.1", svc.port) as c:
            specs = [{"kind": "toy", "gates": 60, "seed": 9000 + i,
                      "slo": ("flagship" if i == 0 else "standard")}
                     for i in range(6)]
            ids = [c.submit(s)["job_id"] for s in specs]
            # the ramp breaches queue/worker >= 2 for >= 2 ticks: the
            # controller must add a slot (the warm JOIN path)
            _wait_for(lambda: sup.active_count() == 2, msg="scale up")
            for spec, jid in zip(specs, ids):
                st = c.wait(jid, timeout_s=_LOAD_BUDGET_S)
                assert st["state"] == "done", st
                assert st["slo"] == spec.get("slo", "standard")
                _hdr, blob = c.result(jid)
                assert blob == _reference(spec)
            # idle tail: retire back to the floor (drain-then-LEAVE)
            _wait_for(lambda: sup.active_count() == 1, msg="scale down")
        # the retire completes asynchronously on its own thread (drain
        # -> LEAVE -> SIGTERM): wait for the counter, not just the flag
        _wait_for(lambda: fm.snapshot()["counters"]
                  .get("worker_retires", 0) >= 1, msg="retire complete")
        sc = svc.metrics.snapshot()["counters"]
        assert sc.get("autoscale_scale_ups", 0) >= 1
        assert sc.get("autoscale_scale_downs", 0) >= 1
        assert sc.get("slo_sheds_flagship", 0) == 0
        # the standard-class roundtrip histogram fed the p95 sensor
        hist = svc.metrics.snapshot()["histograms"]
        assert hist.get("slo_roundtrip/standard", {}).get("count", 0) >= 5
        ctr = fm.snapshot()["counters"]
        assert ctr.get("worker_retires", 0) >= 1
        assert ctr.get("worker_respawns", 0) == 0
        assert ctr.get("worker_flap_capped", 0) == 0
    finally:
        if svc is not None:
            svc.shutdown()
        _shutdown(d, sup)
