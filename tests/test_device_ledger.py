"""One clock for the request and the chip (ISSUE 26), the program's side.

The device ledger under a fake clock (completion-stamp charges, the
fed/unfed account by phase, a reading true to the instant it is taken), the
Tracer's phases and explicit waits, the watcher off the caller's thread, a
toy job through the pool on each of the three prover drivers on the jax
backend (every phase in STATUS `phases`, five rounds in `device`), the
beacon that puts the workers' phases into a profiler session of any length,
and program names that tell MSM from NTT. Bytes and counts; no time is
asserted anywhere.
"""

import random
import threading
import time

import pytest

from distributed_plonk_tpu import prover, trace as T
from distributed_plonk_tpu.service import metrics as MT


class Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def at(self, t):
        self.t = 100.0 + t
        return self


def _ledger():
    clock = Clock()
    return clock, T.DeviceLedger(clock=clock, beacon=False)


# --- the ledger under a fake clock -------------------------------------------

def test_two_owners_interleaved_are_charged_the_span_of_the_completions():
    clock, led = _ledger()
    a = led.open(0)                 # t = 0: A's round is first dispatched
    clock.at(1.0)
    b = led.open(1)                 # B dispatches behind it
    clock.at(3.0)
    led.close(a)                    # A completes: charged from its dispatch
    clock.at(4.5)
    led.close(b)                    # B: from A's completion, not from 1.0
    assert (a.charge, b.charge) == (3.0, 1.5)
    assert (a.start, b.start) == (100.0, 103.0)
    # charges sum to the span of the completions: nothing counted twice,
    # whoever else had work queued
    assert a.charge + b.charge == 4.5
    clock.at(6.0)
    c = led.open(0)                 # after a gap the dispatch bounds it
    clock.at(6.5)
    led.close(c)
    assert c.charge == 0.5 and c.start == 106.0
    led.close(c)                    # safe in a finally: stamped once
    assert c.charge == 0.5
    assert led.counters()["device_unfed_s"] == 1.5


def test_unfed_seconds_go_to_the_phases_of_the_worker_that_ends_the_gap():
    clock, led = _ledger()
    led.idle(0)
    led.idle(1)                             # both wait for a job from t = 0
    clock.at(2.0)
    led.busy(0)                             # worker 0 takes a unit
    tok = led.enter(0, "circuit_build")
    clock.at(5.0)
    led.leave(0, tok)
    tok = led.enter(0, "round1")            # host prelude of the launch
    clock.at(5.5)
    rnd = led.open(0)                       # first dispatch: the gap ends
    got = led.counters()
    assert got["device_unfed_s"] == 5.5
    assert got["device_unfed_s/worker_idle"] == 2.0
    assert got["device_unfed_s/circuit_build"] == 3.0
    assert got["device_unfed_s/round1"] == 0.5
    # worker 1 idled through all of it and is charged nothing: each unfed
    # second is charged once
    by_phase = sum(v for k, v in got.items()
                   if k.startswith("device_unfed_s/"))
    assert by_phase == got["device_unfed_s"]
    # while the round is outstanding the device is fed: the clock runs on,
    # the unfed account does not
    clock.at(9.0)
    led.leave(0, tok)
    got = led.counters()
    assert got["device_unfed_s"] == 5.5 and got["phase_clock_s"] == 9.0
    led.close(rnd)
    # a gap ended by the OTHER worker goes to that worker's phases
    led.busy(1)
    tok = led.enter(1, "checkpoint_save")
    clock.at(10.0)
    led.open(1)
    got = led.counters()
    assert got["device_unfed_s/checkpoint_save"] == 1.0
    assert got["device_unfed_s"] == 6.5


def test_a_reading_charges_the_open_interval_first():
    clock, led = _ledger()
    m = MT.Metrics()
    m.add_source(led)
    m.add_source(led)                       # idempotent per source
    first = m.snapshot()["counters"]
    # every counter exists at 0 from the start
    assert first["device_unfed_s"] == 0.0 and first["phase_clock_s"] == 0.0
    for phase in ("worker_idle", "other", "circuit_build", "guard_open",
                  "checkpoint_save", "serialize", "self_verify",
                  "journal_done", "trace_store", "round1", "round5_finalize"):
        assert first["device_unfed_s/" + phase] == 0.0
    assert "device_unfed_s/pipeline_wait" not in first  # never a worker's
    led.idle(0)
    clock.at(4.0)                           # the gap is still open
    snap = m.snapshot()["counters"]
    assert snap["phase_clock_s"] == 4.0
    assert snap["device_unfed_s"] == 4.0
    assert snap["device_unfed_s/worker_idle"] == 4.0
    clock.at(6.0)
    led.busy(0)
    clock.at(7.0)
    led.open(0)                             # only the rest is charged now
    snap = m.snapshot()["counters"]
    assert snap["device_unfed_s"] == 7.0
    assert snap["device_unfed_s/worker_idle"] == 6.0
    assert snap["device_unfed_s/other"] == 1.0
    assert "dpt_device_unfed_s_worker_idle_total 6.0" in m.to_prometheus()


def test_a_worker_in_two_spans_is_in_the_one_entered_last():
    clock, led = _ledger()
    led.busy(0)
    fin = led.enter(0, "round2_finalize")   # the pipeline's driver thread
    launch = led.enter(0, "round1")         # and its launch executor
    assert led.phase_names() == ["round1"]
    led.leave(0, launch)
    assert led.phase_names() == ["round2_finalize"]
    led.leave(0, fin)
    assert led.phase_names() == ["other"]
    led.idle(0)
    led.idle(1)
    assert led.phase_names() == ["worker_idle", "worker_idle"]


def test_the_watcher_stamps_completion_off_the_callers_thread():
    led = T.DeviceLedger(beacon=False)
    ready = threading.Event()
    seen = []

    class Array:
        def block_until_ready(self):
            seen.append(threading.current_thread().name)
            ready.wait(30)

    rnd = led.open()
    led.watch(rnd, [Array()])               # returns at once
    assert not rnd.done.is_set() and rnd.charge is None
    led.close(rnd)                          # the watcher has it: left alone
    assert not rnd.done.is_set()
    ready.set()
    assert rnd.done.wait(30)
    assert seen == ["dpt-device-watcher"]
    assert rnd.charge >= 0 and rnd.t_ready >= rnd.t_first
    empty = led.open()
    led.watch(empty, [])                    # nothing left to wait for
    assert empty.done.wait(30)
    led.close_threads()


# --- the Tracer's side --------------------------------------------------------

def test_tracer_phases_waits_and_families():
    clock, led = _ledger()
    tr = T.Tracer(ledger=led, worker=3)
    tr.waits = "pipeline_wait"
    tr.park("pipeline_wait")
    tr.park("pipeline_wait")                # already waiting: one wait
    with tr.span("circuit_build"):
        assert led.phase_names() == ["circuit_build"]
        with tr.span("inner"):              # only top-level spans are phases
            assert led.phase_names() == ["circuit_build"]
    assert led.phase_names() == ["other"]
    with tr.span("round1_finalize"):
        with tr.span("checkpoint_save"):    # counted wherever it nests
            pass
    with tr.span("checkpoint_save"):
        pass
    tr.add_event("device/round1", ts=0.0, dur_s=0.25)
    tr.add_event("device/round1", ts=0.0, dur_s=0.25)
    tr.add_event("service/self_verify", ts=0.0, dur_s=9.0)   # not a phase
    tr.unpark()
    spans = [ev["span"] for ev in tr.events]
    # one wait before the first span and one after each top-level span
    assert spans.count("pipeline_wait") == 4
    assert set(tr.phases()) == {"circuit_build", "checkpoint_save",
                                "pipeline_wait"}
    assert tr.family("device") == {"round1": 0.5}
    assert set(tr.totals(depth=1)) == {"circuit_build", "round1_finalize",
                                       "checkpoint_save", "pipeline_wait"}
    # the null tracer takes the same calls
    T.NULL_TRACER.waits = "pipeline_wait"
    T.NULL_TRACER.park("pipeline_wait")
    T.NULL_TRACER.unpark()
    assert T.NULL_TRACER.waits is None and T.NULL_TRACER.phases() == {}


# --- a toy job through the pool, on each of the three drivers ----------------

ROUNDS = {"round%d" % i for i in range(1, 6)}
OWN_PHASES = {"circuit_build", "guard_open", "checkpoint_save", "serialize",
              "self_verify", "journal_done", "trace_store", "unaccounted"}


@pytest.fixture(scope="module")
def jax_service():
    """A service on ONE shared JaxBackend (XLA:CPU), as start_service
    builds it; the tests hand units to its pool directly, so which driver
    proves them is theirs to say."""
    from distributed_plonk_tpu.backend.jax_backend import JaxBackend
    from distributed_plonk_tpu.service import ProofService
    be = JaxBackend()
    svc = ProofService(port=0, prover_workers=1, self_verify="1",
                       backend=be)
    yield svc, be
    svc.pool.shutdown()
    be.device_ledger.close_threads()


def _jobs(svc, seeds):
    from distributed_plonk_tpu.service.jobs import Job, JobSpec
    jobs = [Job(JobSpec.from_wire({"kind": "toy", "gates": 8, "seed": s}))
            for s in seeds]
    return jobs, svc.buckets.get(jobs[0].spec)


@pytest.mark.parametrize("driver", ["prove", "prove_pipelined", "prove_many"])
def test_every_driver_reports_phases_and_device_rounds(jax_service, driver,
                                                       monkeypatch):
    svc, be = jax_service
    if driver == "prove":
        jobs, res = _jobs(svc, [5])
        svc.pool.dispatch(jobs[0], res)
    else:
        # one placement unit of two jobs: the round pipeline proves it, or
        # with the pipeline off the lockstep batch prover
        monkeypatch.setattr(prover, "PIPELINE", driver == "prove_pipelined")
        jobs, res = _jobs(svc, [6, 7])
        svc.pool.dispatch_group(jobs, res)
    for job in jobs:
        assert job.done_event.wait(900), job.status()
        st = job.status()
        assert st["state"] == "done", st
        want = OWN_PHASES | ({"pipeline_wait"} if len(jobs) > 1 else set())
        assert set(st["phases"]) == want
        assert set(st["device"]) == ROUNDS
        assert all(v >= 0 for v in st["device"].values())
        finalizes = {r + "_finalize" for r in ROUNDS}
        assert set(st["rounds"]) == (ROUNDS | finalizes
                                     if driver == "prove_pipelined"
                                     else ROUNDS)
        events = {ev["span"]: ev for ev in job.trace_dump["events"]}
        # every phase is a span of the job's one trace, the stored
        # timeline included
        assert set(st["phases"]) - {"unaccounted"} <= set(events)
        assert all(ev.get("sid") for ev in events.values())
        # device-true time carries the round's work model; a span that
        # only times the enqueue carries none
        assert events["device/round1"]["flops"] > 0
        assert events["device/round3"]["data_bytes"] > 0
        if driver != "prove_many":
            assert "flops" not in events["round1/ifft_wires"]
            k = events["kernels/commit_wires"]
            assert k["flops"] > 0 and k["wait_s"] >= 0
    snap = svc.metrics.snapshot()
    assert "prove_phase/circuit_build" in snap["histograms"]
    assert "prove_round/checkpoint_save" not in snap["histograms"]
    assert "kernel_round1_gflops" in snap["gauges"]
    assert not any(k.startswith("mfu_") for k in snap["gauges"])  # no peak
    c = snap["counters"]
    assert c["phase_clock_s"] > 0
    assert c["device_unfed_s"] == pytest.approx(
        sum(v for k, v in c.items() if k.startswith("device_unfed_s/")))
    assert not any(k.startswith("pipeline_device_idle_s")
                   for k in snap["gauges"])
    # nothing of ours is left outstanding on the device
    assert be.device_ledger._open == 0


def test_a_launch_that_raises_leaves_no_round_open(proven):
    """The ledger must never believe the device fed by a round that will
    not complete."""
    from distributed_plonk_tpu.backend.jax_backend import JaxBackend
    ckt, pk, _vk, _proof = proven
    be = JaxBackend()

    def boom(*_a, **_k):
        raise RuntimeError("launch failed")
    be.commit_many_async = boom
    with pytest.raises(RuntimeError, match="launch failed"):
        prover.prove(random.Random(1), ckt, pk, be, tracer=T.Tracer())
    assert be.device_ledger._open == 0
    be.device_ledger.close_threads()


# --- a sync backend (the mesh): rounds closed at the fetch --------------------

def _sync_with_ledger(clock, ledger, fail_at=None):
    """The host oracle with a device ledger and no async hook, which is
    what MeshBackend is to the prover; every commit and every evaluation
    moves the fake clock one second on, and call `fail_at` raises."""
    from distributed_plonk_tpu.backend.python_backend import PythonBackend

    class Sync(PythonBackend):
        device_ledger = ledger
        calls = 0

        def _tick(self):
            self.calls += 1
            if self.calls == fail_at:
                raise RuntimeError("commit failed")
            clock.t += 1.0

        def commit_many_h(self, ck, hs):
            self._tick()
            return super().commit_many_h(ck, hs)

        def eval_many_h(self, pairs):
            self._tick()
            return super().eval_many_h(pairs)

    return Sync()


def test_a_sync_prove_closes_each_round_at_its_fetch(proven):
    ckt, pk, _vk, want = proven
    clock, led = _ledger()
    tr = T.Tracer(ledger=led, worker=0)
    got = prover.prove(random.Random(1), ckt, pk,
                       _sync_with_ledger(clock, led), tracer=tr)
    assert got.opening_proof == want.opening_proof
    # five rounds, each charged the second its commit (or, in round 4, its
    # evaluation) took on the ledger's clock, with the round's work model
    assert tr.family("device") == {"round%d" % i: 1.0 for i in range(1, 6)}
    events = {ev["span"]: ev for ev in tr.events}
    assert events["device/round1"]["flops"] > 0
    assert events["device/round4"]["flops"] == 0
    # sync kernel spans time the compute and keep their attribution
    assert events["round1/commit_wires"]["flops"] > 0
    assert led._open == 0
    # fed for exactly those five seconds: nothing else moved the clock
    c = led.counters()
    assert c["phase_clock_s"] == 5.0 and c["device_unfed_s"] == 0.0


def test_a_sync_commit_that_raises_leaves_no_round_open(proven):
    ckt, pk, _vk, _want = proven
    clock, led = _ledger()
    with pytest.raises(RuntimeError, match="commit failed"):
        prover.prove(random.Random(1), ckt, pk,
                     _sync_with_ledger(clock, led, fail_at=2),
                     tracer=T.Tracer(ledger=led, worker=0))
    assert led._open == 0
    assert led.counters()["phase_clock_s"] == 1.0


# --- the beacon ----------------------------------------------------------------

def _names_in_session(seconds, around):
    """Event names of a profiler session opened in the middle of
    `around()`'s long span."""
    import jax
    from jax._src.lib import _profiler
    from jax.profiler import ProfileData
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    with around():
        time.sleep(0.05)
        session = _profiler.ProfilerSession(opts)
        time.sleep(seconds)
        xspace = session.stop()
        time.sleep(0.05)
    data = ProfileData.from_serialized_xspace(xspace)
    return {ev.name for plane in data.planes for line in plane.lines
            for ev in line.events}


def test_the_beacon_puts_phases_into_a_short_profiler_session(monkeypatch):
    """A TraceMe that began before the session is dropped, so a 0.2 s
    session inside a long span sees no span of the program: the case that
    failed before the beacon. With it, every moment of the session lies in
    a `service/phases/...` event on the profiler's own clock."""
    monkeypatch.setattr(T, "_JAX_TRACE", True)
    silent = T.DeviceLedger(beacon=False)
    tr = T.Tracer(ledger=silent, worker=0)
    names = _names_in_session(0.2, lambda: tr.span("circuit_build"))
    assert not any(n.startswith(("service/", "circuit_build"))
                   for n in names)
    led = T.DeviceLedger()                  # DPT_JAX_TRACE=1: beacon on
    assert "dpt-phase-beacon" in {t.name for t in threading.enumerate()}
    led.idle(1)
    tr = T.Tracer(ledger=led, worker=0)
    names = _names_in_session(0.2, lambda: tr.span("circuit_build"))
    assert "service/phases/circuit_build+worker_idle" in names
    led.close_threads()
    assert "dpt-phase-beacon" not in {t.name for t in threading.enumerate()}


def test_tracing_off_means_no_beacon_thread_and_no_annotation(monkeypatch):
    assert T._JAX_TRACE is False            # DPT_JAX_TRACE is unset here
    made = []
    import jax.profiler
    monkeypatch.setattr(jax.profiler, "TraceAnnotation",
                        lambda name: made.append(name))
    led = T.DeviceLedger()
    tr = T.Tracer(ledger=led, worker=0)
    with tr.span("circuit_build"):
        pass
    assert made == [] and led._beacon is None
    assert "dpt-phase-beacon" not in {t.name for t in threading.enumerate()}


# --- program names --------------------------------------------------------------

def _module_name(jitted, *specs):
    text = jitted.lower(*specs).as_text()
    return text.split("module @", 1)[1].split(" ", 1)[0]


def test_lowered_programs_are_named_so_msm_reads_apart_from_ntt():
    import jax
    import jax.numpy as jnp
    from distributed_plonk_tpu.backend import msm_jax as MJ, ntt_jax
    from distributed_plonk_tpu.backend import prover_jax as PJ
    from distributed_plonk_tpu.constants import FQ_LIMBS, FR_LIMBS

    u32 = jnp.uint32

    def spec(*shape, dtype=u32):
        return jax.ShapeDtypeStruct(shape, dtype)

    names = []
    ctx = MJ.MsmContext([(1, 2)] * 8)
    n, W = ctx.padded_n, -(-MJ.SCALAR_BITS // ctx.c_batch)
    buckets = 1 << ctx.c_batch
    names.append(_module_name(ctx._digits_batch_fn, spec(FR_LIMBS, n)))
    names.append(_module_name(ctx._digits_many_fn, spec(2, FR_LIMBS, n)))
    g = MJ._group_size_batch(n, 1, ctx.c_batch, signed=ctx.signed,
                             kernel=ctx._mode())
    names.append(_module_name(
        ctx._chunk_fn(n, g), spec(FQ_LIMBS, n), spec(FQ_LIMBS, n),
        spec(n, dtype=jnp.bool_), spec(1, W, n)))
    planes = tuple(spec(FQ_LIMBS, W, buckets) for _ in range(3))
    names.append(_module_name(ctx._finish_fn(1), *planes))
    names.append(_module_name(ctx._merge_fn, planes, planes))
    plan = ntt_jax.get_plan(16)
    fn, consts = plan.traced_kernel(inverse=True)
    names.append(_module_name(fn, spec(FR_LIMBS, 16), consts))
    fn, consts = plan.traced_kernel(coset=True, batch=True)
    names.append(_module_name(fn, spec(FR_LIMBS, 2, 16), consts))
    plan.kernel_fused(False, True, key=("r3gate", 0, 2),
                      epilogue=lambda v, acc: acc)
    fn, consts = next(v for k, v in plan._fns.items() if "fused" in k)
    names.append(_module_name(fn, (spec(FR_LIMBS, 2, 16),),
                              (spec(FR_LIMBS, 16),), consts))
    names.append(_module_name(PJ._to_mont_jit, spec(FR_LIMBS, 16)))
    names.append(_module_name(PJ._from_mont_jit, spec(FR_LIMBS, 16)))
    names.append(_module_name(PJ.roll_jit, spec(FR_LIMBS, 16), 3))
    assert names == [
        "jit_msm_digits", "jit_msm_digits_many", "jit_msm_bucket_scan",
        "jit_msm_finish", "jit_msm_merge", "jit_ntt_plain", "jit_ntt_batch",
        "jit_ntt_fused_r3gate_0_2", "jit_fr_to_mont", "jit_fr_from_mont",
        "jit_roll"]
    assert not any(n in ("jit__unknown", "jit_fn") or "lambda" in n
                   for n in names)
    # a program that had a name of its own keeps it
    assert _module_name(PJ.lin_comb_jit, spec(FR_LIMBS, 2, 16),
                        spec(FR_LIMBS, 2, 1)) == "jit_lin_comb"
