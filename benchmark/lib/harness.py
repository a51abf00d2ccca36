"""One run of one cell: set-up, the measured window, the check, the result.

The flow is `chip_smoke.py::serve_and_check`'s, kept here as the
benchmark's own copy: one process holds the chip, the proof service is the
one `scripts/serve.py` starts (`service.start_service("jax", ...)`) embedded
in it, and the load comes from `ServiceClient` threads over loopback TCP.
From the program the harness takes the service, its STATUS and METRICS
readings and its kernel names; the window, the reduction from traces to
numbers, the work model, the peaks and the comparison that decides
`correct` are the benchmark's own (`benchmark/lib`, `benchmark/plain`,
`benchmark/reference`).
"""

import importlib.util
import json
import os
import random
import sys
import threading
import time

from . import check, manifest as M, peaks, readers, tracered, window as W
from .refpool import RefPool

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# the program's own spans (trace.py, mirrored into the profiler's trace when
# DPT_JAX_TRACE is set): what the host was doing while the device sat idle
HOST_SPAN_RE = (r"^(round\d|ifft_|commit_|perm_product|quotient_|coset_|"
                r"lin_poly|batch_open|checkpoint_save|self_verify|service/)")


class Refused(SystemExit):
    """The run cannot be made here; exits non-zero with no result line."""

    def __init__(self, why):
        print(f"benchmark: {why}", file=sys.stderr, flush=True)
        super().__init__(2)


_T0 = time.monotonic()


def say(**rec):
    """Progress, on earlier lines of standard output than the result."""
    print(json.dumps(dict(rec, t=round(time.monotonic() - _T0, 1))),
          flush=True)


class Monitor:
    """jax.monitoring events with the time each fired."""

    def __init__(self, clock):
        self.clock = clock
        self.events = []

    def install(self):
        import jax
        jax.monitoring.register_event_listener(
            lambda name, **_kw: self.events.append((self.clock(), name)))
        jax.monitoring.register_event_duration_secs_listener(
            lambda name, _secs, **_kw: self.events.append((self.clock(), name)))

    def count(self, name, t0=None, t1=None):
        return sum(1 for t, n in self.events if n == name
                   and (t0 is None or t >= t0) and (t1 is None or t <= t1))


class TraceStretches:
    """Profiles a few short stretches of the window. Short, because the
    device runs some 1.7 million operations a second here: the profiler's
    buffer holds about four seconds of them and stopping it costs about
    15 us an event (PERF.md sec. 6). Several, at offsets drawn from the
    seed, one in each equal part of `seconds`, so that together they are a
    sample of the whole window and not of one phase of a proof that
    somebody chose; a stretch that cannot begin at its offset, because the
    one before is still being stopped, begins when it can, and if all of
    them found the device empty more are taken. A stretch is the sleep
    inside the host annotation `tracered.MARK`, opened once the profiler
    runs and closed before it is stopped: the trace holds more device time
    than the sleep, and the mark is what `tracered` cuts it to, so a
    stretch's busy time and its length are read on the trace's one clock.
    The host clock's reading of the sleep only places the stretch in the
    window (`began_s`) and goes into the log beside the mark's. The session is
    the profiler's own (`ProfilerSession.stop()` returns the trace;
    `jax.profiler.stop_trace` would also export it for TensorBoard, which
    more than doubles the stop). `run()` is called on the thread that
    drives the window while the client threads go on; `read()` after it."""

    def __init__(self, clock, seed, seconds, count=3, stretch_s=0.4):
        rng = random.Random(f"{seed}/trace")
        part = seconds / count
        self.clock, self.stretch_s = clock, stretch_s
        self.offsets = [(i + 0.5 * rng.random()) * part for i in range(count)]
        self.taken = []                  # (began, ended, serialized XSpace)

    def run(self, window, loop_done):
        for offset in self.offsets:
            while (window.t_open is None
                   or self.clock() - window.t_open < offset):
                if loop_done():
                    return
                time.sleep(0.02)
            self._take()
        # a third of this window is host-only phases (PERF.md sec. 5), and a
        # traced run in which nothing ran on the device is refused: where
        # every stretch fell into such a phase, take more while it lasts
        while not loop_done() and all(self.nothing_ran(x)
                                      for _t0, _t1, x in self.taken):
            time.sleep(1.0)
            self._take()

    def _take(self):
        import jax
        from jax._src.lib import _profiler
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        session = _profiler.ProfilerSession(opts)
        t0 = self.clock()
        with jax.profiler.TraceAnnotation(tracered.MARK):
            time.sleep(self.stretch_s)
        t1 = self.clock()
        self.taken.append((t0, t1, session.stop()))

    @staticmethod
    def nothing_ran(xspace):
        """Whether a stretch's trace holds no device event. An idle stretch
        comes back in kilobytes and is read here; one of a megabyte or more
        holds events by the hundred thousand and is not read inside the
        window."""
        return (len(xspace) < 1 << 20
                and tracered.busy(tracered.read_xspace(xspace).events) is None)

    def read(self):
        """The `tracered.Trace` of each stretch, cut to its mark."""
        return [tracered.read_xspace(xspace) for _t0, _t1, xspace in self.taken]


def look_for_chip(cell, require_tpu):
    """The device this run is on, as jax reports it. With `require_tpu`
    (always, outside the tests) anything but the cell's chips is refused."""
    import jax
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if require_tpu:
        if os.environ.get("DPT_PALLAS_INTERPRET"):
            raise Refused("DPT_PALLAS_INTERPRET is set; the benchmark runs "
                          "compiled kernels only")
        if device["platform"] != "tpu":
            raise Refused(f"no TPU: jax found {device}; there is no CPU mode")
        if device["count"] != cell.chips:
            raise Refused(f"cell {cell.name} needs {cell.chips} chip(s), jax "
                          f"found {device['count']}")
    try:
        pk = peaks.peaks_for(device["kind"])
    except KeyError as e:
        if require_tpu:
            raise Refused(str(e)) from None
        pk = None
    return devs, device, pk


class Session:
    """A service set up for one cell, then windows on it. `run_cell` makes
    one window; `benchmark/control.py` makes several on one set-up.
    `require_tpu=False` and `ref_workers=0` exist for the CPU tests, which
    drive this same flow at a toy size; run.py passes neither."""

    def __init__(self, root, workload, trace=False, require_tpu=True,
                 ref_workers=3, clock=time.monotonic, stretch_s=0.4):
        self.clock, self.trace, self.require_tpu = clock, trace, require_tpu
        self.stretch_s = stretch_s
        self.svc = self.ref = None
        if importlib.util.find_spec("distributed_plonk_tpu") is None:
            raise Refused("the program (distributed_plonk_tpu) is not in "
                          "this checkout")
        man = M.load(root)
        bad = M.problems(man)
        if bad:
            raise Refused("BENCHMARK.json: " + "; ".join(bad))
        try:
            self.cell = cell = M.Cell(man, root, workload)
        except (M.ManifestError, OSError) as e:
            raise Refused(str(e)) from None
        self.conf, self.traffic = cell.config, cell.traffic
        if self.traffic.get("loop") != "closed":
            raise Refused(f"traffic {cell.traffic_name}: loop "
                          f"{self.traffic.get('loop')!r} has no generator yet")
        if "jax" in sys.modules and require_tpu:
            raise Refused("jax was imported before the cell's environment "
                          "was set")
        self.clients = (int(self.traffic["clients"])
                        * int(self.traffic.get("outstanding", 1)))
        self.oracle_jobs = int(self.conf["check"]["oracle_jobs"])
        self.tau = int(self.conf["check"]["srs_tau"], 16)
        # placement and tracing knobs the program reads at import
        os.environ.update({k: str(v)
                           for k, v in self.conf.get("env", {}).items()})
        if trace:
            os.environ["DPT_JAX_TRACE"] = "1"
        self.devs, self.device, self.peaks = look_for_chip(cell, require_tpu)
        # a worker for each of the oracle's proves, which run side by side,
        # and each prove fanned out over the host's usable cores less two
        # for the service's set-up, split between the proves
        self.oracle_workers = max(
            1, (len(os.sched_getaffinity(0)) - 2) // self.oracle_jobs)
        self.ref = RefPool(
            max(ref_workers, self.oracle_jobs) if ref_workers else 0,
            os.path.join(root, M.bench_dir(man), ".state", "reference"),
            fanout=self.oracle_workers)

    def oracle(self, seed, precision="full"):
        """Start the host oracle's prove of the sampled jobs of a window of
        `seed` (the first job of each sampled client): {client: future}.
        They run in jax-free workers beside the set-up, each fanned out
        over `oracle_workers` more, none of it on the chip, and are read
        only once the window has closed."""
        return {c: self.ref.oracle_proof(
            W.draw_spec(self.cell.job_mix, seed, "window", c, 0), precision)
            for c in check.sample_clients(seed, self.clients,
                                          self.oracle_jobs)}

    def open(self, seed):
        """Set-up: the device, the service, the warm-up."""
        cell, conf, traffic = self.cell, self.conf, self.traffic
        os.makedirs(cell.state_dir, exist_ok=True)
        import jax
        self.monitor = Monitor(self.clock)
        self.monitor.install()
        from distributed_plonk_tpu.service import ServiceClient, start_service
        if "compile_cache_min_compile_secs" in conf.get("harness", {}):
            jax.config.update(
                "jax_persistent_cache_min_compile_time_secs",
                conf["harness"]["compile_cache_min_compile_secs"])
        say(phase="start", workload=cell.name, seed=seed, trace=self.trace,
            device=self.device,
            compile_cache_dir=jax.config.jax_compilation_cache_dir)
        service = dict(conf["service"])
        backend = service.pop("backend")
        self.svc, runtime = start_service(
            backend, port=0, store_dir=os.path.join(cell.state_dir, "store"),
            **service)
        say(phase="service", runtime=runtime, autotune=self.svc.autotune)
        port = self.svc.port
        self.make_client = lambda: ServiceClient("127.0.0.1", port)
        self.loop = dict(
            job_mix=cell.job_mix, clients=self.clients,
            wait_timeout_s=float(traffic.get("wait_timeout_s", 1000)),
            poll_s=float(traffic.get("poll_s", 0.02)))
        # warm-up: the cell's own pattern, other seeds, until every client
        # has done `warmup_rounds` jobs; a program first compiled on the
        # second prove of a shape is in the cache before the window
        warm = W.Window(self.clock,
                        jobs_per_client=int(traffic["warmup_rounds"]))
        W.run_closed_loop(warm, self.make_client, seed=seed, salt="warmup",
                          **self.loop)
        lost = [r for r in warm.requests if r.state != "done"]
        if lost:
            raise RuntimeError(f"warm-up: {len(lost)} job(s) did not finish: "
                               f"{lost[0].state} {lost[0].error}")
        mon = self.monitor
        say(phase="warm", jobs=len(warm.requests),
            seconds=[round(r.latency_s, 3) for r in warm.requests],
            compiles=mon.count(COMPILE_EVENT),
            cache_hits=mon.count("/jax/compilation_cache/cache_hits"),
            cache_misses=mon.count("/jax/compilation_cache/cache_misses"),
            counters=self.svc.metrics.snapshot()["counters"])

    def window(self, seed, seconds):
        """One measured window; with tracing, a few stretches of it
        profiled. Returns (window, stretches or None, METRICS at open, at
        close)."""
        stretches = None
        win = W.Window(self.clock, seconds=seconds)
        if self.trace:
            stretches = TraceStretches(self.clock, seed, seconds,
                                       stretch_s=self.stretch_s)
        metrics_open = self.svc.metrics.snapshot()
        failure = []

        def drive():
            try:
                W.run_closed_loop(win, self.make_client, seed=seed,
                                  salt="window", **self.loop)
            except BaseException as e:  # noqa: BLE001 - re-raised below
                failure.append(e)

        loop = threading.Thread(target=drive, name="bench-window")
        loop.start()
        try:
            if stretches is not None:
                stretches.run(win, lambda: not loop.is_alive())
        finally:
            loop.join()
        if failure:
            raise failure[0]
        return win, stretches, metrics_open, self.svc.metrics.snapshot()

    def memory(self):
        return [d.memory_stats() or {} for d in self.devs[:self.cell.chips]]

    def stop_service(self):
        if self.svc is not None:
            self.svc.shutdown()
            self.svc = None

    def judge(self, win, oracle_futs):
        """Hold the window's answers to the reference, once it has closed.
        Returns (correct, checks, good requests)."""
        counted = win.counted()
        answered = [r for r in counted
                    if r.state == "done" and r.proof is not None]
        futs = {id(r): self.ref.check_served(
            r.spec, r.proof, r.header.get("public_input"), self.tau)
            for r in answered}
        verdicts = {k: f.result(timeout=900) for k, f in futs.items()}
        first = {r.client: r for r in counted if r.k == 0}
        pairs = []
        for c, fut in oracle_futs.items():
            got = fut.result(timeout=1100)
            served = first.get(c)
            pairs.append((served.proof if served is not None
                          and served.proof is not None else None,
                          got["proof"]))
            st = (served.status or {}) if served is not None else {}
            # on the one monotonic clock: negative where the prove ran on
            # into the window, beside the service under test
            say(phase="oracle", client=c, oracle_prove_s=got["seconds"],
                oracle_workers=self.oracle_workers,
                ended_before_window_s=win.t_open - got["ended"],
                placement=st.get("placement"), batch_size=st.get("batch_size"),
                pipelined=any(k.endswith("_finalize")
                              for k in st.get("rounds") or ()))
        correct, checks = check.decide(
            counted, verdicts, pairs, int(self.conf["sizes"]["proof_bytes"]),
            min(self.oracle_jobs, self.clients))
        good = [r for r in answered if verdicts[id(r)]["verified"]
                and verdicts[id(r)]["pub_equal"]]
        for r in answered:
            if r not in good:
                say(phase="wrong_answer", job=r.job_id, seed=r.spec["seed"],
                    **verdicts[id(r)])
        return correct, checks, good

    def close(self, kill=False):
        """Stop the service and every reference worker; `kill` does not
        wait for a reference prove that nobody will read."""
        self.stop_service()
        if self.ref is not None:
            self.ref.close(kill=kill)
            self.ref = None


def run_cell(root, workload, seed, seconds, trace, t_start, **session_kw):
    """Run one cell once; returns the result object of the last line."""
    ses = Session(root, workload, trace=bool(trace), **session_kw)
    try:
        cell = ses.cell
        oracle_futs = ses.oracle(seed)
        ses.open(seed)
        win, stretches, metrics_open, metrics_close = ses.window(seed, seconds)
        memory = ses.memory()
        ses.stop_service()
        setup_s = win.t_open - t_start
        summary = W.summarize(win)
        say(phase="window", setup_s=setup_s, **summary,
            compiles_in_window=ses.monitor.count(COMPILE_EVENT, win.t_open,
                                                 win.t_close),
            placements=sorted({(r.status or {}).get("placement")
                               for r in win.counted()}, key=str),
            done_at_s=sorted(round(r.t_result - win.t_open, 2)
                             for r in win.counted()))
        # the check, once the window has closed and the peak has been read
        correct, checks, good = ses.judge(win, oracle_futs)
        values = {
            "setup_s": setup_s,
            "proofs_per_s": (len(good) / summary["window_s"]
                             if good and summary["window_s"] > 0 else None),
            "latency_mean_s": summary["latency_mean_s"],
            "latency_max_s": summary["latency_max_s"],
        }
        device_out = dict(ses.device, memory_peak_bytes=max(
            (m.get("peak_bytes_in_use", 0) for m in memory), default=0))
        result = {"correct": correct, "attempted": summary["attempted"],
                  "failed": summary["attempted"] - len(good)}
        if not trace:
            result["metrics"] = {
                m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                for m in cell.end_to_end
                if values.get(m["name"]) is not None}
        else:
            t_read = ses.clock()
            traces = stretches.read()
            read_s = ses.clock() - t_read
            traced = [(tr.events, tr.op_events, tr.window_s) for tr in traces]
            ev = readers.Evidence(
                statuses=[r.status for r in good],
                metrics_open=metrics_open, metrics_close=metrics_close,
                monitoring=ses.monitor.events, t_open=win.t_open,
                t_close=win.t_close, stretches=traced,
                memory_stats=memory, sizes=ses.conf["sizes"], peaks=ses.peaks)
            result["metrics"] = readers.read_all(cell.per_layer, ev)
            b = tracered.busy_over([(e, s) for e, _ops, s in traced])
            if b is not None:
                device_out.update(busy_s=b["busy_s"], window_s=b["window_s"])
            result["breakdown"] = {
                "device_ops": tracered.top(tracered.summed(
                    tracered.time_by_name(e, tracered.MODULES_LINE)
                    for e, _ops, _s in traced)),
                "idle_gaps": tracered.top(tracered.summed(
                    tracered.idle_gaps(e, HOST_SPAN_RE)
                    for e, _ops, _s in traced))}
            # the two clocks of a stretch, side by side: the sleep by this
            # process's clock and by the trace's mark, the device's busy
            # time inside the mark and in all the profiler caught
            say(phase="trace", read_s=read_s,
                stretches=[{"began_s": t0 - win.t_open, "seconds": t1 - t0,
                            "mark_s": (tr.window_s if tracered.find_mark(
                                tr.events) else None),
                            "xspace_bytes": len(x), "op_events": tr.op_events,
                            "busy_s": (tracered.busy(tr.events)
                                       or {}).get("busy_s"),
                            "uncut_busy_s": tr.uncut_busy_s}
                           for (t0, t1, x), tr
                           in zip(stretches.taken, traces)])
        result["device"] = device_out
        result["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                            for k, c in checks.items()}
        check.report(checks, correct)
        ses.close()
        return result
    except BaseException:
        ses.close(kill=True)
        raise
