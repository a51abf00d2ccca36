"""Distributed tracing: one timeline per proof, across processes.

The structured upgrade of the reference's ad-hoc timing printouts
(`println!("Elapsed: {:.2?}")` around each prover round,
/root/reference/src/dispatcher.rs:625,645,678,806,827,942 — commented out
in v2, dispatcher2.rs:293-693), grown into a propagated trace plane:

- every span carries a wall-anchored START timestamp (`ts`) and duration,
  so overlapping spans (pool concurrency, the fleet's concurrent phases)
  reconstruct into a real timeline instead of a bag of durations;
- every tracer owns a 128-bit `trace_id`, every span a 64-bit `sid` with
  a `parent` link, so spans recorded in DIFFERENT PROCESSES (service
  frontend, pool worker, fleet workers) correlate under one id;
- `context()` / `Tracer.from_context()` inject/extract a trace context
  dict across any boundary (job spec field, wire frame prefix — see
  runtime/protocol.py's TRACED flag);
- `merge_traces()` stitches per-process dumps into one timeline,
  applying per-process clock offsets (the dispatcher estimates them from
  the HEALTH ping round trip, NTP-style);
- `to_chrome_trace()` exports the Chrome trace-event JSON that
  chrome://tracing / Perfetto render directly — the xprof-style timeline
  view over the whole request path.

One clock for the request and the chip (ISSUE 26). Three more things ride
the same Tracer, and none of them is a second tracing system:

- PHASES: the top-level spans of a pool job that, with the ten round
  spans, tile its `run_s` (`Tracer.phases()`; STATUS `phases`). A member
  of a pipelined or batched prove that is in none of its own spans is in
  `pipeline_wait`: `Tracer.park()` / `unpark()` record it from explicit
  stamps, never as a remainder.
- `DeviceLedger`: one per device, owned by the backend (a service has one:
  a leased mesh backend is handed the pool backend's). It stamps when a
  round's device work was first dispatched and — from a watcher thread
  that blocks on the round's last device arrays, never on the worker's
  thread and never with a fence in the device queue — when it was done,
  so a round is charged device-true time (`device/roundN` events, STATUS
  `device`). The same ledger knows when NOTHING of ours is outstanding on
  the chip and charges that unfed time to the phase the worker that ends
  the gap was in (`device_unfed_s/<phase>` counters, whole window).
- with DPT_JAX_TRACE=1 a beacon thread mirrors the workers' phases into
  the profiler every 20 ms, so a profiler session of any length, begun at
  any time, finds the program's phases on its own clock (a TraceMe that
  began before the session or ends after it is dropped, which loses every
  long span).

Timestamps: each Tracer latches (time.time(), perf_counter()) once at
construction and derives every span's `ts` from the perf_counter delta —
monotonic WITHIN a process, wall-anchored for cross-process merge. Within
one process, later spans therefore never time-travel even if the system
clock steps.

Usage:
    tracer = Tracer(proc="pool/w0g1")
    with tracer.span("round1"):
        with tracer.span("round1/ifft", polys=5):
            ...
    print(tracer.to_json())

Cross-process:
    ctx = tracer.context()               # {"trace_id": ..., "parent_id": ...}
    ...ship ctx...
    remote = Tracer.from_context(ctx, proc="worker/2")
    merged = merge_traces([tracer.dump(), remote_dump], offsets=[0.0, off])
    open("trace.json", "w").write(json.dumps(to_chrome_trace(merged)))
"""

import json
import os
import queue
import secrets
import socket
import threading
import time
from contextlib import contextmanager, nullcontext

# DPT_JAX_TRACE=1: every Tracer span additionally opens a
# jax.profiler.TraceAnnotation, so spans show up on the device timeline of
# a jax.profiler capture (the SURVEY §5 device-trace replacement for the
# reference's wall-clock printouts). Off by default: annotation setup is
# not free on the hot path and tooling to view traces may be absent.
_JAX_TRACE = bool(os.environ.get("DPT_JAX_TRACE"))


def _jax_annotation(path):
    if not _JAX_TRACE:
        return nullcontext()
    from jax.profiler import TraceAnnotation
    return TraceAnnotation(path)


# The top-level spans of a pool job outside its rounds. With round1..5
# (and roundN_finalize under the pipeline) they tile the job's run_s;
# service/pool.py reports what is left as `unaccounted`.
PHASES = ("circuit_build", "guard_open", "checkpoint_save", "pipeline_wait",
          "serialize", "self_verify", "journal_done", "trace_store")
# what a pool worker is in when none of its jobs' top-level spans is open
WORKER_IDLE = "worker_idle"      # blocked on the dispatch queue
WORKER_OTHER = "other"           # has a unit, between spans


def new_trace_id():
    """128-bit trace id, 32 hex chars."""
    return secrets.token_hex(16)


def new_span_id():
    """64-bit span id, 16 hex chars."""
    return secrets.token_hex(8)


# --- workload flops/bytes models ---------------------------------------------
# The bench.py attribution model, exported so prover/worker kernel spans
# can carry `flops`/`data_bytes` attrs and the metrics layer can expose
# live per-stage MFU instead of bench-only numbers. "Useful flops" = the
# band FMAs of the field muls each kernel performs (limb-matrix SOS
# multiplication: 3 byte-product bands of (2L)^2 MACs, 2 flops each).

FR_BAND_FLOPS = 3 * 32 * 32 * 2      # one Fr mul (L=16 u16 limbs)
FQ_BAND_FLOPS = 3 * 48 * 48 * 2      # one Fq mul (L=24)
FR_BYTES = 32
MSM_MULS_PER_POINT = 32 * 11         # signed radix-256: 32 windows, ~11
                                     # Fq muls per mixed add


def ntt_flops(n, count=1):
    """Model flops for `count` n-point NTTs."""
    if n < 2:
        return 0
    return count * (n // 2) * (n.bit_length() - 1) * FR_BAND_FLOPS


def msm_flops(n_points, count=1):
    """Model flops for `count` n-point G1 MSMs."""
    return count * n_points * MSM_MULS_PER_POINT * FQ_BAND_FLOPS


class Tracer:
    """Span recorder for one process's slice of one trace.

    Thread-safe: the span stack is thread-local (concurrent pool/fleet
    threads nest independently) and the event list is lock-guarded, so
    one tracer can serve a whole multi-threaded prove."""

    def __init__(self, trace_id=None, parent_id=None, proc=None, host=None,
                 ledger=None, worker=None):
        # ledger/worker: top-level spans are this pool worker's PHASE on
        # the device ledger (fed/unfed account, profiler beacon)
        self.ledger = ledger
        self.worker = worker
        # waits: a member of a pipelined or batched prove is parked under
        # this name whenever one of its top-level spans ends
        self.waits = None
        self._parked = None
        self.trace_id = trace_id or new_trace_id()
        self.parent_id = parent_id    # remote parent span (extracted ctx)
        self.proc = proc or "main"
        self.host = host or socket.gethostname()
        self.pid = os.getpid()
        self.events = []
        self._tls = threading.local()
        self._lock = threading.Lock()
        # wall anchor: spans derive ts from the perf_counter delta, so
        # within this process timestamps are monotonic AND wall-anchored
        self._wall0 = time.time()
        self._perf0 = time.perf_counter()

    @classmethod
    def from_context(cls, ctx, proc=None, host=None):
        """Extract: continue a propagated trace in this process. `ctx` is
        the dict `context()` produced (tolerates None/garbage — a fresh
        root trace is started instead, never an error)."""
        if not isinstance(ctx, dict):
            return cls(proc=proc, host=host)
        tid = ctx.get("trace_id")
        if not (isinstance(tid, str) and tid):
            tid = None
        pid = ctx.get("parent_id")
        if not isinstance(pid, str):
            pid = None
        return cls(trace_id=tid, parent_id=pid, proc=proc, host=host)

    def _stack(self):
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        return st

    def context(self):
        """Inject: the propagation dict for the CURRENT point in the
        trace — innermost active span on this thread as parent, falling
        back to the extracted remote parent."""
        stack = self._stack()
        parent = stack[-1][1] if stack else self.parent_id
        ctx = {"trace_id": self.trace_id}
        if parent is not None:
            ctx["parent_id"] = parent
        return ctx

    @contextmanager
    def span(self, name, parent=None, **attrs):
        """Record one span; yields its span id (the value to use as a
        remote child's parent). `parent` overrides the inferred parent
        (innermost active span on this thread, else the extracted remote
        parent) — receivers link each incoming frame's span to the
        caller-supplied parent this way without racing on tracer state."""
        stack = self._stack()
        top = not stack
        path = "/".join([s[0] for s in stack] + [name])
        sid = new_span_id()
        if parent is None:
            parent = stack[-1][1] if stack else self.parent_id
        stack.append((name, sid))
        token = None
        if top:
            self.unpark()
            if self.ledger is not None:
                token = self.ledger.enter(self.worker, name)
        t0 = time.perf_counter()
        try:
            with _jax_annotation(path):
                yield sid
        finally:
            dur = time.perf_counter() - t0
            stack.pop()
            if top:
                if token is not None:
                    self.ledger.leave(self.worker, token)
                if self.waits:
                    self.park(self.waits)
            ev = {"span": path, "dur_s": round(dur, 6),
                  "ts": round(self._wall0 + (t0 - self._perf0), 6),
                  "sid": sid,
                  # thread lane: overlapping spans from concurrent fleet/
                  # pool threads render side by side, not stacked
                  "tid": threading.get_ident() % 1_000_000}
            if parent is not None:
                ev["parent"] = parent
            if attrs:
                ev.update(attrs)
            with self._lock:
                self.events.append(ev)

    def add_event(self, name, ts, dur_s, parent=None, **attrs):
        """Record a synthetic span from explicit wall-clock bounds (e.g.
        the queue-wait interval measured outside any `with` block).
        Like span(), an omitted parent falls back to the extracted
        remote parent so synthetic spans stay in the caller's tree."""
        if parent is None:
            parent = self.parent_id
        ev = {"span": name, "dur_s": round(float(dur_s), 6),
              "ts": round(float(ts), 6), "sid": new_span_id()}
        if parent is not None:
            ev["parent"] = parent
        if attrs:
            ev.update(attrs)
        with self._lock:
            self.events.append(ev)
        return ev["sid"]

    def park(self, name):
        """The job waits from now on, for something that is not its own
        work (a pipeline-mate's turn at the driver): the next top-level
        span, or unpark(), ends the wait and records it as one `name`
        event. Explicit stamps at both ends, so the wait is measured and
        not inferred."""
        with self._lock:
            if self._parked is None:
                self._parked = (name, time.perf_counter())

    def unpark(self):
        with self._lock:
            parked, self._parked = self._parked, None
        if parked is not None:
            name, t0 = parked
            self.add_event(name, ts=self._wall0 + (t0 - self._perf0),
                           dur_s=time.perf_counter() - t0)

    def totals(self, depth=1):
        """{span: total seconds} for spans at most `depth` levels deep."""
        out = {}
        with self._lock:
            events = list(self.events)
        for ev in events:
            if ev["span"].count("/") < depth:
                out[ev["span"]] = out.get(ev["span"], 0.0) + ev["dur_s"]
        return out

    def phases(self):
        """{phase: total seconds} over PHASES: the top-level spans of
        those names, and `checkpoint_save` wherever it nests."""
        out = {}
        with self._lock:
            events = list(self.events)
        for ev in events:
            name = ev["span"]
            if name.endswith("/checkpoint_save"):
                name = "checkpoint_save"
            if name in PHASES:
                out[name] = out.get(name, 0.0) + ev["dur_s"]
        return out

    def family(self, prefix):
        """{last segment: total seconds} of the events under `prefix/`
        (`device` -> {"round1": ...} from the device/roundN events)."""
        out = {}
        with self._lock:
            events = list(self.events)
        for ev in events:
            head, _, tail = ev["span"].partition("/")
            if head == prefix and tail:
                out[tail] = out.get(tail, 0.0) + ev["dur_s"]
        return out

    def dump(self):
        """This process's slice of the trace: one JSON-able dict
        (merge_traces input; TRACE_DUMP ships exactly this)."""
        with self._lock:
            events = list(self.events)
        return {"trace_id": self.trace_id, "proc": self.proc,
                "host": self.host, "pid": self.pid, "events": events}

    def wall(self, perf_t):
        """A perf_counter reading of this process as a span `ts`."""
        return self._wall0 + (perf_t - self._perf0)

    def to_json(self):
        return json.dumps(self.dump(), separators=(",", ":"))

    def to_chrome_trace(self):
        """Chrome trace-event export of this process's spans alone (the
        merged multi-process export goes through merge_traces first)."""
        return to_chrome_trace(self.dump())


# --- the device's side of the clock ------------------------------------------

class DeviceRound:
    """One member's round on the device: opened at the round's first
    dispatch, closed by the completion stamp of its last device arrays.
    `charge` is the device-true seconds it is charged (see DeviceLedger),
    `start` the perf_counter reading the charge begins at; both None
    until `done` is set."""

    __slots__ = ("worker", "t_first", "t_ready", "start", "charge", "done",
                 "handed")

    def __init__(self, worker, t_first):
        self.worker = worker
        self.t_first = t_first
        self.t_ready = self.start = self.charge = None
        self.done = threading.Event()
        self.handed = False      # to the watcher, or closed


class _WorkerPhases:
    """One pool worker on the ledger: `base` (idle / between spans), the
    top-level `spans` open on its threads as (token, name), and `hist`,
    its phase changes [(t, phase)] since the current unfed gap began (the
    last one alone while the device is fed)."""

    __slots__ = ("base", "spans", "hist")

    def __init__(self):
        self.base, self.spans, self.hist = WORKER_OTHER, [], []


class DeviceLedger:
    """What one device has of ours to run, and who left it empty.

    Completion stamps (device-true round time). `open(worker)` stamps a
    round's first dispatch; `watch(rnd, arrays)` hands the round's LAST
    device arrays (the commit's totals, the evaluations) to one long-lived
    daemon watcher, which calls `block_until_ready` on them off the
    worker's thread and stamps `t_ready`; `close(rnd)` stamps at once, for
    a caller that has already fetched the result. The device executes in
    dispatch order, so a round is charged
    `t_ready - max(t_first, t_ready of the previous completion)`:
    everything it dispatched, counted once, whoever else had work queued.
    Nothing is added to the device queue.

    Fed / unfed account. While no round is open the device has nothing of
    ours to run. Every such second is charged once, to the phases the
    worker whose `open` ended the gap went through during it
    (`device_unfed_s/<phase>`; WORKER_IDLE where it was waiting for a
    job), and all time to `phase_clock_s`. `counters()` charges the still
    open gap first, so a reading is true to the instant it is taken.

    Phases. `idle` / `busy` set a pool worker's base phase, `enter` /
    `leave` bracket the top-level spans of its jobs (Tracer does that);
    a worker with two threads in spans at once (the pipeline's launch
    executor and its finalize driver) is in the one entered last. With
    DPT_JAX_TRACE=1 (or `beacon=True`) a daemon thread mirrors all
    workers' phases into the profiler every 20 ms as
    `service/phases/<a>+<b>`; off, that thread does not exist.

    `clock` is injectable for the tests; the watcher starts at the first
    `watch`."""

    BEACON_S = 0.02
    _SEEDED = ((WORKER_IDLE, WORKER_OTHER)
               + tuple(p for p in PHASES if p != "pipeline_wait")
               + tuple("round%d%s" % (i, suffix) for i in range(1, 6)
                       for suffix in ("", "_finalize")))

    def __init__(self, clock=time.perf_counter, beacon=None):
        self._clock = clock
        self._lock = threading.Lock()
        self._mark = self._gap_start = clock()
        self._open = 0
        self._prev_ready = None
        self._workers = {}      # worker -> _WorkerPhases
        self._tokens = 0
        self._clock_s = 0.0
        self._unfed = {p: 0.0 for p in self._SEEDED}
        self._queue = queue.Queue()
        self._watcher = None
        self._stop = threading.Event()
        self._beacon = None
        if _JAX_TRACE if beacon is None else beacon:
            self._beacon = threading.Thread(
                target=self._beacon_loop, name="dpt-phase-beacon",
                daemon=True)
            self._beacon.start()

    # -- phases ---------------------------------------------------------------

    def _state(self, worker):
        st = self._workers.get(worker)
        if st is None:
            st = self._workers[worker] = _WorkerPhases()
        return st

    def _changed(self, st, now):
        phase = st.spans[-1][1] if st.spans else st.base
        hist = st.hist
        if hist and hist[-1][1] == phase:
            return
        if self._gap_start is None:
            del hist[:]          # fed: no gap can reach back before now
        hist.append((now, phase))

    def _set_base(self, worker, phase):
        if worker is None:
            return
        now = self._clock()
        with self._lock:
            st = self._state(worker)
            st.base = phase
            self._changed(st, now)

    def idle(self, worker):
        """The worker blocks on the dispatch queue."""
        self._set_base(worker, WORKER_IDLE)

    def busy(self, worker):
        """The worker holds a dispatch unit."""
        self._set_base(worker, WORKER_OTHER)

    def enter(self, worker, phase):
        if worker is None:
            return None
        now = self._clock()
        with self._lock:
            self._tokens += 1
            st = self._state(worker)
            st.spans.append((self._tokens, phase))
            self._changed(st, now)
            return self._tokens

    def leave(self, worker, token):
        if worker is None or token is None:
            return
        now = self._clock()
        with self._lock:
            st = self._state(worker)
            st.spans[:] = [e for e in st.spans if e[0] != token]
            self._changed(st, now)

    def phase_names(self):
        """Every worker's current phase, sorted."""
        with self._lock:
            return sorted(st.hist[-1][1] for st in self._workers.values()
                          if st.hist)

    # -- rounds on the device -------------------------------------------------

    def open(self, worker=None):
        """A round's first dispatch. Ends the unfed gap, if there is one,
        on this worker's account."""
        now = self._clock()
        with self._lock:
            if self._open == 0:
                self._charge_gap(now, worker)
                self._gap_start = None
            self._open += 1
        return DeviceRound(worker, now)

    def watch(self, rnd, arrays):
        """Stamp `rnd` done when `arrays` are ready, off this thread."""
        if self._watcher is None:
            with self._lock:
                if self._watcher is None:
                    self._watcher = threading.Thread(
                        target=self._watch_loop, name="dpt-device-watcher",
                        daemon=True)
                    self._watcher.start()
        rnd.handed = True
        self._queue.put((rnd, tuple(arrays)))

    def close(self, rnd):
        """Stamp `rnd` done now: its result is already on the host, or
        its launch failed before anything could be watched. A round the
        watcher already has is left to it, so this is safe in a
        `finally`."""
        if not rnd.handed:
            rnd.handed = True
            self._stamp(rnd)

    def _stamp(self, rnd):
        now = self._clock()
        with self._lock:
            rnd.start = max(rnd.t_first, self._prev_ready or rnd.t_first)
            rnd.t_ready = self._prev_ready = now
            rnd.charge = max(0.0, now - rnd.start)
            self._open -= 1
            if self._open == 0:
                self._gap_start = now
        rnd.done.set()

    def _watch_loop(self):
        while True:
            item = self._queue.get()
            if item is None:
                return
            rnd, arrays = item
            for a in arrays:
                try:
                    a.block_until_ready()
                except Exception:  # a failed launch is still a completion
                    pass
            self._stamp(rnd)

    # -- the account ----------------------------------------------------------

    def _charge_gap(self, now, worker):
        """Charge the open gap, up to `now`, to `worker`'s phases during
        it; call with the lock held."""
        g0 = self._gap_start
        if g0 is None or now <= g0:
            return
        st = self._workers.get(worker)
        hist = list(st.hist) if st and st.hist else [(g0, WORKER_OTHER)]
        if hist[0][0] > g0:      # before the worker existed
            hist.insert(0, (g0, WORKER_IDLE))
        ends = [t for t, _p in hist[1:]] + [now]
        for (t, phase), end in zip(hist, ends):
            a, b = max(t, g0), min(end, now)
            if b > a:
                self._unfed[phase] = self._unfed.get(phase, 0.0) + (b - a)
        self._gap_start = now
        for other in self._workers.values():
            del other.hist[:-1]

    def _likely_ender(self):
        """Whom to charge a gap that nobody has ended yet: the busy worker
        that changed phase last, else the one that went idle last."""
        best = None
        for worker, st in self._workers.items():
            if st.hist:
                t, phase = st.hist[-1]
                key = (phase != WORKER_IDLE, t)
                if best is None or key > best[0]:
                    best = (key, worker)
        return best[1] if best else None

    def counters(self):
        """The account as cumulative counters, true to this instant:
        `phase_clock_s` (all time since the ledger was made),
        `device_unfed_s` and `device_unfed_s/<phase>` (which sum to it)."""
        now = self._clock()
        with self._lock:
            self._clock_s += now - self._mark
            self._mark = now
            if self._gap_start is not None:
                self._charge_gap(now, self._likely_ender())
            out = {"device_unfed_s/" + p: v for p, v in self._unfed.items()}
            out["device_unfed_s"] = sum(self._unfed.values())
            out["phase_clock_s"] = self._clock_s
        return out

    # -- the beacon -----------------------------------------------------------

    def _beacon_loop(self):
        from jax.profiler import TraceAnnotation
        while not self._stop.is_set():
            names = self.phase_names()
            if not names:
                time.sleep(self.BEACON_S)
                continue
            with TraceAnnotation("service/phases/" + "+".join(names)):
                time.sleep(self.BEACON_S)

    def close_threads(self):
        """Stop the watcher and the beacon (tests; a service's ledger
        lives as long as its backend, and both threads are daemons)."""
        self._stop.set()
        if self._watcher is not None:
            self._queue.put(None)
            self._watcher.join(timeout=5)
        if self._beacon is not None:
            self._beacon.join(timeout=5)


class _NullTracer:
    """No-op tracer: `span` costs one contextmanager enter/exit."""

    events = ()
    trace_id = None
    ledger = worker = None

    @contextmanager
    def span(self, name, **attrs):
        yield None

    def add_event(self, name, ts, dur_s, parent=None, **attrs):
        return None

    def context(self):
        return None

    # settable and ignored: the drivers mark their members' tracers
    waits = property(lambda self: None, lambda self, value: None)

    def park(self, name):
        pass

    def unpark(self):
        pass

    def wall(self, perf_t):
        return 0.0

    def totals(self, depth=1):
        return {}

    def phases(self):
        return {}

    def family(self, prefix):
        return {}

    def dump(self):
        return {}

    def to_json(self):
        return "{}"


NULL_TRACER = _NullTracer()


# --- cross-process merge + export --------------------------------------------

def merge_traces(dumps, offsets=None):
    """Stitch per-process tracer dumps into ONE timeline.

    dumps: list of Tracer.dump() dicts (or TRACE_DUMP replies). offsets:
    optional list, aligned with dumps, of estimated seconds each dump's
    clock runs AHEAD of the reference clock (dump 0's, usually the
    dispatcher's) — subtracted from that dump's timestamps, so a worker
    whose wall clock is skewed still lands in the right place on the
    merged timeline. The offset estimate comes from the HEALTH ping
    round trip: offset = worker_now - (t_send + t_recv)/2.

    Returns {"trace_id", "processes": [{proc, host, pid, offset_s,
    spans}], "events": [...]} with per-event proc/host/pid labels
    attached and events sorted by corrected start time.
    """
    if offsets is None:
        offsets = [0.0] * len(dumps)
    trace_id = next((d.get("trace_id") for d in dumps
                     if d.get("trace_id")), None)
    processes = []
    events = []
    for d, off in zip(dumps, offsets):
        if not d or not d.get("events"):
            continue
        if "processes" in d:
            # already-merged timeline (e.g. fetched from /trace/<job_id>):
            # splice it in — events carry their proc/pid labels already —
            # so a client can stitch its own spans onto a server timeline
            processes.extend(dict(p) for p in d.get("processes") or [])
            for ev in d["events"]:
                ev = dict(ev)
                ev["ts"] = round(float(ev.get("ts", 0.0)) - off, 6)
                events.append(ev)
            continue
        proc = d.get("proc") or "?"
        host = d.get("host") or "?"
        pid = d.get("pid") or 0
        processes.append({"proc": proc, "host": host, "pid": pid,
                          "offset_s": round(float(off), 6),
                          "spans": len(d["events"])})
        for ev in d["events"]:
            ev = dict(ev)
            ev["ts"] = round(float(ev.get("ts", 0.0)) - off, 6)
            ev["proc"] = proc
            ev["host"] = host
            ev["pid"] = pid
            events.append(ev)
    events.sort(key=lambda ev: ev["ts"])
    return {"trace_id": trace_id, "processes": processes, "events": events}


_EVENT_KEYS = ("span", "ts", "dur_s", "sid", "parent", "proc", "host",
               "pid", "tid")


def to_chrome_trace(merged):
    """Merged timeline (merge_traces output, or a single Tracer.dump())
    -> Chrome trace-event JSON dict: load the result in chrome://tracing
    or https://ui.perfetto.dev. Complete events ("ph": "X") with
    microsecond timestamps rebased to the earliest span; per-process
    metadata rows name each pid as proc@host."""
    if "processes" not in merged:
        merged = merge_traces([merged])
    events = merged.get("events") or []
    base = min((ev["ts"] for ev in events), default=0.0)
    out = []
    for p in merged.get("processes", []):
        out.append({"ph": "M", "name": "process_name", "pid": p["pid"],
                    "args": {"name": f"{p['proc']}@{p['host']}"}})
    for ev in events:
        args = {k: v for k, v in ev.items() if k not in _EVENT_KEYS}
        args["sid"] = ev.get("sid")
        if ev.get("parent") is not None:
            args["parent"] = ev["parent"]
        out.append({
            "ph": "X",
            "name": ev["span"],
            "cat": "span",
            "ts": round((ev["ts"] - base) * 1e6, 1),
            "dur": round(ev["dur_s"] * 1e6, 1),
            "pid": ev.get("pid", 0),
            "tid": ev.get("tid", 0),
            "args": args,
        })
    # structured log events (obs/log.py, merged in by collect_trace /
    # the service pool) render as instant events on the same timeline:
    # quarantines/replans/respawns line up visually under the spans
    for ev in merged.get("logs") or []:
        out.append({
            "ph": "i",
            "name": f"{ev.get('subsystem', '?')}/{ev.get('event', '?')}",
            "cat": "log",
            "s": "g",  # global-scope instant marker
            "ts": round((float(ev.get("ts", base)) - base) * 1e6, 1),
            "pid": ev.get("pid", 0),
            "tid": 0,
            "args": {k: v for k, v in ev.items()
                     if k not in ("ts", "pid")},
        })
    return {"traceEvents": out, "displayTimeUnit": "ms",
            "otherData": {"trace_id": merged.get("trace_id"),
                          "base_ts_s": round(base, 6)}}
