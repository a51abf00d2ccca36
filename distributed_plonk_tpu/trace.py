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

    def __init__(self, trace_id=None, parent_id=None, proc=None, host=None):
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
        path = "/".join([s[0] for s in stack] + [name])
        sid = new_span_id()
        if parent is None:
            parent = stack[-1][1] if stack else self.parent_id
        stack.append((name, sid))
        t0 = time.perf_counter()
        try:
            with _jax_annotation(path):
                yield sid
        finally:
            dur = time.perf_counter() - t0
            stack.pop()
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

    def totals(self, depth=1):
        """{span: total seconds} for spans at most `depth` levels deep."""
        out = {}
        with self._lock:
            events = list(self.events)
        for ev in events:
            if ev["span"].count("/") < depth:
                out[ev["span"]] = out.get(ev["span"], 0.0) + ev["dur_s"]
        return out

    def dump(self):
        """This process's slice of the trace: one JSON-able dict
        (merge_traces input; TRACE_DUMP ships exactly this)."""
        with self._lock:
            events = list(self.events)
        return {"trace_id": self.trace_id, "proc": self.proc,
                "host": self.host, "pid": self.pid, "events": events}

    def to_json(self):
        return json.dumps(self.dump(), separators=(",", ":"))

    def to_chrome_trace(self):
        """Chrome trace-event export of this process's spans alone (the
        merged multi-process export goes through merge_traces first)."""
        return to_chrome_trace(self.dump())


class _NullTracer:
    """No-op tracer: `span` costs one contextmanager enter/exit."""

    events = ()
    trace_id = None

    @contextmanager
    def span(self, name, **attrs):
        yield None

    def add_event(self, name, ts, dur_s, parent=None, **attrs):
        return None

    def context(self):
        return None

    def totals(self, depth=1):
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
