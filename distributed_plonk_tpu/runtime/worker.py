"""Worker daemon: serves MSM/NTT over the native framed transport.

The analog of the reference's worker binary (/root/reference/src/worker.rs:
441-536): holds device-resident SRS state across requests (State,
worker.rs:42-59), executes kernels per RPC. Threading model: one thread per
connection, state guarded by a lock — replacing the reference's
single-thread-plus-unsafe-aliasing design (worker.rs:135 etc.) with an
actually sound one.

Also serves the cross-worker sharded 4-step FFT (the reference's signature
protocol): FFT_INIT allocates a task (worker.rs:187-233), FFT1 runs the
stage-1 row kernels (worker.rs:235-278 -> 66-94), FFT2_PREPARE pushes each
peer its column slices over direct worker<->worker connections
(worker.rs:280-345 sender, 412-438 receiver), FFT2 runs the stage-2 column
kernels and returns the result shard (worker.rs:347-381 -> 96-115). Unlike
the reference there is no second listener plane: peer exchange frames
arrive on the same port, distinguished by tag (netconfig.py documents the
single-plane choice).

Run: python -m distributed_plonk_tpu.runtime.worker <index> [config.json]
    [--backend python|jax] [--store DIR]

--store serves the given artifact store over the STORE_FETCH tag (a
replacement worker on a fresh host pulls SRS/pk/checkpoint blobs from a
peer instead of rebuilding — store/remote.py is the client side).
"""

import os
import struct
import sys
import threading
import time
from collections import OrderedDict

import numpy as np

from . import native, protocol
from .faults import FaultInjector
from .netconfig import NetworkConfig
from ..constants import R_MOD, FR_GENERATOR
from ..fields import fr_inv, fr_root_of_unity
from ..obs import log as olog
from ..obs import profiling
from ..poly import Domain, poly_eval
from ..service.metrics import Metrics
from ..trace import NULL_TRACER, Tracer, msm_flops, ntt_flops

# resident per-trace span buffers: the dispatcher fetches-and-forgets
# them via TRACE_DUMP, but a dispatcher that dies mid-prove must not
# leak its trace buffers forever — LRU cap, oldest trace dropped
_TRACE_CAP = int(os.environ.get("DPT_WORKER_TRACE_CAP", "32"))


def _make_backend(name):
    if name == "jax":
        from ..backend.jax_backend import JaxBackend
        return JaxBackend()
    from ..backend.python_backend import PythonBackend
    return PythonBackend()


class FftTask:
    """In-flight sharded FFT state (the reference's FftTask,
    /root/reference/src/worker.rs:50-54): stage-1 results for our rows,
    stage-2 input columns filled in by peer exchanges.

    Data plane is numpy limb matrices end to end (exchange panels land with
    one slice assignment); `created` supports age-based GC, fixing the
    reference's task leak on dispatcher abort (worker.rs:378)."""

    def __init__(self, inverse, coset, n, r, c, rs, re, col_ranges, me,
                 keep_raw=False):
        self.inverse = inverse
        self.coset = coset
        self.n, self.r, self.c = n, r, c
        self.rs, self.re = rs, re          # our stage-1 rows (j2 indices)
        self.col_ranges = col_ranges       # every worker's stage-2 range (k1)
        self.cs, self.ce = col_ranges[me]
        self.rows = [None] * (re - rs)     # [local j2] -> length-r row (ints)
        self.rows_mat = None               # (16, re-rs, r) panel (jax path)
        self.rows_filled = np.zeros(re - rs, dtype=bool)
        # RAW stage-1 input panels as received (first_row -> limbs): the
        # integrity plane's input-side partial is a power sum of what
        # this worker actually holds, so the dispatcher can tell "your
        # input rotted" from "your stage-2 math lied" (keyed by
        # first_row, so a retried FFT1 resend overwrites idempotently).
        # Retained only when FFT_INIT announced an armed integrity plane
        # (keep_raw) — a plane-off fleet keeps legacy panel memory.
        self.keep_raw = keep_raw
        self.raw_panels = {}
        # [16, local k1, j2] stage-2 input columns; fill_mask tracks exchange
        # completeness per (column, row) cell — a REGION mask, not a counter,
        # so a retried FFT2_PREPARE (same panels re-pushed after a dispatcher
        # reconnect) stays idempotent
        self.cols = np.zeros((16, self.ce - self.cs, c), dtype=np.uint32)
        self.fill_mask = np.zeros((self.ce - self.cs, c), dtype=bool)
        self.cols_lock = threading.Lock()
        self.created = time.monotonic()
        # FFT2 caches its reply here instead of deleting the task, so a
        # dispatcher retry (reconnect after timeout) gets the same bytes
        # back — FFT2 is idempotent like every other request; completed
        # tasks are GC'd by age at the next FFT_INIT
        self.result = None
        self.done_at = None


class WorkerState:
    def __init__(self, backend, config=None, me=0, store=None, epoch=0):
        self.backend = backend
        self.config = config
        self.me = me
        self.store = store  # optional ArtifactStore served via STORE_FETCH
        # membership-roster version this worker last adopted (0 = static
        # fleet / never joined): FFT_INIT frames planned against an older
        # epoch are rejected as stale, and ROSTER pushes advance it
        self.epoch = epoch
        # worker-side chaos: the `corrupt:at=data` plane perturbs OUR
        # computed results before framing (SDC model — runtime/faults.py);
        # None when DPT_FAULTS is unset, zero-overhead fast path
        self.faults = FaultInjector.from_env()
        self.sdc_injected = 0
        self.warm = None  # warm-rejoin stats (store/remote.warm_sync)
        # full observability registry (served counters, kernel latency
        # histograms, live gflops/MFU gauges) served over METRICS_FETCH —
        # the structured upgrade of the raw {tag: count} STATS dict,
        # which stays for wire back-compat. The structured-log ring
        # (obs/log.py) publishes its counters here too.
        self.metrics = Metrics()
        olog.set_metrics(self.metrics)
        self.started = time.monotonic()
        self.base_sets = {}  # set_id -> bases (a worker can adopt ranges)
        self.lock = threading.Lock()
        self.domains = {}
        self.fft_tasks = {}
        self.peers = {}
        self.peer_lock = threading.Lock()
        self.counters = {}
        # trace_id -> Tracer holding this worker's spans for that trace
        # (shipped back + forgotten on TRACE_DUMP; LRU-capped)
        self.traces = OrderedDict()
        # jax workers run whole FFT1/FFT2 frames as single batched device
        # launches over limb panels (no per-row dispatch, no host ints)
        if getattr(backend, "name", "") == "jax":
            from .jax_stages import StageKernels
            self.stages = StageKernels()
        else:
            self.stages = None

    def domain(self, n):
        if n not in self.domains:
            self.domains[n] = Domain(n)
        return self.domains[n]

    def count(self, tag):
        with self.lock:
            self.counters[tag] = self.counters.get(tag, 0) + 1
        # served_<tag> counter family in the structured registry: what
        # the fleet scraper aggregates into dpt_fleet_served_* series
        self.metrics.inc("served_" + protocol.tag_name(tag).lower())

    def observe_kernel(self, stage, dur_s, flops=0, data_bytes=0):
        """Fold one kernel execution into the live per-stage surfaces:
        a latency histogram plus — when the flops model applies — the
        same kernel_<stage>_gflops / mfu_<stage>_pct gauges the service
        pool derives from trace spans, so a fleet worker's device
        utilization is scrapeable without a trace being armed."""
        self.metrics.observe(f"worker_{stage}_s", dur_s)
        if flops:
            self.metrics.observe_kernels(
                [{"span": stage, "flops": flops, "dur_s": dur_s,
                  "data_bytes": data_bytes}],
                device_kind=self.backend.device_info()["device_kind"])

    def tracer_for(self, ctx):
        """The per-trace Tracer an incoming traced frame records under
        (created on first sight of the trace id, LRU past _TRACE_CAP)."""
        tid = ctx.get("trace_id") if isinstance(ctx, dict) else None
        if not tid:
            return NULL_TRACER
        with self.lock:
            tr = self.traces.get(tid)
            if tr is None:
                tr = self.traces[tid] = Tracer(
                    trace_id=tid, proc=f"worker/{self.me}")
                while len(self.traces) > _TRACE_CAP:
                    self.traces.popitem(last=False)
            else:
                self.traces.move_to_end(tid)
            return tr

    def pop_trace(self, trace_id):
        with self.lock:
            return self.traces.pop(trace_id, None)

    def peer(self, p):
        """Lazy worker->worker connection (the reference opens peer
        connections per exchange, worker.rs:297-338; here they are cached).
        Includes the self-loop via TCP, as the reference does."""
        with self.peer_lock:
            if p not in self.peers:
                host, port = self.config.workers[p]
                conn = native.connect(host, port)
                self.peers[p] = (conn, threading.Lock())
            return self.peers[p]

    def drop_peer(self, p):
        """Forget a cached peer connection (it broke mid-exchange — the
        peer died or restarted; the next peer() dials fresh)."""
        with self.peer_lock:
            entry = self.peers.pop(p, None)
        if entry is not None:
            try:
                entry[0].close()
            except OSError:  # pragma: no cover - already dead
                pass

    def peer_call(self, p, tag, payload):
        """One request/reply to peer p, retrying ONCE on a fresh
        connection: a cached stream goes stale when the peer restarts
        (cross-host re-admission), and the exchange payload is idempotent
        at the receiver (region-mask overwrite), so a blind resend is
        safe. Raises on the second failure — the dispatcher's fleet probe
        then attributes the death correctly."""
        for attempt in (0, 1):
            pconn, plock = self.peer(p)
            with plock:
                try:
                    pconn.send(tag, payload)
                    return pconn.recv()
                except (ConnectionError, OSError):
                    self.drop_peer(p)
                    if attempt:
                        raise


def _sdc_due(state, tag):
    """True when the worker-side data-plane chaos should corrupt the
    result just computed for `tag` (see runtime/faults.py, at=data)."""
    if state.faults is None:
        return False
    if not state.faults.on_data(state.me, tag):
        return False
    with state.lock:
        state.sdc_injected += 1
    olog.emit("worker", "sdc_injected", level="warn", worker=state.me,
              tag=protocol.tag_name(tag))
    return True


# traced kernel tags that earn a per-request structured log event (the
# control/bulk tags — PING, FFT1 panels, exchanges — would only be noise)
_LOGGED_TAGS = frozenset((protocol.MSM, protocol.NTT, protocol.FFT2,
                          protocol.EVAL, protocol.FFT_INIT))

# sum_j row[j] * base^j — exactly dense-poly Horner evaluation
_horner = poly_eval


def _fft2_partials(task, point):
    """The integrity piggyback (runtime/integrity.py): (input-side,
    output-side) partial power sums at the dispatcher's random point.
    Input side walks the RAW stage-1 rows as received (flat index
    j1*c + j2 -> row j2 Horner in base t^c, scaled t^j2); output side
    walks the computed result panel (flat index k1 + r*k2 -> row k1
    Horner in base t^r, scaled t^k1). Both are computed from the SAME
    buffers the data plane serves, so an SDC in either shows up in the
    partials exactly as it does in the data. O(n/k) host muls."""
    a = 0
    tc = pow(point, task.c, R_MOD)
    for first_row, panel in sorted(task.raw_panels.items()):
        count, row_len = panel.shape[1], panel.shape[2]
        ints = protocol.matrix_to_ints(panel.reshape(16, count * row_len))
        tk = pow(point, first_row, R_MOD)
        for off in range(count):
            row = ints[off * row_len:(off + 1) * row_len]
            a = (a + _horner(row, tc) * tk) % R_MOD
            tk = tk * point % R_MOD
    b = 0
    vals = protocol.decode_scalars(task.result)
    c = task.c
    tr = pow(point, task.r, R_MOD)
    tk = pow(point, task.cs, R_MOD)
    for k1 in range(task.ce - task.cs):
        b = (b + _horner(vals[k1 * c:(k1 + 1) * c], tr) * tk) % R_MOD
        tk = tk * point % R_MOD
    return a, b


def _stage1_row(backend, domain_r, task, j2, row):
    """Stage-1 kernel for one global row j2 (fft1_helper,
    /root/reference/src/worker.rs:66-94): optional forward-coset pre-scale
    g^(j2 + c*j1), r-point (i)FFT, mid twiddle w^(+-j2*k1) — twiddles built
    incrementally, not per-element pow (improving on worker.rs:77-79)."""
    n, r, c = task.n, task.r, task.c
    if task.coset and not task.inverse:
        gc = pow(FR_GENERATOR, c, R_MOD)
        t = pow(FR_GENERATOR, j2, R_MOD)
        scaled = []
        for v in row:
            scaled.append(v * t % R_MOD)
            t = t * gc % R_MOD
        row = scaled
    out = backend.ifft(domain_r, row) if task.inverse else backend.fft(domain_r, row)
    w = fr_root_of_unity(n)
    base = pow(fr_inv(w) if task.inverse else w, j2, R_MOD)
    t = 1
    tw = []
    for v in out:
        tw.append(v * t % R_MOD)
        t = t * base % R_MOD
    return tw


def _stage2_row(backend, domain_c, task, k1, row):
    """Stage-2 kernel for one global column row k1 (fft2_helper,
    /root/reference/src/worker.rs:96-115): c-point (i)FFT + inverse-coset
    post-scale g^-(k1 + r*k2); the 1/n factor comes from the two stage
    iFFTs (1/r * 1/c), as in the reference."""
    out = backend.ifft(domain_c, row) if task.inverse else backend.fft(domain_c, row)
    if task.inverse and task.coset:
        g_inv = fr_inv(FR_GENERATOR)
        step = pow(g_inv, task.r, R_MOD)
        t = pow(g_inv, k1, R_MOD)
        scaled = []
        for v in out:
            scaled.append(v * t % R_MOD)
            t = t * step % R_MOD
        return scaled
    return out


def handle(conn, state):
    """Serve one connection until EOF/shutdown. Returns False to stop the
    whole daemon."""
    while True:
        try:
            tag, payload = conn.recv()
        except ConnectionError:
            return True
        try:
            # trace-context framing: a TRACED frame carries the caller's
            # {trace_id, parent_id}; the request is served under a span in
            # that trace's buffer (shipped back via TRACE_DUMP). Untraced
            # frames take the identical path with the null tracer.
            tag, ctx, payload = protocol.strip_context(tag, payload)
            tracer = state.tracer_for(ctx) if ctx is not None else NULL_TRACER
            parent = ctx.get("parent_id") if ctx else None
            with tracer.span("serve/" + protocol.tag_name(tag).lower(),
                             parent=parent, req_bytes=len(payload)):
                cont = _dispatch(conn, state, tag, payload, tracer=tracer)
            if ctx is not None and tag in _LOGGED_TAGS:
                # trace-correlated structured event per traced KERNEL
                # frame (debug level; the ring cap bounds it): the
                # worker's leg of the incident timeline — LOG_FETCH
                # filtered by this trace_id returns exactly these
                olog.emit("worker", "served", level="debug",
                          worker=state.me, trace_id=tracer.trace_id,
                          tag=protocol.tag_name(tag))
        except Exception as e:  # malformed payload / backend failure
            # counted so the fleet scrape's serve-error aggregate
            # (dpt_fleet_serve_errors_total) reflects real error replies
            state.metrics.inc("serve_errors")
            try:
                conn.send(protocol.ERR, repr(e).encode())
            except ConnectionError:
                return True
            continue
        if cont is False:
            return False


# abandoned FFT tasks (dispatcher died mid-protocol) are purged when older
# than this; COMPLETED tasks (kept only so FFT2 retries can re-read their
# reply) are purged much sooner; both checked on every FFT_INIT
_FFT_TASK_TTL_S = float(os.environ.get("DPT_FFT_TASK_TTL", "600"))
_FFT_DONE_TTL_S = float(os.environ.get("DPT_FFT_DONE_TTL", "60"))
# hard cap on resident tasks (the FFT2 replay cache grew per task_id with
# no bound between FFT_INITs — a fast dispatcher loop could OOM a worker
# inside one TTL window): LRU eviction, completed tasks first (their reply
# cache is the cheap thing to lose — a retry after eviction recomputes),
# then oldest in-flight (those are abandoned replans by construction when
# the cap is hit)
_FFT_TASK_CAP = int(os.environ.get("DPT_FFT_TASK_CAP", "64"))


def _evict_fft_tasks(tasks, cap, now):
    """TTL purge + LRU cap for the task table (state.lock held). Keeps at
    most `cap` - 1 entries so the task the caller is about to insert fits."""
    stale = [tid for tid, t in tasks.items()
             if (now - t.created > _FFT_TASK_TTL_S
                 or (t.done_at is not None
                     and now - t.done_at > _FFT_DONE_TTL_S))]
    for tid in stale:
        del tasks[tid]
    room = max(cap - 1, 0)
    if len(tasks) <= room:
        return
    done = sorted((tid for tid, t in tasks.items() if t.done_at is not None),
                  key=lambda tid: tasks[tid].done_at)
    live = sorted((tid for tid, t in tasks.items() if t.done_at is None),
                  key=lambda tid: tasks[tid].created)
    for tid in done + live:
        if len(tasks) <= room:
            break
        del tasks[tid]


def _dispatch(conn, state, tag, payload, tracer=NULL_TRACER):
    """Handle one request frame. Returns False to stop the daemon, anything
    else to keep serving.

    Locking: state.lock guards only STATE lookups/mutations (bases ref,
    domain/task tables); kernel execution happens OUTSIDE it, so one worker
    can overlap compute for concurrent connections (round-2 weakness #9
    serialized the whole MSM under the lock)."""
    state.count(tag)
    if tag == protocol.PING:
        conn.send(protocol.OK)
    elif tag == protocol.INIT_BASES:
        set_id, bases = protocol.decode_init_bases(payload)
        with state.lock:
            state.base_sets[set_id] = bases
        conn.send(protocol.OK)
    elif tag == protocol.MSM:
        set_id, scalars = protocol.decode_msm_request(payload)
        with state.lock:
            bases = state.base_sets.get(set_id)
        if bases is None:
            conn.send(protocol.ERR, b"no bases for set %d" % set_id)
            return None
        # kernel span attrs carry the bench.py flops/bytes model so the
        # merged timeline (and the MFU gauges fed from it) can attribute
        # where device time went, not just that it went
        t0 = time.perf_counter()
        with tracer.span("msm", n=len(scalars),
                         flops=msm_flops(len(scalars)),
                         data_bytes=len(scalars) * protocol.FR_BYTES):
            result = state.backend.msm(bases, scalars)
        state.observe_kernel("msm", time.perf_counter() - t0,
                             flops=msm_flops(len(scalars)),
                             data_bytes=len(scalars) * protocol.FR_BYTES)
        if _sdc_due(state, protocol.MSM):
            # a WELL-FORMED wrong answer (on-curve, in-subgroup): only
            # value-level checks (duplicate execution) can catch it
            from .. import curve as _C
            result = _C.g1_add_affine(result, _C.G1_GEN)
        conn.send(protocol.OK, protocol.encode_point(result))
    elif tag == protocol.NTT:
        values, inverse, coset = protocol.decode_ntt_request(payload)
        with state.lock:
            domain = state.domain(len(values))
        t0 = time.perf_counter()
        with tracer.span("ntt", n=len(values), inverse=inverse, coset=coset,
                         flops=ntt_flops(len(values)),
                         data_bytes=len(values) * protocol.FR_BYTES):
            if inverse and coset:
                out = state.backend.coset_ifft(domain, values)
            elif inverse:
                out = state.backend.ifft(domain, values)
            elif coset:
                out = state.backend.coset_fft(domain, values)
            else:
                out = state.backend.fft(domain, values)
        state.observe_kernel("ntt", time.perf_counter() - t0,
                             flops=ntt_flops(len(values)),
                             data_bytes=len(values) * protocol.FR_BYTES)
        if _sdc_due(state, protocol.NTT):
            out = list(out)
            out[0] = (out[0] + 1) % R_MOD  # one flipped field element
        conn.send(protocol.OK,
                  protocol.encode_scalar_matrix(protocol.ints_to_matrix(out)))
    elif tag == protocol.FFT_INIT:
        (task_id, inverse, coset, n, r, c, rs, re,
         col_ranges, epoch, keep_raw) = protocol.decode_fft_init(payload)
        now = time.monotonic()
        with state.lock:
            if epoch and state.epoch and epoch != state.epoch:
                # roster mismatch in EITHER direction is unservable: an
                # older plan's col_ranges no longer match the fleet, and
                # a NEWER plan references peers this worker's table does
                # not know yet (it missed a roster push) — rejecting
                # loudly beats an IndexError mid-exchange, and the
                # dispatcher re-pushes the roster on the replan path so
                # the lagging side converges (epoch 0 on either side =
                # no membership plane, always accepted)
                state.counters["stale_epoch"] = \
                    state.counters.get("stale_epoch", 0) + 1
                conn.send(protocol.ERR,
                          b"stale epoch: frame %d, roster %d"
                          % (epoch, state.epoch))
                return None
            _evict_fft_tasks(state.fft_tasks, _FFT_TASK_CAP, now)
            state.fft_tasks[task_id] = FftTask(
                inverse, coset, n, r, c, rs, re, col_ranges, state.me,
                keep_raw=keep_raw)
        conn.send(protocol.OK)
    elif tag == protocol.FFT1:
        task_id, first_row, panel = protocol.decode_fft1_matrix(payload)
        with state.lock:
            task = state.fft_tasks[task_id]
        count = panel.shape[1]
        if task.keep_raw:
            # retain the raw input panel: the FFT2 integrity piggyback's
            # input-side partial is computed over exactly what we received
            task.raw_panels[first_row] = panel
        t0 = time.perf_counter()
        with tracer.span("fft1_rows", rows=count, r=task.r,
                         flops=ntt_flops(task.r, count),
                         data_bytes=count * task.r * protocol.FR_BYTES):
            if state.stages is not None:
                staged = state.stages.stage1_panel(task, first_row, panel)
                lo = first_row - task.rs
                with task.cols_lock:
                    if task.rows_mat is None:
                        task.rows_mat = np.zeros(
                            (16, task.re - task.rs, task.r), dtype=np.uint32)
                    task.rows_mat[:, lo:lo + count, :] = staged
                    task.rows_filled[lo:lo + count] = True
            else:
                with state.lock:
                    domain_r = state.domain(task.r)
                ints = protocol.matrix_to_ints(
                    panel.reshape(16, count * panel.shape[2]))
                row_len = panel.shape[2]
                for off in range(count):
                    j2 = first_row + off
                    task.rows[j2 - task.rs] = _stage1_row(
                        state.backend, domain_r, task, j2,
                        ints[off * row_len:(off + 1) * row_len])
        state.observe_kernel("fft1", time.perf_counter() - t0,
                             flops=ntt_flops(task.r, count))
        conn.send(protocol.OK)
    elif tag == protocol.FFT2_PREPARE:
        (task_id,) = struct.unpack_from("<Q", payload, 0)
        with state.lock:
            task = state.fft_tasks[task_id]
        # push every peer its column slice of our rows (the all-to-all,
        # worker.rs:280-345); each send waits for the peer's ACK, so our OK
        # to the dispatcher implies all our data has landed. Rows go out as
        # ONE contiguous limb panel per peer (bulk codec, no per-row lists).
        if task.re > task.rs:
            if task.rows_mat is not None:
                # loud failure if any row range never saw an FFT1 frame —
                # the zero-initialized panel must not ship silently (the
                # int path raised on a None row here)
                assert task.rows_filled.all(), \
                    f"fft2_prepare before stage 1 complete " \
                    f"({task.rows_filled.sum()}/{task.rows_filled.size})"
                rows_np = task.rows_mat
            else:
                flat = [v for j2 in range(task.rs, task.re)
                        for v in task.rows[j2 - task.rs]]
                rows_np = protocol.ints_to_matrix(flat).reshape(
                    16, task.re - task.rs, task.r)
            # the all-to-all is worker->worker: re-inject our trace
            # context into each peer frame so the receiving workers'
            # exchange spans land in the SAME trace (peer legs would
            # otherwise be invisible to the merged timeline)
            with tracer.span("fft_exchange_push") as push_sid:
                for p, (ps, pe) in enumerate(task.col_ranges):
                    if pe == ps:
                        continue
                    panel = np.ascontiguousarray(rows_np[:, :, ps:pe])
                    xtag, xpayload = protocol.FFT_EXCHANGE, \
                        protocol.encode_fft_exchange(
                            task_id, ps, pe - ps, task.rs, panel)
                    if push_sid is not None:
                        xtag, xpayload = protocol.wrap_traced(
                            xtag, xpayload, {"trace_id": tracer.trace_id,
                                             "parent_id": push_sid})
                    # peer_call retries once on a fresh stream: a peer that
                    # restarted since the last FFT invalidates the cached
                    # conn
                    rtag, rpayload = state.peer_call(p, xtag, xpayload)
                    if rtag != protocol.OK:
                        raise RuntimeError(
                            f"peer {p} exchange failed: {rpayload!r}")
        conn.send(protocol.OK)
    elif tag == protocol.FFT_EXCHANGE:
        task_id, col_start, col_count, row_start, panel = \
            protocol.decode_fft_exchange(payload)
        with state.lock:
            task = state.fft_tasks[task_id]
        lo = col_start - task.cs
        with task.cols_lock:
            task.cols[:, lo:lo + col_count,
                      row_start:row_start + panel.shape[1]] = \
                panel.transpose(0, 2, 1)
            task.fill_mask[lo:lo + col_count,
                           row_start:row_start + panel.shape[1]] = True
        conn.send(protocol.OK)
    elif tag == protocol.FFT2:
        task_id, check_point = protocol.decode_fft2_request(payload)
        with state.lock:
            task = state.fft_tasks[task_id]
            domain_c = state.domain(task.c)
        if task.result is None:
            assert task.fill_mask.all(), \
                f"fft2 before exchange complete ({task.fill_mask.sum()}" \
                f"/{task.fill_mask.size})"
            t0 = time.perf_counter()
            with tracer.span("fft2_cols", cols=task.ce - task.cs, c=task.c,
                             flops=ntt_flops(task.c, task.ce - task.cs)):
                if state.stages is not None and task.ce > task.cs:
                    staged = state.stages.stage2_panel(task, task.cols)
                    task.result = protocol.encode_scalar_matrix(
                        staged.reshape(16,
                                       staged.shape[1] * staged.shape[2]))
                else:
                    out = []
                    for local, k1 in enumerate(range(task.cs, task.ce)):
                        row = protocol.matrix_to_ints(task.cols[:, local, :])
                        out.extend(_stage2_row(state.backend, domain_c,
                                               task, k1, row))
                    # reply rides the bulk codec (wire-identical path)
                    task.result = protocol.encode_scalar_matrix(
                        protocol.ints_to_matrix(out))
            state.observe_kernel("fft2", time.perf_counter() - t0,
                                 flops=ntt_flops(task.c,
                                                 task.ce - task.cs))
            if task.result and _sdc_due(state, protocol.FFT2):
                # SDC in the computed panel: one element perturbed IN the
                # cached buffer — retries and the integrity partials all
                # see the same corrupted result, like a real bad chip
                v = (protocol.decode_scalar(task.result) + 1) % R_MOD
                task.result = protocol.encode_scalar(v) \
                    + task.result[protocol.FR_BYTES:]
            task.done_at = time.monotonic()
        if check_point is not None and task.result \
                and (task.keep_raw or task.re <= task.rs):
            # integrity piggyback: (input-side, output-side) partial
            # power sums at the dispatcher's random point, computed from
            # the very buffers the data plane serves (O(n/k) host muls).
            # A task whose FFT_INIT did not announce the plane (mixed-
            # version fleet) answers plain — a zero input-side claim
            # over rows we dropped would read as a false SDC verdict.
            a, b = _fft2_partials(task, check_point)
            conn.send(protocol.OK,
                      protocol.encode_fft2_partials(a, b, task.result))
        else:
            conn.send(protocol.OK, task.result)
    elif tag == protocol.EVAL:
        # distributed partial evaluation (round 4 of the fleet prove):
        # sum_i c_i * point^i over the shipped coefficient chunk — the
        # dispatcher scales by point^start and folds across workers;
        # duplicate-executed chunks cross-check workers for SDC
        point, chunk = protocol.decode_eval_request(payload)
        with tracer.span("eval", n=len(chunk)):
            val = state.backend.eval_h(state.backend.lift(chunk), point)
        if _sdc_due(state, protocol.EVAL):
            val = (val + 1) % R_MOD
        conn.send(protocol.OK, protocol.encode_scalar(val))
    elif tag == protocol.STATS:
        import json as _json
        with state.lock:
            snap = dict(state.counters)
        conn.send(protocol.OK, _json.dumps(snap).encode())
    elif tag == protocol.HEALTH:
        # the liveness/re-admission probe (runtime/health.py): cheap,
        # lock-scoped snapshot — MUST stay fast even mid-FFT, a probe
        # that queues behind a kernel defeats the breaker's fast-fail
        import json as _json
        with state.lock:
            snap = {
                "uptime_s": round(time.monotonic() - state.started, 3),
                "served": sum(state.counters.values()),
                "fft_tasks": len(state.fft_tasks),
                "base_sets": sorted(state.base_sets),
                "backend": getattr(state.backend, "name", "?"),
                # wall-clock sample: the dispatcher brackets the probe
                # with its own clock and estimates this worker's offset
                # as now - (t_send + t_recv)/2, NTP-style — how merged
                # trace timestamps get onto one timeline
                "now": time.time(),
                "traces": len(state.traces),
                "epoch": state.epoch,
                # result-integrity chaos visibility: how many computed
                # results this worker's data plane has corrupted (always
                # 0 outside DPT_FAULTS soaks)
                "sdc_injected": state.sdc_injected,
                # warm-rejoin stats (set once after a --join worker
                # finishes its peer sync): the supervisor/operator's
                # evidence that a respawn came up warm
                "warm": state.warm,
            }
        conn.send(protocol.OK, _json.dumps(snap).encode())
    elif tag == protocol.ROSTER:
        # membership push: adopt the epoch table iff it is NEWER (an
        # out-of-order push is a no-op — epochs only move forward), and
        # drop every cached peer stream: indices are stable but a rejoin
        # means the old socket to that index is dead
        import json as _json
        req = protocol.decode_json(payload)
        new_epoch = int(req.get("epoch", 0))
        adopted = False
        with state.lock:
            if new_epoch > state.epoch:
                state.epoch = new_epoch
                state.config = NetworkConfig(req.get("workers", []))
                adopted = True
        if adopted:
            with state.peer_lock:
                stale = list(state.peers)
            for p in stale:
                state.drop_peer(p)
        conn.send(protocol.OK,
                  _json.dumps({"epoch": state.epoch,
                               "adopted": adopted}).encode())
    elif tag == protocol.STORE_LIST:
        from ..store import remote as store_remote
        store_remote.serve_list(
            state.store, payload, conn,
            no_store_reason="no store on this worker (--store)")
    elif tag == protocol.METRICS_FETCH:
        # the fleet-scrape surface (obs/fleet.py): this worker's FULL
        # structured registry — served counters, kernel latency
        # histograms, live gflops/MFU gauges — plus identity fields, one
        # JSON blob. Old dispatchers never send this; old workers answer
        # ERR "unknown tag" and the scraper degrades to snapshot=None.
        import json as _json
        snap = state.metrics.snapshot()
        with state.lock:
            snap.update({
                "index": state.me,
                "epoch": state.epoch,
                "backend": getattr(state.backend, "name", "?"),
                "uptime_s": round(time.monotonic() - state.started, 3),
                "sdc_injected": state.sdc_injected,
                "fft_tasks": len(state.fft_tasks),
                "base_sets": len(state.base_sets),
                "traces": len(state.traces),
                "log_seq": olog.buffer().seq,
            })
        conn.send(protocol.OK, _json.dumps(snap).encode())
    elif tag == protocol.LOG_FETCH:
        # structured-log ring fetch (obs/log.py): optionally filtered to
        # one trace id (the dispatcher's collect_trace merge) or tailed
        # via since_seq (the console). Reads never clear the ring.
        import json as _json
        req = protocol.decode_json(payload)
        out = olog.fetch(trace_id=req.get("trace_id"),
                         since_seq=int(req.get("since_seq") or 0),
                         limit=req.get("limit"))
        conn.send(protocol.OK, _json.dumps(out).encode())
    elif tag == protocol.PROFILE:
        # on-demand capture (obs/profiling.py): jax.profiler xplane on
        # jax backends, all-thread Python stack sampler otherwise. The
        # capture blocks only THIS connection thread for the window —
        # kernel serving on other connections continues (and is exactly
        # what the sampler sees). Reply is header+blob like STORE_FETCH.
        req = protocol.decode_json(payload)
        meta, blob = profiling.capture(
            duration_ms=req.get("duration_ms"),
            kind=req.get("kind", "auto"),
            backend_name=getattr(state.backend, "name", None))
        meta["worker"] = state.me
        state.metrics.inc("profiles_captured")
        olog.emit("worker", "profile_captured", worker=state.me,
                  format=meta.get("format"), bytes=len(blob))
        conn.send(protocol.OK, protocol.encode_result(meta, blob))
    elif tag == protocol.TRACE_DUMP:
        # fetch-and-forget one trace's worker-side spans: the dispatcher
        # stitches them (offset-corrected) into the merged per-job
        # timeline; an unknown id answers {} (the worker may have been
        # restarted, or LRU-dropped an abandoned trace)
        import json as _json
        req = protocol.decode_json(payload)
        tr = state.pop_trace(req.get("trace_id"))
        conn.send(protocol.OK,
                  _json.dumps(tr.dump() if tr is not None else {}).encode())
    elif tag == protocol.STORE_FETCH:
        # peer-serving plane: a replacement worker on a fresh host pulls
        # SRS/pk/checkpoint blobs from us instead of rebuilding them
        from ..store import remote as store_remote
        store_remote.serve_fetch(
            state.store, payload, conn,
            no_store_reason="no store on this worker (--store)")
    elif tag == protocol.SHUTDOWN:
        conn.send(protocol.OK)
        return False
    else:
        conn.send(protocol.ERR, b"unknown tag")
    return None


def _make_store(store_dir):
    if store_dir is None:
        return None
    from ..store import ArtifactStore, set_jax_cache_env
    # synced/persisted compiled executables live under the store: point
    # a not-yet-imported jax backend's persistent compile cache there so
    # warm-rejoined cache entries actually get hit
    set_jax_cache_env(store_dir)
    return ArtifactStore(store_dir)


def _run_server(listener, state, ready_event=None):
    """Accept loop until a SHUTDOWN frame lands."""
    if ready_event is not None:
        ready_event.set()
    stop = threading.Event()

    def run_conn(conn):
        if not handle(conn, state):
            stop.set()
        conn.close()

    def accept_loop():
        while True:
            conn = listener.accept()
            if conn.fd < 0:
                return
            threading.Thread(target=run_conn, args=(conn,), daemon=True).start()

    threading.Thread(target=accept_loop, daemon=True).start()
    stop.wait()  # SHUTDOWN flips this; daemon threads die with the process
    listener.close()


def serve(index, config, backend_name="python", ready_event=None,
          store_dir=None):
    """Static-fleet daemon: index + config fixed at startup (epoch 0)."""
    host, port = config.workers[index]
    listener = native.Listener(host, port)
    # store BEFORE backend: _make_store points the jax compile cache
    # under the store via env that field_jax reads at import — building
    # the backend first would configure the cache elsewhere and leave
    # this worker with zero jaxcache:* entries to serve warm-rejoiners
    store = _make_store(store_dir)
    olog.configure_from_env(proc=f"worker/{index}")
    state = WorkerState(_make_backend(backend_name), config=config, me=index,
                        store=store)
    olog.emit("worker", "serving", worker=index, backend=backend_name,
              port=port, store=store_dir is not None)
    _run_server(listener, state, ready_event=ready_event)


def serve_joined(join_addr, listen_addr=("127.0.0.1", 0),
                 backend_name="python", store_dir=None, ready_event=None):
    """Dynamic-membership daemon (`--join host:port`): bind first (port 0
    = ephemeral), announce to the membership server, adopt the returned
    index + epoch + roster, serve — then warm-rejoin in the background:
    pull bucket-key artifacts and jax persistent-compile-cache entries
    from the roster's store-serving peers (STORE_FETCH/STORE_LIST), so a
    replacement worker reaches first-kernel-launch without rebuilding
    keys or recompiling stages. The worker is schedulable from the JOIN
    ack; the sync only ACCELERATES first touches, it gates nothing."""
    from . import membership
    host, port = listen_addr
    listener = native.Listener(host, port)
    port = port or native.listener_port(listener)
    reply = membership.join_fleet(join_addr[0], join_addr[1], host, port,
                                  store=store_dir is not None)
    store = _make_store(store_dir)
    olog.configure_from_env(proc=f"worker/{reply['index']}")
    state = WorkerState(_make_backend(backend_name),
                        config=NetworkConfig(reply["workers"]),
                        me=int(reply["index"]), store=store,
                        epoch=int(reply["epoch"]))
    olog.emit("worker", "joined", worker=state.me, backend=backend_name,
              port=port, epoch=state.epoch)

    def warm_sync():
        from ..store import remote as store_remote
        me = f"{host}:{port}"
        peers = [tuple(a.rsplit(":", 1)) for a in reply.get("stores", [])
                 if a != me]
        stats = {"warm_rejoin_s": 0.0, "artifacts": 0, "jax_cache_files": 0,
                 "peers": 0}
        if store is not None and peers:
            stats = store_remote.warm_sync(
                store, [(h, int(p)) for h, p in peers])
        state.warm = stats
        olog.emit("worker", "warm_rejoin", worker=state.me, **{
            k: v for k, v in stats.items()
            if isinstance(v, (int, float, str, bool))})
        if store is not None:
            # storeless joiners have nothing to sync: reporting ready
            # would count a zero-length "warm rejoin" and fill the
            # warm_rejoin_s histogram with meaningless 0.0 samples
            membership.report_ready(join_addr[0], join_addr[1], host,
                                    port, stats)

    threading.Thread(target=warm_sync, daemon=True).start()
    _run_server(listener, state, ready_event=ready_event)


def _parse_hostport(s):
    h, _, p = s.rpartition(":")
    return h or "127.0.0.1", int(p)


def main(argv):
    backend = "python"
    if "--backend" in argv:
        backend = argv[argv.index("--backend") + 1]
    store_dir = None
    if "--store" in argv:
        store_dir = argv[argv.index("--store") + 1]
    if "--join" in argv:
        join_addr = _parse_hostport(argv[argv.index("--join") + 1])
        listen_addr = ("127.0.0.1", 0)
        if "--listen" in argv:
            listen_addr = _parse_hostport(argv[argv.index("--listen") + 1])
        serve_joined(join_addr, listen_addr, backend, store_dir=store_dir)
        return
    index = int(argv[0])
    cfg_path = argv[1] if len(argv) > 1 else "config/network.json"
    serve(index, NetworkConfig.load(cfg_path), backend, store_dir=store_dir)


if __name__ == "__main__":
    main(sys.argv[1:])
