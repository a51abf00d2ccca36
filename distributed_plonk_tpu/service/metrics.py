"""Service observability: counters, gauges, latency histograms, exposition.

The structured upgrade of the worker plane's raw `{tag: count}` STATS
counters (runtime/worker.py) for the serving layer: one `Metrics` registry
aggregates queue depth, wait/run latencies, per-prover-round times (fed
from trace.Tracer totals), retries/kills, and throughput, snapshots to one
JSON-able dict for the METRICS wire tag, and renders the Prometheus text
exposition (`to_prometheus`) that serve.py --obs-port serves at /metrics.

Histograms keep a bounded reservoir (uniform sampling past the cap, so
long runs stay O(1) memory) and report count/sum/min/mean/percentiles
computed from the reservoir at snapshot time; `samples` says how many
reservoir values back the percentile estimates (past the cap they are
estimates over a uniform sample, not exact order statistics).

METRIC GLOSSARY — every counter/histogram name the code records must be
documented here; analysis/lint.py's OBS01 lint enforces it (a `_*`
suffix documents a name family). Scoped registries (Metrics.scoped)
publish under their prefix: the artifact store's entries appear as
store_<name>.

Job lifecycle (service/server.py, service/pool.py, service/queue.py):
    jobs_submitted / jobs_accepted / jobs_rejected   admission outcomes
    jobs_completed / jobs_failed / jobs_timeout      terminal outcomes
    job_retries / job_attempt_errors                 retry-loop activity
    jobs_evicted                                     finished jobs aged out
                                                     of the job table
    workers_spawned / workers_killed / kill_requests  pool slot lifecycle
                                                     + fault injection
    warmups                                          WARMUP requests served
    job_wait / job_run (histograms)                  submit->start and
                                                     start->done seconds
    prove_round/* (histograms)                       per-round prover
                                                     latency (trace totals:
                                                     round1..5, and
                                                     roundN_finalize under
                                                     the round pipeline)
    prove_phase/* (histograms)                       a job's seconds on its
                                                     worker OUTSIDE the
                                                     rounds, by phase (STATUS
                                                     `phases`): circuit_build,
                                                     guard_open,
                                                     checkpoint_save,
                                                     pipeline_wait, serialize,
                                                     self_verify, journal_done,
                                                     trace_store, and
                                                     unaccounted (run_s less
                                                     every span above and the
                                                     rounds)
    queue_depth / queue_high_water (gauges)          admission backlog
    circuit_builds                                   `merkle` circuits built
                                                     for a job by a pool
                                                     worker (circuits/
                                                     merkle_witness.py)
    circuit_template_hits                            of those, builds that
                                                     found their shape's
                                                     structure template (a
                                                     miss runs the plain
                                                     builder once and keeps
                                                     its structure)
    circuit_build_permutations                       Rescue permutations the
                                                     witness-only builds
                                                     computed: one per
                                                     distinct tree node of a
                                                     job (the plain build of
                                                     a miss is not in it)
    circuit_build_permutations_native                of those, the ones whose
                                                     trace the native code
                                                     computed (runtime/
                                                     native.RescueTrace): all
                                                     of them

Scheduler + shape buckets (service/scheduler.py):
    batches_dispatched / batch_size                  shape-batch activity
    dispatch_errors                                  pool handoff failures

Placement + cross-job batched proving (service/placement.py, pool.py):
    placement_*                                      decisions per popped
                                                     shape batch: _batch
                                                     (data-parallel cross-
                                                     job prove), _mesh
                                                     (sharded submesh
                                                     prove), _pool (per-job
                                                     dispatch)
    batch_proves                                     batched prove_many
                                                     attempts launched
    batch_jobs                                       jobs proved inside
                                                     batched attempts
    batch_jobs_per_launch (histogram)                achieved jobs per
                                                     batched attempt
    batch_member_kills                               batch members killed
                                                     mid-prove (resumed
                                                     alone; the others
                                                     finished unaffected)
    submesh_leases                                   device leases granted
                                                     (big sharded proves +
                                                     opportunistic batch
                                                     leases)
    submesh_devices_free (gauge)                     unleased devices
    mesh_ntt_calls / mesh_ntt_sharded                NTTs of mesh-placed
                                                     jobs, and those that
                                                     took the 4-step plan
                                                     (the rest fell back to
                                                     the replicated kernel)
    mesh_msm_chunks                                  shard-mapped MSM chunk
                                                     launches
    mesh_all_to_all_bytes / mesh_all_gather_bytes    bytes the NTT's
                                                     all_to_all and the
                                                     MSM's all_gather move
                                                     between chips, by the
                                                     shapes of the calls
    bucket_hits / bucket_misses / bucket_disk_hits   key-cache tiers
    bucket_peer_hits                                 keys fetched from a
                                                     warm STORE_FETCH peer
    bucket_latch_waits                               callers that waited on
                                                     another thread's
                                                     in-flight key setup
    bucket_mem_evictions / buckets_resident (gauge)  memory-tier LRU
    bucket_build / bucket_disk_load (histograms)     tier latencies
    bucket_build_srs / bucket_build_preprocess       a build's parts: the
      / bucket_build_store (histograms)              SRS walk, the 18 iFFTs
                                                     and commits, the store
                                                     write
    bucket_builds_srs_device                         builds whose SRS came
                                                     from the device walk
    bucket_build_errors                              key builds that failed
    store_write_errors                               best-effort artifact
                                                     writes that failed

Round-pipelined proving (prover.PipelinedProver via pool._run_pipeline):
    pipelined_proves                         pipelined attempts launched
                                             (one per coalesced window)
    pipelined_jobs                           jobs proved inside pipelined
                                             attempts
    pipeline_depth (gauge)                   members in flight at the last
                                             observed stage boundary
    pipeline_depth_achieved (histogram)      in-flight depth sampled at
                                             every stage finalize (the
                                             fill the pipeline actually
                                             achieved vs DPT_PIPELINE_DEPTH)
    pipeline_stage_wait_s (histogram)        driver wait for a member's
                                             oldest ready stage (also per
                                             round: pipeline_stage_wait_s/
                                             round<N>)

Device ledger, the fed/unfed account of the chip (trace.DeviceLedger, owned
by the backend, read at every snapshot through Metrics.add_source and
charged up to that instant; float seconds, cumulative since the backend was
made; absent on a backend with no ledger, the host oracle. A leased mesh
backend opens its rounds on the pool backend's ledger, parallel/
mesh_backend.py::MeshBackend.attach, so there is one account a service):
    phase_clock_s                            all time on the ledger's clock
    device_unfed_s                           seconds in which no round of
                                             ours was outstanding on the
                                             device (nothing dispatched and
                                             not yet complete): the chip had
                                             nothing to run
    device_unfed_s/*                         the same seconds by the phase
                                             the worker whose dispatch ended
                                             the gap was in during it
                                             (worker_idle: waiting for a
                                             job; other: between spans;
                                             circuit_build, guard_open,
                                             round<N>, round<N>_finalize,
                                             checkpoint_save, serialize,
                                             self_verify, journal_done,
                                             trace_store); they sum to
                                             device_unfed_s

Artifact store, scoped `store_*` (store/artifacts.py, store/remote.py):
    store_hits / store_misses / store_evictions      blob cache activity
    store_corrupt                                    integrity failures on
                                                     read (entry deleted,
                                                     rebuilt on demand)
    store_entries / store_bytes (gauges)             resident inventory
    store_put_bytes                                  bytes written
    store_jax_cache_bytes / store_jax_cache_evictions  compile-cache GC
    store_fetch_served / store_fetch_misses          STORE_FETCH server side
    store_fetch_bytes                                blob bytes served

Failure-observability vocabulary (one registry can be handed to the
runtime Dispatcher AND the service pool, so a whole deployment's fault
story reads off one snapshot):
    fleet_reconnects / fleet_backoff_waits   reconnect loop activity
    fleet_backoff (histogram)                seconds slept in backoff
    fleet_breaker_opens / fleet_readmissions  circuit-breaker transitions
    fleet_range_adoptions                    MSM ranges moved off a dead
                                             worker (runtime dispatcher)
    fleet_fft_replans / fleet_fft_degraded   sharded-FFT recovery events
    checkpoint_saves / checkpoint_resumes    prover round snapshots and
                                             resumed (not restarted)
                                             attempts (service pool)
    faults_injected_* / faults_ckpt_corrupted  chaos-injection activity
                                             (runtime/faults.py)

Membership & supervision vocabulary (runtime/membership.py,
runtime/supervisor.py — the self-healing fleet):
    fleet_size (gauge)                       current member count (slots,
                                             incl. breaker-open ones)
    membership_epoch (gauge)                 roster version; bumps on
                                             every join/rejoin/leave
    membership_joins / membership_rejoins    new members admitted vs
                                             known addresses re-admitted
                                             in place (supervisor
                                             respawns land here)
    membership_leaves                        members declared permanently
                                             gone (flap cap, operator)
    roster_pushes                            epoch tables pushed to live
                                             workers after a change
    warm_rejoins                             JOIN phase=ready reports
                                             carrying warm-sync stats
    warm_rejoin_s (histogram)                seconds a joiner spent
                                             pulling bucket/compile-cache
                                             artifacts from roster peers
    worker_respawns                          supervisor restarts of dead
                                             or wedged worker processes
    worker_flap_capped                       slots given up on (flap_cap
                                             respawns inside the window)
    supervisor_probe_misses                  liveness probes a supervised
                                             worker failed to answer
    supervised_workers (gauge)               slots under supervision
    bucket_peers_added / bucket_peers_removed  store-serving members
                                             auto-registered as key-fetch
                                             peers / dropped on LEAVE
                                             (attach_membership)
    store_list_served                        STORE_LIST enumerations
                                             answered (warm-rejoin scans)

Durability vocabulary (service/journal.py + the restart-recovery path):
    journal_appends / journal_replays        records written / replayed
                                             at open
    journal_torn_records / journal_compactions  damaged-tail truncations
                                             and log rewrites
    jobs_recovered / jobs_recovered_finished  re-enqueued in-flight jobs
                                             and artifact-served DONE
                                             jobs after a restart
    jobs_shed                                TTL/deadline load-shed
                                             verdicts (journaled)
    dedup_hits                               duplicate job_key SUBMITs
                                             answered from the original
    drain_started / drain_clean / drain_forced  graceful-drain outcomes
    jobs_drain_parked                        in-flight jobs checkpointed
                                             + parked by a forced drain
    proof_artifacts_lost                     DONE records whose proof
                                             artifact was evicted (job
                                             re-proved, same bytes)

Result-integrity vocabulary (runtime/integrity.py, runtime/dispatcher.py,
runtime/health.py, service/pool.py — the SDC defense):
    integrity_checks                         algebraic phase checks run
                                             (FFT/NTT Schwartz-Zippel,
                                             MSM group-law sanity, eval
                                             dup sampling decisions)
    integrity_failures                       checks that caught a WRONG
                                             (well-formed) answer
    integrity_msm_dups                       MSM ranges duplicate-
                                             executed on a second worker
                                             (rate DPT_INTEGRITY_MSM_DUP)
    integrity_eval_dups                      evaluation chunks duplicate-
                                             executed (same rate knob)
    workers_quarantined                      workers marked SUSPECT by an
                                             attributed integrity failure
                                             (sticky breaker; LEAVEd when
                                             membership is armed)
    integrity_challenges                     known-answer challenge
                                             proves run against (re-)
                                             joining quarantined
                                             addresses
    integrity_challenges_failed              challenges the worker
                                             answered WRONG (it stays
                                             quarantined)
    self_verify_checks                       verify-before-serve pairing
                                             checks run (DPT_SELF_VERIFY)
    self_verify_failures                     finished proofs that failed
                                             the pairing verifier
    self_verify_s (histogram)                verify-before-serve latency
    proofs_blocked                           proofs withheld from the
                                             journal/client by a failed
                                             self-verify (job re-proved)

Tracing vocabulary (trace.py, service/pool.py, server.py --obs-port):
    trace_spans_recorded                     spans folded into finished
                                             jobs' merged timelines
    traces_stored                            trace:<job_id> artifacts
                                             written to the store
    obs_http_requests                        /metrics /healthz /trace
                                             requests served
    kernel_*_gflops / mfu_*_pct (gauges)     live per-stage throughput
                                             and model-flops MFU from
                                             events that carry `flops`:
                                             on the device backend the
                                             device/round<N> and kernels/
                                             <commit> events, whose dur_s
                                             is device-true (completion
                                             stamps, never the enqueue);
                                             on a sync backend the kernel
                                             spans, which time the
                                             compute (peak set by the
                                             DEVICE_PEAKS entry of the
                                             backend's chip; none for an
                                             unknown device)

Fleet observability vocabulary (obs/log.py, obs/fleet.py,
runtime/worker.py METRICS_FETCH/LOG_FETCH/PROFILE — the one-pane plane,
ISSUE 15):
    served_*                                 worker-side request counters
                                             per wire tag (served_msm,
                                             served_fft2, ...): the
                                             structured twin of the raw
                                             STATS dict, scrapeable over
                                             METRICS_FETCH
    worker_*_s (histograms)                  worker-side kernel latency
                                             per stage (worker_msm_s,
                                             worker_ntt_s, worker_fft1_s,
                                             worker_fft2_s)
    serve_errors                             worker request frames that
                                             drew an ERR reply (malformed
                                             payload / backend failure)
    log_events                               structured log events
                                             recorded into the ring
    log_dropped                              ring-capacity overwrites:
                                             every oldest-event eviction
                                             once the ring is full (a
                                             fetch may or may not have
                                             read it first — high values
                                             mean raise DPT_LOG_CAP or
                                             tail more often)
    fleet_scrapes                            METRICS_FETCH scrape cycles
                                             completed by the aggregator
    fleet_scrape_errors                      scrape cycles that failed
                                             whole (fan-out error)
    fleet_width / fleet_reachable (gauges)   roster size vs members that
                                             answered the last scrape
    fleet_suspects / fleet_breakers_open (gauges)  quarantined members /
                                             open breakers at last scrape
    fleet_served_total / fleet_serve_errors_total (gauges)  fleet-summed
                                             request counters from the
                                             last scrape
    profiles_captured                        PROFILE captures served by
                                             this worker
    profiles_stored                          profile:<id> artifacts
                                             persisted by the service
    profile_errors                           captures that failed or came
                                             back empty/unsupported

Autoscaling & SLO-class vocabulary (service/autoscale.py,
service/queue.py, service/pool.py, runtime/supervisor.py — the
closed-loop controller, ISSUE 16):
    autoscale_*                              controller activity:
                                             autoscale_ticks (control-
                                             loop cycles), autoscale_
                                             decisions (recorded
                                             verdicts), autoscale_scale_
                                             ups / autoscale_scale_downs
                                             (worker-count moves
                                             APPLIED), autoscale_lease_
                                             resizes (submesh capacity
                                             moves), autoscale_sheds
                                             (pressure evictions),
                                             autoscale_sensor_errors;
                                             gauges autoscale_workers /
                                             autoscale_target_workers /
                                             autoscale_queue_<class>
                                             (per-class queued depth at
                                             last tick)
    slo_*                                    per-class serving outcomes:
                                             slo_roundtrip/<class>
                                             (histogram: submit -> done
                                             seconds per SLO class; the
                                             standard-class p95_s is the
                                             controller's latency
                                             sensor), slo_sheds_<class>
                                             (terminal SHED verdicts per
                                             class), slo_preempt_sheds
                                             (lower-class jobs evicted
                                             by a full queue admitting a
                                             higher class)
    worker_retires                           supervised workers retired
                                             gracefully by scale-down:
                                             drain -> membership LEAVE
                                             -> SIGTERM (SIGKILL only
                                             past DPT_SUP_RETIRE_
                                             TIMEOUT_S); a retire is
                                             never a flap and never
                                             respawns

Circuit zoo + proof aggregation vocabulary (circuits/, aggregate.py,
service/server.py AGGREGATE path — ISSUE 17):
    circuit_kind_*                           jobs served to DONE per
                                             circuit kind (circuit_kind_
                                             toy, circuit_kind_range,
                                             ...): the zoo mix as the
                                             server actually proved it
    aggregates_built                         batch-KZG aggregates built
                                             (self-verified + journaled)
    aggregate_members                        constituent proofs folded
                                             into built aggregates
                                             (members per build summed)
    aggregate_verify_s (histogram)           server-side fold-then-one-
                                             pairing-check latency per
                                             built aggregate
    aggregate_verify_failures                aggregate builds REJECTED by
                                             the server's own verify gate
                                             (nothing journaled/served)
    aggregates_recovered                     aggregate artifacts restored
                                             from the journal after a
                                             restart
    aggregate_artifacts_lost                 journaled aggregates whose
                                             artifact bytes were gone at
                                             recovery (store eviction)
"""

import math
import os
import random
import re
import threading
import time

_RESERVOIR = 2048

# Published peaks of one chip, keyed by the `device_kind` string jax
# reports for it. The mfu_*_pct gauges divide by bf16_tflops; a kind that
# is not in the table publishes no such gauge (an unknown chip, or the
# host oracle, has no peak to be a share of).
DEVICE_PEAKS = {
    "TPU v5 lite": {"bf16_tflops": 197.0, "hbm_gbps": 819.0,
                    "source": 'Google Cloud documentation, "TPU v5e"'},
}


class Histogram:
    def __init__(self):
        self.count = 0
        self.sum = 0.0
        self.min = None
        self.max = None
        self._samples = []
        self._rng = random.Random(0xC0FFEE)

    def record(self, v):
        v = float(v)
        self.count += 1
        self.sum += v
        self.min = v if self.min is None else min(self.min, v)
        self.max = v if self.max is None else max(self.max, v)
        if len(self._samples) < _RESERVOIR:
            self._samples.append(v)
        else:
            i = self._rng.randrange(self.count)
            if i < _RESERVOIR:
                self._samples[i] = v

    def snapshot(self):
        if not self.count:
            return {"count": 0}
        s = sorted(self._samples)

        def pct(p):
            # nearest-rank percentile over the reservoir: ceil(p*k)-1,
            # clamped for tiny counts (the old int(p*k) indexed the MAX
            # for any p >= 1-1/k — e.g. a 2-sample p50 returned the max)
            return s[max(0, min(len(s) - 1, math.ceil(p * len(s)) - 1))]

        return {
            "count": self.count,
            # percentiles below are computed over `samples` retained
            # reservoir values, not all `count` observations — estimates,
            # not exact order statistics, once samples < count
            "samples": len(s),
            "sum_s": round(self.sum, 6),
            "min_s": round(self.min, 6),
            "mean_s": round(self.sum / self.count, 6),
            "p50_s": round(pct(0.50), 6),
            "p90_s": round(pct(0.90), 6),
            "p95_s": round(pct(0.95), 6),
            "p99_s": round(pct(0.99), 6),
            "max_s": round(self.max, 6),
        }


def _prom_name(name):
    """Metric name -> Prometheus-legal name under the dpt_ namespace."""
    return "dpt_" + re.sub(r"[^a-zA-Z0-9_]", "_", name)


class Metrics:
    def __init__(self):
        self._lock = threading.Lock()
        self._counters = {}
        self._gauges = {}
        self._hists = {}
        self._sources = []
        self.started_at = time.monotonic()

    def inc(self, name, by=1):
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + by

    def gauge(self, name, value):
        with self._lock:
            self._gauges[name] = value

    def observe(self, name, seconds):
        with self._lock:
            h = self._hists.get(name)
            if h is None:
                h = self._hists[name] = Histogram()
            h.record(seconds)

    def add_source(self, source):
        """Register an account that keeps its own cumulative counters and
        is read at snapshot time: `source.counters()` -> {name: value},
        charged up to the instant of the call (trace.DeviceLedger: the
        fed/unfed account of the device has an interval open at any
        moment, so it is asked, not told). Idempotent per source."""
        with self._lock:
            if not any(s is source for s in self._sources):
                self._sources.append(source)

    def scoped(self, prefix):
        """A view of this registry that prefixes every metric name with
        `prefix_` — how subsystems with their own metric vocabulary (the
        artifact store's hits/misses/bytes/evictions) publish into the
        one service registry without hardcoding its namespace."""
        return _Scoped(self, prefix)

    def observe_rounds(self, totals, phases=None):
        """Fold a prove's round spans (trace.Tracer.totals(): round1..
        round5, roundN_finalize) into prove_round/<name> histograms and
        its phases (STATUS `phases`: circuit_build, checkpoint_save, ...)
        into prove_phase/<name>."""
        for span, dur in totals.items():
            self.observe(f"prove_round/{span}", dur)
        for span, dur in (phases or {}).items():
            self.observe(f"prove_phase/{span}", dur)

    def observe_kernels(self, events, device_kind=None):
        """Fold kernel spans carrying `flops` attrs (trace.Tracer events
        of a finished prove — see prover.py / trace.ntt_flops) into live
        per-stage gauges: kernel_<stage>_gflops (model-flops throughput)
        and, when `device_kind` (the proving backend's
        device_info()["device_kind"]) is in DEVICE_PEAKS,
        mfu_<stage>_pct against that chip's published bf16 peak."""
        peak = DEVICE_PEAKS.get(device_kind, {}).get("bf16_tflops", 0.0) \
            * 1e12
        for ev in events:
            flops = ev.get("flops")
            dur = ev.get("dur_s")
            if not flops or not dur:
                continue
            stage = re.sub(r"[^a-zA-Z0-9_]", "_",
                           ev["span"].rsplit("/", 1)[-1])
            self.gauge(f"kernel_{stage}_gflops",
                       round(flops / dur / 1e9, 3))
            if peak > 0:
                self.gauge(f"mfu_{stage}_pct",
                           round(100.0 * flops / (dur * peak), 4))

    def snapshot(self):
        with self._lock:
            sources = list(self._sources)
        # sources first, outside our lock (each has its own): they charge
        # their open interval, so the reading is true to this instant
        pulled = {}
        for source in sources:
            pulled.update(source.counters())
        with self._lock:
            done = self._counters.get("jobs_completed", 0)
            uptime = time.monotonic() - self.started_at
            return {
                "uptime_s": round(uptime, 3),
                "counters": dict(self._counters, **pulled),
                "gauges": dict(self._gauges),
                # analysis: ok(Histogram.snapshot is a lockless data object)
                "histograms": {k: h.snapshot()
                               for k, h in sorted(self._hists.items())},
                "throughput_jobs_per_s": round(done / uptime, 6) if uptime else 0.0,
            }

    def to_prometheus(self, extra_gauges=None):
        """Prometheus text exposition (format version 0.0.4) of the
        current snapshot: counters as `dpt_<name>_total`, gauges as
        `dpt_<name>`, histograms as summaries (`{quantile=...}` series
        from the reservoir percentiles, plus _sum/_count and a _samples
        gauge for the reservoir size). `extra_gauges` lets the caller
        splice in point-in-time values (queue depth) the registry does
        not own."""
        snap = self.snapshot()
        gauges = dict(snap["gauges"])
        if extra_gauges:
            gauges.update(extra_gauges)
        gauges["uptime_s"] = snap["uptime_s"]
        gauges["throughput_jobs_per_s"] = snap["throughput_jobs_per_s"]
        lines = []
        for name, v in sorted(snap["counters"].items()):
            n = _prom_name(name) + "_total"
            lines.append(f"# TYPE {n} counter")
            lines.append(f"{n} {v}")
        for name, v in sorted(gauges.items()):
            if not isinstance(v, (int, float)) or isinstance(v, bool):
                continue  # non-numeric gauge (labels) — JSON snapshot only
            n = _prom_name(name)
            lines.append(f"# TYPE {n} gauge")
            lines.append(f"{n} {v}")
        for name, h in sorted(snap["histograms"].items()):
            if not h.get("count"):
                continue
            n = _prom_name(name) + "_seconds"
            lines.append(f"# TYPE {n} summary")
            for q, key in (("0.5", "p50_s"), ("0.9", "p90_s"),
                           ("0.95", "p95_s"), ("0.99", "p99_s")):
                lines.append(f'{n}{{quantile="{q}"}} {h[key]}')
            lines.append(f"{n}_sum {h['sum_s']}")
            lines.append(f"{n}_count {h['count']}")
            lines.append(f"# TYPE {n}_samples gauge")
            lines.append(f"{n}_samples {h['samples']}")
        return "\n".join(lines) + "\n"


class _Scoped:
    """Name-prefixing adapter over a Metrics registry (see Metrics.scoped)."""

    def __init__(self, base, prefix):
        self._base = base
        self._prefix = prefix

    def inc(self, name, by=1):
        self._base.inc(f"{self._prefix}_{name}", by)

    def gauge(self, name, value):
        self._base.gauge(f"{self._prefix}_{name}", value)

    def observe(self, name, seconds):
        self._base.observe(f"{self._prefix}_{name}", seconds)
