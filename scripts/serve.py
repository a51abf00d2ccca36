#!/usr/bin/env python3
"""Run the proof service daemon.

    python scripts/serve.py --port 9555 --workers 2 [--backend jax] \
        [--queue-depth 64] [--max-batch 8] [--retries 2] [--timeout 300] \
        [--journal-dir /var/dpt/journal] [--chaos] [--verify]

--backend is a deployment setting: `jax` (the default) proves on the
local accelerator through JaxBackend — one process per chip, so run one
daemon per chip and start nothing else that needs it from a process that
has imported jax; `python` is the pure-host oracle. A CPU run of the jax
backend is something the caller asks for (JAX_PLATFORMS=cpu, as the tests
and scripts/ci.sh do); the daemon pins no platform. The JAX compile cache
is where JAX_COMPILATION_CACHE_DIR says, else <checkout>/.jax_cache.

--journal-dir enables the crash-safe job journal: every submitted job
survives a crash or deploy restart (in-flight ones resume from their
checkpoints, finished ones serve from proof artifacts). SIGTERM/SIGINT
triggers a graceful drain — admission stops, in-flight jobs get up to
DPT_DRAIN_TIMEOUT_S (default 30) to finish, stragglers checkpoint and
park, the journal flushes, and the process exits 0; a later start on the
same --journal-dir picks every deferred job back up.

--chaos enables the KILL_WORKER fault-injection tag (scripts/loadgen.py
--kill uses it) and arms DPT_FAULTS-spec'd rules — including
journal-plane service kills (`DPT_FAULTS="kill:at=journal:tag=ROUND2"`
makes THIS PROCESS os._exit at exactly that journal occurrence; the
restart-recovery tests and loadgen --kill-service drive it). Never
enable it on a service you care about. --verify makes workers verify
each proof server-side before marking it done.
Prints one JSON line once listening, with the bound address and what the
backend runs on (backend, platform, device_kind, devices); SHUTDOWN tag
stops it.
"""

import argparse
import json
import os
import signal
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

DRAIN_TIMEOUT_S = float(os.environ.get("DPT_DRAIN_TIMEOUT_S", "30"))


def parse_peers(arg):
    """'host:port,host:port' -> [(host, port)], failing fast with a
    message that names the flag (a forgotten port otherwise surfaces as
    a bare int() traceback)."""
    peers = []
    for entry in arg.split(","):
        host, sep, port = entry.strip().rpartition(":")
        if not sep or not host or not port.isdigit():
            raise SystemExit(
                f"--store-peers: {entry.strip()!r} is not host:port")
        peers.append((host, int(port)))
    return peers


def validate_journal_dir(arg):
    """Fail fast, at flag-parse time, with a message that names the flag:
    a journal dir that can't actually take fsync'd appends must stop the
    daemon BEFORE it accepts jobs it cannot make durable (discovering it
    on the first SUBMIT would lose that job's durability silently)."""
    path = os.path.abspath(os.path.expanduser(arg))
    if os.path.exists(path) and not os.path.isdir(path):
        raise SystemExit(f"--journal-dir: {path!r} exists and is not a "
                         "directory")
    try:
        os.makedirs(path, exist_ok=True)
        probe = os.path.join(path, ".probe.%d" % os.getpid())
        with open(probe, "wb") as f:
            f.write(b"x")
            os.fsync(f.fileno())
        os.remove(probe)
    except OSError as e:
        raise SystemExit(f"--journal-dir: {path!r} is not writable "
                         f"({e.strerror or e})")
    return path


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=9555)
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--backend", choices=("jax", "python"), default="jax",
                    help="prover backend: jax = JaxBackend on the local "
                         "accelerator (default), python = host oracle")
    ap.add_argument("--queue-depth", type=int, default=64)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--retries", type=int, default=2)
    ap.add_argument("--timeout", type=float, default=None,
                    help="per-job wall-clock budget, seconds")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--journal-dir", default=None,
                    help="crash-safe job journal: jobs survive service "
                         "restarts (resume from checkpoints, finished "
                         "proofs served from artifacts); also the "
                         "SIGTERM graceful-drain surface")
    ap.add_argument("--store-dir", default=None,
                    help="artifact store root: persists SRS/keys across "
                         "restarts; warm it ahead of time with "
                         "scripts/warmup.py")
    ap.add_argument("--store-budget", type=int, default=None,
                    help="store byte budget (LRU eviction past it)")
    ap.add_argument("--bucket-cap", type=int, default=64,
                    help="max shape buckets resident in memory (LRU)")
    ap.add_argument("--store-peers", default=None,
                    help="comma-separated host:port peers speaking "
                         "STORE_FETCH: on a bucket miss, pull the key "
                         "blob from a warm peer (digest-verified) before "
                         "paying for a full build — a scaled-out replica "
                         "serves warm after one network copy")
    ap.add_argument("--log-dir", default=None,
                    help="structured-log JSONL sink (obs/log.py): every "
                         "shed/retry/quarantine verdict appends one "
                         "trace-correlated JSON line to "
                         "<dir>/serve-<pid>.jsonl; the env DPT_LOG_DIR "
                         "does the same for worker subprocesses")
    ap.add_argument("--obs-port", type=int, default=None,
                    help="observability HTTP port (0 = ephemeral): serves "
                         "/metrics (Prometheus text exposition incl. "
                         "per-round latency + MFU gauges), /healthz, and "
                         "/trace/<job_id> (the job's merged distributed "
                         "timeline as chrome://tracing JSON)")
    ap.add_argument("--chaos", action="store_true")
    ap.add_argument("--verify", action="store_true")
    ap.add_argument("--allow-remote-shutdown", action="store_true",
                    help="let any client's SHUTDOWN frame stop the daemon")
    args = ap.parse_args()

    journal_dir = None
    if args.journal_dir is not None:
        journal_dir = validate_journal_dir(args.journal_dir)

    from distributed_plonk_tpu.obs import log as olog
    from distributed_plonk_tpu.runtime.faults import FaultInjector
    from distributed_plonk_tpu.service import start_service
    from distributed_plonk_tpu.service.server import ObsServer

    log_path = None
    if args.log_dir is not None:
        log_path = olog.configure(log_dir=args.log_dir, proc="serve")
        if log_path is None:
            raise SystemExit(f"--log-dir: {args.log_dir!r} is not writable")

    faults = None
    if args.chaos:
        # journal-plane kills die for real: os._exit skips every atexit/
        # finally (the whole point — a crash leaves no goodbye), so the
        # restarted process sees exactly what a power cut would leave
        faults = FaultInjector.from_env(
            kill_cb=lambda _label: os._exit(1))

    svc, runtime = start_service(
        args.backend,
        host=args.host, port=args.port, prover_workers=args.workers,
        queue_depth=args.queue_depth, max_batch=args.max_batch,
        max_retries=args.retries, job_timeout_s=args.timeout,
        ckpt_dir=args.ckpt_dir, chaos=args.chaos,
        verify_on_complete=args.verify,
        allow_remote_shutdown=args.allow_remote_shutdown,
        store_dir=args.store_dir, store_byte_budget=args.store_budget,
        bucket_cap=args.bucket_cap, journal_dir=journal_dir,
        faults=faults,
        store_peers=parse_peers(args.store_peers)
        if args.store_peers else None)

    obs = None
    if args.obs_port is not None:
        obs = ObsServer(svc, host=args.host, port=args.obs_port).start()

    # closed-loop autoscaler per DPT_AUTOSCALE (0=off/bit-parity,
    # dry=recommend-only, 1=actuating). The standalone daemon has no
    # WorkerSupervisor, so worker scaling records as not-applied; lease
    # resizes and pressure sheds still actuate in mode 1.
    autoscaler = svc.attach_autoscaler()

    drain_state = {}

    def _drain_handler(signum, _frame):
        # signal handlers run on the main thread while serve_forever
        # blocks in Event.wait — drain() releases that wait when done
        if drain_state:
            return  # second signal during a drain: already on our way out
        drain_state["signal"] = signal.Signals(signum).name
        drain_state["clean"] = svc.drain(timeout_s=DRAIN_TIMEOUT_S)

    signal.signal(signal.SIGTERM, _drain_handler)
    signal.signal(signal.SIGINT, _drain_handler)

    print(json.dumps({"listening": f"{svc.host}:{svc.port}",
                      **runtime,
                      "obs": f"{obs.host}:{obs.port}" if obs else None,
                      "workers": args.workers, "chaos": args.chaos,
                      "store": args.store_dir, "journal": journal_dir,
                      "log_file": log_path,
                      "autoscale": autoscaler.mode if autoscaler else "0"}),
          flush=True)
    svc.serve_forever()
    if obs is not None:
        obs.close()
    if drain_state:
        ctr = svc.metrics.snapshot()["counters"]
        print(json.dumps({"drained": drain_state.get("signal"),
                          "clean": drain_state.get("clean"),
                          "jobs_drain_parked":
                              ctr.get("jobs_drain_parked", 0)}),
              flush=True)


if __name__ == "__main__":
    main()
