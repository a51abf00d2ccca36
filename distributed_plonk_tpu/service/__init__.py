"""Multi-client proving service in front of the prover/backends.

The serving layer the ROADMAP's "heavy traffic" north star needs and the
reference never had (its dispatcher proves exactly one hardcoded workload
per process, /root/reference/src/dispatcher2.rs:1218-1295):

    client --SUBMIT/STATUS/RESULT/METRICS/WARMUP--> server.ProofService
        -> queue.JobQueue          (priority, admission control, backpressure)
        -> placement.PlacementScheduler
                                   (shape buckets: shared SRS/pk per bucket,
                                    BucketCache tiers memory -> disk -> build
                                    over the ../store artifact store; then the
                                    PLACEMENT decision — small jobs prove
                                    data-parallel as one batched launch set,
                                    big jobs shard over a leased submesh,
                                    mid sizes take the per-job pool)
        -> pool.WorkerPool         (per-job timeout, bounded retry,
                                    resume-from-checkpoint on worker death;
                                    batched groups via prover.prove_many)
        -> metrics.Metrics         (counters + latency histograms, JSON)

The wire control plane rides runtime/protocol.py's framed transport (tags
SUBMIT/STATUS/RESULT/METRICS/KILL_WORKER/WARMUP). Entry points:
scripts/serve.py (daemon), scripts/loadgen.py (concurrent submitters +
fault injection), and scripts/warmup.py (shape pre-warming / offline store
provisioning); tests/test_service.py runs the whole loop in-process and
tests/test_store.py pins the warm-start contracts.
"""

from .jobs import Job, JobSpec, build_circuit, build_bucket_keys, shape_key
from .journal import JobJournal
from .queue import JobQueue, Rejected
from .metrics import Metrics
from .placement import PlacementScheduler, SubmeshLeaser
from .pool import WorkerPool, WorkerKilled, JobTimeout, WorkerDrained
from .scheduler import BucketCache, Scheduler
from .server import ProofService, make_backend, start_service
from .client import ServiceClient

__all__ = [
    "Job", "JobSpec", "build_circuit", "build_bucket_keys", "shape_key",
    "JobJournal", "JobQueue", "Rejected", "Metrics", "WorkerPool",
    "WorkerKilled", "JobTimeout", "WorkerDrained", "BucketCache",
    "Scheduler", "PlacementScheduler", "SubmeshLeaser", "ProofService",
    "ServiceClient", "make_backend", "start_service",
]
