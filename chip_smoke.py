#!/usr/bin/env python3
"""Chip smoke: the reference's 2^13 Merkle prove, served, on the TPU.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # same jobs, placement forced to a
                                     # 4-device mesh; fails on fewer devices

The quickest proof that the system still starts on the chip. It drives the
main path once through the entry points a user calls: the proof service is
started by `service.start_service("jax", ...)` — the code scripts/serve.py
runs, one pool worker — and a `service.ServiceClient` SUBMITs three
`{"kind":"merkle","height":32,"num_proofs":1}` jobs (the reference's v1
workload: ~5,173 constraints, n = 2^13, quotient domain 2^16) with different
seeds over loopback TCP, one after another: the first meets a cold bucket
(key build + every compile), the next two a warm one. Each RESULT must be a
944-byte proof that `verifier.verify` accepts under the bucket's vk, and the
first is byte-compared with a `PythonBackend` prove of the same seed at the
same size (2^13). On one chip the client then sends WARMUP with aot, as
`scripts/warmup.py --aot` does, and any stage the compiler refuses to the
AOT warmers fails the check.

ONE PROCESS HOLDS THE CHIP, and it is this one: the service is embedded and
the client is a thread of the same process. The script starts no child
process (the native transport .so is compiled by g++ at first import, which
finishes before anything else runs). Never start it from a process that has
imported jax.

There is no CPU mode: it exits non-zero, printing no result line, unless
`jax.devices()[0].platform == "tpu"`; likewise if any phase raises, a proof
fails to verify or differs from the oracle's, or Pallas interpret mode was
asked for. The JAX compile cache stays where JAX_COMPILATION_CACHE_DIR says,
else at <checkout>/.jax_cache; a second run in the same place reports
persistent-cache hits. Wall times printed here are smoke timings (cold
compiles included), not metrics.

The last line of standard output is the contract's JSON object:
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""

import argparse
import hashlib
import json
import os
import random
import sys
import time

MERKLE_2P13 = {"kind": "merkle", "height": 32, "num_proofs": 1}
SEEDS = (1301, 1302, 1303)
PROOF_BYTES = 944


def say(**rec):
    print(json.dumps(rec), flush=True)


def serve_and_check(spec, seeds, wait_s=1000.0, aot_warm=True):
    """Start the service the way the daemon does (jax backend, one pool
    worker), SUBMIT one job per seed over TCP and wait for each RESULT in
    turn, verify every proof under the bucket's vk, and byte-compare the
    first with the host oracle's proof of the same seed. With aot_warm,
    then send WARMUP with aot (what `scripts/warmup.py --aot` sends) and
    fail on any stage the compiler refused to the AOT warmers. Returns
    (job reports, service metrics snapshot, runtime, placement backends);
    raises on any failure. Platform-agnostic on purpose: the tier-1 test
    rehearses this exact flow at a toy size under JAX_PLATFORMS=cpu."""
    from distributed_plonk_tpu.backend.python_backend import PythonBackend
    from distributed_plonk_tpu.proof_io import deserialize_proof, serialize_proof
    from distributed_plonk_tpu.prover import prove
    from distributed_plonk_tpu.service import (JobSpec, ServiceClient,
                                               build_circuit, start_service)
    from distributed_plonk_tpu.store import aot_errors
    from distributed_plonk_tpu.verifier import verify

    svc, runtime = start_service("jax", port=0, prover_workers=1)
    try:
        jobs = []
        with ServiceClient("127.0.0.1", svc.port) as client:
            for seed in seeds:
                t0 = time.monotonic()
                job_id = client.submit(dict(spec, seed=seed))["job_id"]
                status = client.wait(job_id, timeout_s=wait_s, poll_s=0.2)
                if status["state"] != "done":
                    raise RuntimeError(f"job {job_id} (seed {seed}) ended "
                                       f"{status['state']}: {status['error']}")
                header, proof = client.result(job_id)
                jobs.append({
                    "seed": seed, "proof": proof, "header": header,
                    "placement": status["placement"],
                    "wall_s": round(time.monotonic() - t0, 3),
                    "wait_s": status["wait_s"], "run_s": status["run_s"],
                    "rounds": status["rounds"]})
            if aot_warm:
                warm = client.warmup(dict(spec, seed=seeds[0]), aot=True)
                if warm["source"] != "memory" or warm["aot"]["aot"] != "ok":
                    raise RuntimeError(
                        f"WARMUP --aot: bucket from {warm['source']}, aot "
                        f"{warm['aot']['aot']}: {aot_errors(warm['aot'])}")
                runtime = dict(runtime, aot_warm={
                    "ntt": {n: r["compiled"]
                            for n, r in warm["aot"]["ntt"].items()},
                    "msm": warm["aot"]["msm"]["compiled"],
                    "smoke_aot_s": warm["aot"]["aot_s"]})
        first = JobSpec.from_wire(dict(spec, seed=seeds[0]))
        bucket = svc.buckets.get(first)
        for job in jobs:
            if len(job["proof"]) != PROOF_BYTES:
                raise RuntimeError(f"seed {job['seed']}: proof is "
                                   f"{len(job['proof'])} bytes")
            pub = [int(x, 16) for x in job["header"]["public_input"]]
            t0 = time.monotonic()
            if not verify(bucket.vk, pub, deserialize_proof(job["proof"]),
                          rng=random.Random(1)):
                raise RuntimeError(f"seed {job['seed']}: verifier.verify "
                                   "rejected the proof")
            job["verify_s"] = round(time.monotonic() - t0, 3)
        t0 = time.monotonic()
        oracle = serialize_proof(prove(random.Random(first.seed),
                                       build_circuit(first), bucket.pk,
                                       PythonBackend()))
        if oracle != jobs[0]["proof"]:
            raise RuntimeError(f"seed {first.seed}: device proof differs "
                               "from the PythonBackend proof")
        jobs[0]["oracle_equal"] = True
        jobs[0]["oracle_prove_s"] = round(time.monotonic() - t0, 3)
        runtime = dict(runtime, domain_size=bucket.domain_size,
                       key_build_s=round(bucket.build_s, 3))
        return (jobs, svc.metrics.snapshot(), runtime,
                list(svc.scheduler._mesh_backends.values()))
    finally:
        svc.shutdown()


def kernel_paths(domain_size):
    """What `auto` resolved each kernel to at this prove's shapes."""
    from distributed_plonk_tpu.backend import field_jax, msm_jax, ntt_jax
    return {
        "field_mul": "pallas" if field_jax._use_pallas((16, domain_size))
        else "xla-" + ("f32" if field_jax._f32_active() else "u32"),
        "ntt_radix": ntt_jax._active_radix(),
        "msm": msm_jax._kernel_mode(),
        "msm_bucket_update":
            "onehot" if msm_jax._use_onehot_update() else "put",
        "pallas_interpret": field_jax.pallas_interpret(),
    }


def check_mesh_spread(mesh_backends, chips, counters):
    """The mesh leg's proof that work was on every chip: the commit key a
    MeshBackend keeps resident is sharded over all of them, every device
    has held live buffers, and no NTT of a mesh-placed job fell back to
    the replicated kernel (the service's `mesh_ntt_*` counters)."""
    import jax
    calls = counters.get("mesh_ntt_calls", 0)
    sharded = counters.get("mesh_ntt_sharded", 0)
    if not calls or sharded != calls:
        raise RuntimeError(f"{sharded} of {calls} NTTs of the mesh-placed "
                           "jobs took the sharded plan, want all")
    if not mesh_backends:
        raise RuntimeError("no job was placed on a mesh backend")
    spread = []
    for be in mesh_backends:
        for _bases, ctx in be._msm_ctxs.values():
            spread.append(len(ctx.point[0].sharding.device_set))
    if not spread or min(spread) != chips:
        raise RuntimeError(f"resident commit key spans {spread} devices, "
                           f"want {chips}")
    peaks = {}
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peaks[str(d.id)] = stats.get("peak_bytes_in_use")
        if not peaks[str(d.id)]:
            raise RuntimeError(f"device {d.id} never held a buffer")
    return {"commit_key_devices": spread, "peak_bytes_in_use": peaks,
            "mesh_ntt_sharded": sharded, "mesh_ntt_calls": calls}


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1,
                    help="devices the prove must use; above 1 the jobs are "
                         "placed on a mesh of that many (fails on fewer)")
    args = ap.parse_args()
    t_start = time.monotonic()
    if args.chips > 1:
        # placement reads these at import: every 2^13 job takes the mesh
        os.environ["DPT_PLACE_LARGE_MIN"] = "8192"
        os.environ["DPT_MESH_LEASE"] = str(args.chips)

    import jax
    import jaxlib
    from importlib import metadata

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if device["platform"] != "tpu":
        sys.exit(f"chip_smoke: no TPU — jax found {device}; this check has "
                 "no CPU mode")
    if len(devs) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} asked, jax found "
                 f"{len(devs)} device(s)")

    cache_events = {}

    def on_event(event, **_kw):
        if event.startswith("/jax/compilation_cache/"):
            name = event.rsplit("/", 1)[-1]
            cache_events[name] = cache_events.get(name, 0) + 1
    jax.monitoring.register_event_listener(on_event)

    from distributed_plonk_tpu.backend import field_jax
    if field_jax.pallas_interpret():
        sys.exit("chip_smoke: DPT_PALLAS_INTERPRET is set; the chip check "
                 "runs compiled kernels only")
    say(phase="start", jax=jax.__version__, jaxlib=jaxlib.__version__,
        libtpu=metadata.version("libtpu"), device=device,
        chips_asked=args.chips,
        compile_cache_dir=jax.config.jax_compilation_cache_dir)

    # WARMUP warms the pool's one-device backend, which proves nothing in
    # the mesh leg: there every job runs on the leased MeshBackend
    jobs, metrics, runtime, mesh_backends = serve_and_check(
        MERKLE_2P13, SEEDS, aot_warm=args.chips == 1)
    say(phase="served", runtime=runtime, kernels=kernel_paths(
        runtime["domain_size"]))
    for i, job in enumerate(jobs):
        say(phase="job", n=i, bucket="cold" if i == 0 else "warm",
            seed=job["seed"], placement=job["placement"],
            proof_bytes=len(job["proof"]),
            proof_sha256=hashlib.sha256(job["proof"]).hexdigest(),
            verified=True, oracle_equal=job.get("oracle_equal"),
            smoke_wall_s=job["wall_s"], smoke_wait_s=job["wait_s"],
            smoke_run_s=job["run_s"], smoke_rounds_s=job["rounds"],
            smoke_verify_s=job["verify_s"],
            smoke_oracle_prove_s=job.get("oracle_prove_s"))
    want = "mesh" if args.chips > 1 else "pool"
    placed = sorted({job["placement"] for job in jobs})
    if placed != [want]:
        raise RuntimeError(f"jobs were placed {placed}, want all {want}")
    counters = metrics["counters"]
    if args.chips > 1:
        say(phase="mesh", **check_mesh_spread(mesh_backends, args.chips,
                                              counters))
    say(phase="done",
        jobs_completed=counters.get("jobs_completed"),
        job_attempt_errors=counters.get("job_attempt_errors", 0),
        compile_cache=cache_events,
        smoke_total_s=round(time.monotonic() - t_start, 1))
    if counters.get("jobs_completed") != len(SEEDS) \
            or counters.get("job_attempt_errors", 0):
        raise RuntimeError(f"service counters disagree: {counters}")
    say(ok=True, device=device)
    return 0


if __name__ == "__main__":
    sys.exit(main())
