#!/usr/bin/env python3
"""Config #2 baseline: the v1 workload proved over a local CPU worker
fleet (BASELINE.json config "2^20 circuit, 4 CPU workers over capnp" —
scaled to the workload size given on the CLI; the reference's analog is
test2 over its 2-host LAN, /root/reference/src/dispatcher2.rs:1273-1295).

Spawns N worker daemons (JAX CPU backend) on localhost, preprocesses
locally, prove()s through RemoteBackend so every NTT/MSM rides the fleet
protocol, verifies, and emits one JSON line.

Usage: python scripts/fleet_baseline.py [--workers 4] [--height 32]
           [--proofs 1] [--worker-timeout S] [--out FILE]
"""

import argparse
import json
import os
import random
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def scrubbed_cpu_env():
    env = dict(os.environ)
    for k in list(env):
        if k.startswith("TPU_"):
            env.pop(k)
    env["JAX_PLATFORMS"] = "cpu"
    return env


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--height", type=int, default=32)
    ap.add_argument("--proofs", type=int, default=1)
    ap.add_argument("--worker-timeout", type=float, default=600,
                    help="seconds to wait for the fleet to come up (4 jax"
                         " imports on one contended core take minutes)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    # the dispatcher side must also be CPU-pinned: RemoteBackend runs the
    # round math locally between fleet calls (capture the scrubbed copy
    # BEFORE clearing — scrubbed_cpu_env reads os.environ)
    scrubbed = scrubbed_cpu_env()
    os.environ.clear()
    os.environ.update(scrubbed)

    from distributed_plonk_tpu import kzg
    from distributed_plonk_tpu.prover import prove
    from distributed_plonk_tpu.verifier import verify
    from distributed_plonk_tpu.workload import generate_circuit
    from distributed_plonk_tpu.runtime.netconfig import NetworkConfig
    from distributed_plonk_tpu.runtime.dispatcher import Dispatcher, RemoteBackend
    from distributed_plonk_tpu.trace import Tracer

    res = {"workers": args.workers, "height": args.height,
           "num_proofs": args.proofs}
    t0 = time.perf_counter()
    ckt, _ = generate_circuit(rng=random.Random(11), height=args.height,
                              num_proofs=args.proofs)
    res["n"] = ckt.n
    res["log2_n"] = ckt.n.bit_length() - 1
    res["circuit_gen_s"] = round(time.perf_counter() - t0, 3)
    print(f"[fleet] circuit n = 2^{res['log2_n']}", file=sys.stderr)

    t0 = time.perf_counter()
    srs = kzg.universal_setup(ckt.n + 3, rng=random.Random(12))
    pk, vk = kzg.preprocess(srs, ckt)
    res["setup_preprocess_host_s"] = round(time.perf_counter() - t0, 3)
    print(f"[fleet] host setup+preprocess {res['setup_preprocess_host_s']}s",
          file=sys.stderr)

    def free_port():
        # bind-0-and-read-back (same trick as tests/test_multihost.py):
        # beats a pid-derived fixed scheme, which fails only after the
        # full worker-timeout when a computed port is already bound
        import socket
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    cfg_path = os.path.join(REPO, f".fleet_baseline_{os.getpid()}.json")
    cfg = NetworkConfig(
        [f"127.0.0.1:{free_port()}" for _ in range(args.workers)])
    cfg.save(cfg_path)
    logs = []
    procs = []
    try:
        for i in range(args.workers):
            log = open(os.path.join(REPO, f".fleet_worker_{i}.log"), "w")
            logs.append(log)
            procs.append(subprocess.Popen(
                [sys.executable, "-m", "distributed_plonk_tpu.runtime.worker",
                 str(i), cfg_path, "--backend", "jax"],
                cwd=REPO, env=scrubbed_cpu_env(), stdout=log, stderr=log))
        d = None
        deadline = time.time() + args.worker_timeout
        while time.time() < deadline:
            try:
                d = Dispatcher(cfg)
                d.ping()
                break
            except (ConnectionError, OSError):
                time.sleep(0.5)
                d = None
        assert d is not None, "workers did not come up"
        print("[fleet] workers up", file=sys.stderr)

        be = RemoteBackend(d)
        t0 = time.perf_counter()
        prove(random.Random(13), ckt, pk, be)
        res["prove_cold_s"] = round(time.perf_counter() - t0, 3)
        tr = Tracer()
        t0 = time.perf_counter()
        proof = prove(random.Random(13), ckt, pk, be, tracer=tr)
        res["prove_s"] = round(time.perf_counter() - t0, 3)
        res["rounds"] = {k: round(v, 3) for k, v in tr.totals(1).items()}
        t0 = time.perf_counter()
        ok = verify(vk, ckt.public_input(), proof, rng=random.Random(14))
        res["verify_s"] = round(time.perf_counter() - t0, 3)
        res["verified"] = bool(ok)
        assert ok
        d.shutdown()
        for p in procs:
            p.wait(timeout=15)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        for log in logs:
            log.close()
        try:
            os.remove(cfg_path)
        except OSError:
            pass

    out = json.dumps(res)
    if args.out:
        with open(args.out, "w") as f:
            f.write(out + "\n")
    print(out)


if __name__ == "__main__":
    main()
