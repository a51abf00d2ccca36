#!/usr/bin/env python3
"""Kernel microbenchmarks: mont_mul / NTT throughput on the current platform.

Usage: python scripts/kernel_bench.py [fr|fq|ntt|all]
Honors DPT_FIELD_MUL (f32 default / u32 fallback) — run twice to compare the
MXU-era multiplier against the round-2 integer path. Timing syncs via a
small device->host transfer (device execution is in-order, so fetching the
last output fences the loop).
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import numpy as np


def _sync(x):
    np.asarray(x[:1, :1] if x.ndim >= 2 else x[:1])


def bench_mont_mul(spec_name, n, chain=8, reps=3):
    import jax
    import jax.numpy as jnp
    from distributed_plonk_tpu.backend import field_jax as FJ

    spec = FJ.FR if spec_name == "fr" else FJ.FQ

    @jax.jit
    def f(a, b):
        # dependent chain: defeats dead-code elimination and amortizes
        # dispatch over `chain` multiplies
        acc = a
        for _ in range(chain):
            acc = FJ.mont_mul(spec, acc, b)
        return acc

    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.integers(0, 1 << 16, size=(spec.n_limbs, n),
                                 dtype=np.uint32))
    b = jnp.asarray(rng.integers(0, 1 << 16, size=(spec.n_limbs, n),
                                 dtype=np.uint32))
    _sync(f(a, b))  # compile + warm
    t0 = time.perf_counter()
    for _ in range(reps):
        out = f(a, b)
    _sync(out)
    dt = (time.perf_counter() - t0) / reps
    per_s = n * chain / dt
    return {"kernel": f"mont_mul_{spec_name}", "n": n, "chain": chain,
            "s_per_call": round(dt, 5), "mul_per_s": round(per_s),
            "ns_per_mul": round(1e9 / per_s, 2)}


def bench_msm(log_n, reps=2):
    """Warm MSM at 2^log_n points (distinct-base tiling like the
    reference's micro-test, src/dispatcher.rs:188-196)."""
    import random
    from distributed_plonk_tpu import curve as C
    from distributed_plonk_tpu.constants import R_MOD
    from distributed_plonk_tpu.backend.msm_jax import MsmContext

    n = 1 << log_n
    rng = random.Random(3)
    distinct = [C.g1_mul(C.G1_GEN, rng.randrange(1, R_MOD))
                for _ in range(1 << 11)]
    bases = (distinct * (n // len(distinct) + 1))[:n]
    ctx = MsmContext(bases)
    scalars = [rng.randrange(R_MOD) for _ in range(n)]
    ctx.msm(scalars)  # compile + warm
    t0 = time.perf_counter()
    for _ in range(reps):
        ctx.msm(scalars)
    dt = (time.perf_counter() - t0) / reps
    return {"kernel": f"msm_2p{log_n}", "s": round(dt, 3),
            "points_per_s": round(n / dt)}


def bench_ntt(log_n, reps=3):
    from distributed_plonk_tpu.backend import ntt_jax

    n = 1 << log_n
    plan = ntt_jax.get_plan(n)
    kernel = plan.kernel()
    rng = np.random.default_rng(2)
    v = rng.integers(0, 1 << 16, size=(16, n), dtype=np.uint32)
    _sync(kernel(v))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = kernel(v)
    _sync(out)
    dt = (time.perf_counter() - t0) / reps
    return {"kernel": f"ntt_2p{log_n}", "s": round(dt, 5),
            "elements_per_s": round(n / dt)}


def main():
    what = sys.argv[1] if len(sys.argv) > 1 else "all"
    from distributed_plonk_tpu.backend import field_jax as FJ
    out = {"mul_path": FJ._MUL_MODE}  # the resolved mode, not a guess
    import jax
    out["platform"] = jax.devices()[0].platform
    if what in ("fr", "all"):
        out["fr"] = bench_mont_mul("fr", 1 << 20)
    if what in ("fq", "all"):
        out["fq"] = bench_mont_mul("fq", 1 << 18)
    if what in ("ntt", "all"):
        out["ntt"] = bench_ntt(20)
    if what in ("msm", "all"):
        out["msm_2p16"] = bench_msm(16)
        out["msm_2p20"] = bench_msm(20, reps=1)
    if what == "msm24":
        # BASELINE config #5 (2^24 streaming MSM): the chunked pipeline
        # streams ~4.6 GB of bases through per-call-budget device launches
        out["msm_2p24"] = bench_msm(24, reps=1)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
