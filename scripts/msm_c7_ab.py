#!/usr/bin/env python3
"""A/B c=8 (32x128) vs c=7 (37x64) signed MSM windows on the chip.

DPT_MSM_C is an import-time class default, so each config runs in a
fresh subprocess: warm 2^20 MSM wall-clock (reference micro-test scale,
/root/reference/src/dispatcher.rs:188-196: 2^11 distinct bases tiled up)
plus a 2^12 host-oracle correctness check. The two configs must also
agree on the 2^20 result point.

Usage: python scripts/msm_c7_ab.py [--log-n 20] [--out FILE]
"""

import argparse
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

INNER = r"""
import json, random, sys, time
sys.path.insert(0, %(repo)r)
from distributed_plonk_tpu import curve as C
from distributed_plonk_tpu.constants import R_MOD
from distributed_plonk_tpu.backend.msm_jax import MsmContext

LOG_N = %(log_n)d
N = 1 << LOG_N
rng = random.Random(3)
distinct = [C.g1_mul(C.G1_GEN, rng.randrange(1, R_MOD)) for _ in range(1 << 11)]
bases = (distinct * (N // len(distinct) + 1))[:N]
scalars = [rng.randrange(R_MOD) for _ in range(N)]

small = MsmContext(bases[:1 << 12])
got = small.msm(scalars[:1 << 12])
assert got == C.g1_msm(bases[:1 << 12], scalars[:1 << 12]), "oracle mismatch"

ctx = MsmContext(bases)
ctx.msm(scalars)  # compile + warm
t0 = time.perf_counter()
pt = ctx.msm(scalars)
dt = time.perf_counter() - t0
print("RESULT " + json.dumps({
    "c": MsmContext._C_BATCH, "msm_s": round(dt, 3),
    "points_per_s": round(N / dt),
    "oracle_2p12_ok": True,
    "point_x_mod": pt[0] %% 0xFFFFFFFF if pt else None}))
"""


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--log-n", type=int, default=20)
    ap.add_argument("--out", default=None)
    ap.add_argument("--timeout", type=int, default=2400)
    args = ap.parse_args()

    results = []
    for c in ("8", "7"):
        env = dict(os.environ, DPT_MSM_C=c)
        print(f"[ab] c={c} ...", file=sys.stderr)
        try:
            proc = subprocess.run(
                [sys.executable, "-c",
                 INNER % {"repo": REPO, "log_n": args.log_n}],
                env=env, capture_output=True, text=True,
                timeout=args.timeout)
        except subprocess.TimeoutExpired:
            results.append({"c": int(c), "error": "timeout"})
            continue
        line = next((l for l in proc.stdout.splitlines()
                     if l.startswith("RESULT ")), None)
        if line:
            results.append(json.loads(line[len("RESULT "):]))
            print(f"[ab]   -> {line[len('RESULT '):]}", file=sys.stderr)
        else:
            results.append({"c": int(c),
                            "error": (proc.stderr or "")[-500:]})
            print(f"[ab]   FAILED rc={proc.returncode}", file=sys.stderr)
    ok = [r for r in results if r.get("point_x_mod") is not None]
    agree = len(ok) == 2 and ok[0]["point_x_mod"] == ok[1]["point_x_mod"]
    blob = json.dumps({"log_n": args.log_n, "configs": results,
                       "c7_c8_agree": agree})
    if args.out:
        with open(args.out, "w") as f:
            f.write(blob + "\n")
    print(blob)


if __name__ == "__main__":
    main()
