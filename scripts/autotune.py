#!/usr/bin/env python3
"""Offline kernel calibration of an artifact store (scripts/warmup.py's
sibling): measure the MSM/NTT/field-mul candidate spaces at the given
shapes on THIS machine, persist the winning plan (+ the winners'
AOT-compiled executables in the store-owned persistent compile cache),
and print one JSON report line. A store calibrated here serves with
zero knob setup: `serve.py --store-dir` (and fleet workers pointed at
the store) load the plan at startup and reach first proof with zero
measurement runs and zero kernel compiles at the calibrated shapes.

  python scripts/autotune.py --store-dir /var/dpt/store \
      --shapes 2^10,2^14,2^18 --budget-s 300 --report

With no --shapes, calibrates at DPT_AUTOTUNE_SHAPES, else the domain
sizes of the store's provisioned shape buckets (run scripts/warmup.py
first so the plan covers the real serving mix), else 2^10. --force
remeasures even when the store already holds a plan for this machine
fingerprint (knob sweeps, post-driver-update refreshes); the default is
load-or-run, so re-invoking on a calibrated store is free.

Exit 0 iff a plan is active when we're done (loaded or fresh).
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--store-dir", required=True,
                    help="artifact store to calibrate (created if missing)")
    ap.add_argument("--shapes", default=None,
                    help="comma-separated domain sizes, 2^k accepted "
                         "(default: store shape buckets, else 2^10)")
    ap.add_argument("--budget-s", type=float, default=None,
                    help="wall-clock budget for the whole measure pass "
                         "(default DPT_AUTOTUNE_BUDGET_S, 120)")
    ap.add_argument("--force", action="store_true",
                    help="remeasure even if the store holds a plan for "
                         "this machine fingerprint")
    ap.add_argument("--no-aot", action="store_true",
                    help="skip pre-compiling the winners' executables")
    ap.add_argument("--report", action="store_true",
                    help="include the full per-cell plan in the output")
    args = ap.parse_args()

    from distributed_plonk_tpu.store import ArtifactStore, calibration
    from distributed_plonk_tpu.backend import autotune

    t0 = time.time()
    store = ArtifactStore(args.store_dir)
    # winners' AOT executables land in the compile cache the daemon
    # reads: JAX_COMPILATION_CACHE_DIR if set, else <checkout>/.jax_cache
    shapes = calibration.parse_shapes(args.shapes) if args.shapes else None

    if args.force:
        tuner = autotune.Autotuner(
            shapes or calibration._default_shapes(store),
            budget_s=args.budget_s)
        with calibration.calibration_lock(store):
            plan = tuner.run(aot=not args.no_aot)
            calibration.store_plan(store, plan)
        autotune.set_active_plan(plan)
        out = {"source": "fresh", "fingerprint": plan.fingerprint,
               "cells": len(plan.cells)}
    else:
        out = calibration.load_or_run(store, mode="run", shapes=shapes,
                                      budget_s=args.budget_s,
                                      aot=not args.no_aot)

    plan = autotune.active_plan()
    ok = plan is not None
    out["ok"] = ok
    out["wall_s"] = round(time.time() - t0, 3)
    if args.report and plan is not None:
        out["plan"] = {f"{k}:{n}": cell
                       for (k, n), cell in sorted(plan.cells.items())}
        out["meta"] = plan.meta
    print(json.dumps(out), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
