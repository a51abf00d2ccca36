#!/usr/bin/env python3
"""The control of `correct`, on the chip, at a cell's own size.

    python3 benchmark/control.py --workload <cell> --seeds 11,12,13 --seconds 12 \
        [--faults answer_altered,blinding_reused,answer_missing]

Not part of a benchmark run. One process and one set-up; then for every seed
a sound window, which has to come out correct, and one window for each fault
planted underneath the same service (`benchmark/lib/faults.py`), which has to
come out not correct. It also holds the reference put in the program's place
with its blinding reused (`precision="reused_blinding"`) to the same comparison.
Prints one JSON line per window and a last line {"ok": ...}; exits non-zero
when a sound window is not correct or a control is.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None, root=ROOT, **session_kw):
    """`root` and the session's keywords are for the CPU test, which drives
    this same flow at a toy size."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated window seeds")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--faults", default="")
    ap.add_argument("--ref-workers", type=int, default=8)
    args = ap.parse_args(argv)
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from benchmark.lib import check, faults, harness, window as W

    seeds = [int(s) for s in args.seeds.split(",")]
    wanted = [f for f in args.faults.split(",") if f]
    session_kw.setdefault("ref_workers", args.ref_workers)
    ses = harness.Session(root, args.workload, **session_kw)
    ok = True
    try:
        oracles = {s: ses.oracle(s) for s in seeds}
        reused = {s: ses.oracle(s, precision="reused_blinding") for s in seeds}
        ses.open(seeds[0])
        for seed in seeds:
            for fault in [None] + wanted:
                if fault is None:
                    win = ses.window(seed, args.seconds)[0]
                else:
                    with faults.planted(fault):
                        win = ses.window(seed, args.seconds)[0]
                correct, checks, good = ses.judge(win, oracles[seed])
                summary = W.summarize(win)
                sound = correct if fault is None else not correct
                ok = ok and sound
                harness.say(window="sound" if fault is None else fault,
                            seed=seed, correct=correct, as_expected=sound,
                            checks={k: [c["value"], c["limit"]]
                                    for k, c in checks.items()},
                            attempted=summary["attempted"],
                            proofs_per_s=summary["proofs_per_s"],
                            latency_mean_s=summary["latency_mean_s"])
            # the reference in the program's place, blinding reused: its
            # answer verifies and must still fail the byte comparison
            for c, fut in reused[seed].items():
                spec = W.draw_spec(ses.cell.job_mix, seed, "window", c, 0)
                ctl = fut.result(timeout=3000)["proof"]
                full = oracles[seed][c].result(timeout=3000)["proof"]
                verdict = ses.ref.check_served(
                    spec, ctl, None, ses.tau).result(timeout=900)
                diffs = check.byte_diffs(ctl, full)
                fails = diffs > 0
                ok = ok and fails and verdict["verified"]
                harness.say(window="reference_reused", seed=seed,
                            verified=verdict["verified"],
                            oracle_byte_diffs=diffs, limit=0,
                            as_expected=fails and verdict["verified"])
    finally:
        ses.close()
    print(json.dumps({"ok": ok}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
