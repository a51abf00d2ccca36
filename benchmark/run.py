#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the chip this process is started on.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process: it embeds the proof service as `scripts/serve.py` starts it,
drives it with `ServiceClient` threads over loopback TCP, and prints as the
last line of standard output one JSON object (correct, attempted, failed,
metrics, device, and with --trace 1 breakdown). It exits non-zero and
prints no result line without the cell's TPU chips: there is no CPU mode.
Cells, configurations, traffic mixes and per-layer metrics are the data
files BENCHMARK.json names; see PERF.md.
"""

import time

T_START = time.monotonic()  # process start, for setup_s

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from benchmark.lib import harness
    result = harness.run_cell(ROOT, args.workload, args.seed, args.seconds,
                              args.trace, T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
