"""Driver-hook tests: entry() compiles, dryrun_multichip(8) fits the budget.

The dry run re-executes its body in a subprocess pinned to the CPU
(__graft_entry__.scrubbed_cpu_env), so it behaves the same whatever
platform the caller's environment selects.
"""

import os
import pathlib
import subprocess
import sys
import time

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent

# matches the driver-facing _DRYRUN_TIMEOUT_S contract: since round 4 the
# dryrun runs a FULL tiny mesh prove (cold-compiles the SPMD prover
# programs, ~15-20 min cold on a shared 8-core host; minutes warm via the
# persistent compile cache)
BUDGET_S = 2400


@pytest.mark.slow
def test_dryrun_multichip_8_within_budget():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    t0 = time.time()
    proc = subprocess.run(
        [sys.executable, "-c",
         "import __graft_entry__ as g; g.dryrun_multichip(8); print('DRYRUN_OK')"],
        cwd=str(REPO), env=env, capture_output=True, text=True,
        timeout=BUDGET_S)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "DRYRUN_OK" in proc.stdout
    assert time.time() - t0 < BUDGET_S


def test_entry_compiles_and_runs():
    import numpy as np
    import __graft_entry__ as g

    fn, args = g.entry()
    out = fn(*args)
    assert np.asarray(out).shape == np.asarray(args[0]).shape
