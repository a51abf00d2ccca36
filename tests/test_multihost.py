"""Multi-host (DCN) proof: two REAL processes join one jax.distributed
mesh and run the framework's collectives across it.

The reference's only multi-host evidence is its 2-host LAN deployment
(/root/reference/config/network.json:1-10, src/worker.rs:441-536); this is
the jax.distributed multi-controller analog, runnable in CI without
hardware: each subprocess owns 4 virtual CPU devices
(xla_force_host_platform_device_count), process 0 is the coordinator
(network.json analog), and the 8-device global mesh runs the 4-step
cross-shard NTT (lax.all_to_all over what would be DCN) plus a sharded
MSM — asserting bit-identity against the host oracle in every process.
"""

import os
import pathlib
import socket
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent

_CHILD = r"""
import os, random, sys
sys.path.insert(0, {repo!r})
import jax
from distributed_plonk_tpu.parallel.mesh import init_multihost, make_mesh
from distributed_plonk_tpu.parallel.ntt_mesh import MeshNttPlan
from distributed_plonk_tpu.parallel.msm_mesh import MeshMsmContext
from distributed_plonk_tpu import poly as P
from distributed_plonk_tpu import curve as C
from distributed_plonk_tpu.constants import R_MOD

pid = int(sys.argv[1])
nproc, ndev = init_multihost(sys.argv[2], 2, pid)
assert nproc == 2, nproc
assert ndev == 8, ndev  # 4 local virtual cpu devices per process

mesh = make_mesh(8)
rng = random.Random(21)
n = 64
domain = P.Domain(n)
values = [rng.randrange(R_MOD) for _ in range(n)]
plan = MeshNttPlan(mesh, n)
coeffs = plan.run_ints(values, inverse=True)
assert coeffs == P.ifft(domain, values), "multihost mesh iNTT mismatch"

bases = [C.g1_mul(C.G1_GEN, rng.randrange(1, R_MOD)) for _ in range(16)]
scalars = [rng.randrange(R_MOD) for _ in range(16)]
ctx = MeshMsmContext(mesh, bases)
assert ctx.msm(scalars) == C.g1_msm(bases, scalars), "multihost MSM mismatch"
print("MULTIHOST_OK", pid, flush=True)
"""


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


@pytest.mark.slow
def test_two_process_distributed_mesh():
    port = _free_port()
    coord = f"127.0.0.1:{port}"
    env = dict(os.environ)
    for k in list(env):
        if k.startswith("TPU_"):
            env.pop(k)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env.pop("JAX_NUM_PROCESSES", None)
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _CHILD.format(repo=str(REPO)), str(pid),
             coord],
            cwd=str(REPO), env=env, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True)
        for pid in range(2)
    ]
    outs = []
    try:
        for p in procs:
            out, err = p.communicate(timeout=900)
            outs.append((p.returncode, out, err))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for rc, out, err in outs:
        assert rc == 0, err[-3000:]
        assert "MULTIHOST_OK" in out, (out, err[-1500:])
