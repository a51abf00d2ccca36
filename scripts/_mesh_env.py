"""Shared virtual-CPU-mesh environment forcing for the mesh scale scripts.

Must be imported (and `force_cpu_mesh()` called) BEFORE jax reads its
platform (same recipe as tests/conftest.py). DPT_MESH_PLATFORM=real skips
the forcing for an actual multi-chip host.
"""

import os
import sys


def force_cpu_mesh(argv=None):
    if os.environ.get("DPT_MESH_PLATFORM", "cpu") != "cpu":
        return
    argv = sys.argv if argv is None else argv
    for k in list(os.environ):
        if k.startswith("TPU_"):
            os.environ.pop(k)
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        # honor --devices / --devices=N (argparse has not run yet)
        n = "8"
        for i, a in enumerate(argv):
            if a == "--devices" and i + 1 < len(argv):
                n = argv[i + 1]
            elif a.startswith("--devices="):
                n = a.split("=", 1)[1]
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}").strip()
