"""Warm-start layer: AOT shape warmup + the fleet worker's store-parked
compile cache.

Two cold-start costs dominate serving a new circuit shape (PAPER.md's
prover pays both once per shape): trusted-setup/key construction and the
XLA compilation of the prover's NTT/MSM stages. The artifact store
(artifacts.py + keycache.py) removes the first across restarts; this
module removes the second with an AOT warmup entry point that pre-builds
keys AND pre-lowers/compiles the prover stages for a shape before any
job arrives (WARMUP wire tag, scripts/warmup.py). Compiled stages land in
JAX's persistent compile cache, which the daemon keeps at one of two
fixed places (JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache —
backend/field_jax.configure_compile_cache). A fleet worker started with
--store still parks its cache under that store (`set_jax_cache_env`),
because warm rejoin syncs those files between store peers.

None of this imports jax at module scope: an embedded ProofService
defaults to the pure-host oracle and must stay importable (and testable)
with no XLA present. jax only loads when a jax-capable backend is
actually handed in.
"""

import os
import time

from . import keycache
from .artifacts import JAX_CACHE_SUBDIR  # one name for the GC'd subdir


def set_jax_cache_env(store_root):
    """Point the (not-yet-imported) jax backend's persistent compile cache
    under `store_root`, via the DPT_JAX_CACHE_DIR knob field_jax reads at
    import (runtime/worker.py --store). Env-only — safe to call from
    processes that never load jax. An explicit user setting (either knob)
    wins; JAX_COMPILATION_CACHE_DIR wins inside
    field_jax.configure_compile_cache whatever this sets."""
    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        os.environ.setdefault(
            "DPT_JAX_CACHE_DIR",
            os.path.join(os.path.abspath(store_root), JAX_CACHE_SUBDIR))


def aot_errors(report):
    """What the compiler said for every stage an aot_warmup report counts
    as failed, flat (empty: every stage compiled)."""
    errors = list(report.get("msm", {}).get("errors", ()))
    for per_domain in report.get("ntt", {}).values():
        errors += per_domain.get("errors", ())
    return errors


def aot_warmup(backend, domain_size, ck=None):
    """Pre-lower/compile the prover stages for one shape's domain on a
    backend that supports it (JaxBackend.warm_stages); the host oracle
    has no compile step, so it reports `unsupported` and costs nothing.
    `aot` is "failed" when the compiler refused any stage (aot_errors
    lists what it said); callers that must not continue past a refusal
    — chip_smoke.py, scripts/warmup.py's exit code — read that."""
    if backend is None or not hasattr(backend, "warm_stages"):
        return {"aot": "unsupported",
                "backend": getattr(backend, "name", None)}
    t0 = time.monotonic()
    report = backend.warm_stages(domain_size, ck=ck)
    report["aot"] = "failed" if aot_errors(report) else "ok"
    report["aot_s"] = round(time.monotonic() - t0, 3)
    return report


def warm_spec(store, spec_obj, backend=None, aot_backend=None):
    """Offline store provisioning (scripts/warmup.py --store-dir): make
    sure `store` holds the bucket keys for one wire spec, building them
    only on a disk miss; `aot_backend` additionally precompiles the
    shape's prover stages. Returns a summary dict ({source: disk|built})."""
    from ..service import jobs as J

    spec = J.JobSpec.from_wire(spec_obj)
    key = J.shape_key(spec)
    t0 = time.monotonic()
    hit = keycache.load_bucket(store, key)
    if hit is not None:
        _srs, pk, vk, meta = hit
        out = {"shape_key": [str(p) for p in key], "source": "disk",
               "domain_size": vk.domain_size,
               "load_s": round(time.monotonic() - t0, 6),
               "build_s": meta.get("build_s")}
    else:
        srs, pk, vk = J.build_bucket_keys(spec, backend=backend)
        build_s = time.monotonic() - t0
        keycache.store_bucket(store, key, srs, pk, vk, build_s=build_s)
        out = {"shape_key": [str(p) for p in key], "source": "built",
               "domain_size": vk.domain_size, "build_s": round(build_s, 6)}
    if aot_backend is not None:
        out["aot"] = aot_warmup(aot_backend, vk.domain_size, ck=pk.ck)
    return out
