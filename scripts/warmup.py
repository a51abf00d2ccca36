#!/usr/bin/env python3
"""Pre-warm proof-service shape buckets (keys + compiled stages).

Two modes:

  # against a running server (WARMUP wire tag; --aot also precompiles):
  python scripts/warmup.py --host 127.0.0.1 --port 9555 \
      --spec '{"kind":"toy","gates":16}' --spec '{"kind":"toy","gates":60}'

  # offline store provisioning, no server (build keys straight into the
  # artifact store a later `serve.py --store-dir` will read):
  python scripts/warmup.py --store-dir /var/dpt/store \
      --spec '{"kind":"merkle","height":32,"num_proofs":1}'

With no --spec, warms the default loadgen mix (toy gates 16/60/150/300).
Prints one JSON line: per-shape source (memory|disk|built) + timings.
Exit 0 iff every shape warmed and, with --aot, every stage compiled.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_DEFAULT_MIX = [{"kind": "toy", "gates": g} for g in (16, 60, 150, 300)]


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--host", default=None,
                    help="warm a running server over the wire")
    ap.add_argument("--port", type=int, default=9555)
    ap.add_argument("--store-dir", default=None,
                    help="offline mode: provision this artifact store "
                         "directly, no server involved")
    ap.add_argument("--spec", action="append", default=[],
                    help="job spec JSON (repeatable); default: loadgen mix")
    ap.add_argument("--aot", action="store_true",
                    help="also precompile prover stages (wire mode: on the "
                         "server's backend; offline: on a local JaxBackend, "
                         "which takes the chip like any jax process)")
    args = ap.parse_args()
    if (args.host is None) == (args.store_dir is None):
        ap.error("exactly one of --host or --store-dir is required")

    specs = [json.loads(s) for s in args.spec] or list(_DEFAULT_MIX)
    shapes, ok = [], True
    t0 = time.time()

    if args.host is not None:
        from distributed_plonk_tpu.service import ServiceClient
        with ServiceClient(args.host, args.port) as c:
            for spec in specs:
                try:
                    shapes.append(c.warmup(spec, aot=args.aot))
                except Exception as e:  # noqa: BLE001 - report per shape
                    ok = False
                    shapes.append({"spec": spec, "error": repr(e)})
    else:
        from distributed_plonk_tpu.store import ArtifactStore, warm_spec
        store = ArtifactStore(args.store_dir)
        aot_backend = None
        if args.aot:
            # executables land in the compile cache the daemon reads:
            # JAX_COMPILATION_CACHE_DIR if set, else <checkout>/.jax_cache
            from distributed_plonk_tpu.backend.jax_backend import JaxBackend
            aot_backend = JaxBackend()
        for spec in specs:
            try:
                shapes.append(warm_spec(store, spec,
                                        aot_backend=aot_backend))
            except Exception as e:  # noqa: BLE001 - report per shape
                ok = False
                shapes.append({"spec": spec, "error": repr(e)})

    # a stage the compiler refused is in the shape's aot report with its
    # message; it fails the warm-up like a shape that raised
    if any(s.get("aot", {}).get("aot") == "failed" for s in shapes):
        ok = False
    print(json.dumps({"ok": ok, "wall_s": round(time.time() - t0, 3),
                      "shapes": shapes}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
