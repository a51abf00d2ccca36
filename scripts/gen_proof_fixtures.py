#!/usr/bin/env python3
"""Regenerate the golden proof fixtures (tests/fixtures/*.hex).

The recipes live in tests/test_proof_golden.py (RECIPES + _prove_bytes)
and are IMPORTED here — generator and replaying tests share one source,
so they cannot drift. Regeneration is only legitimate when the proof
system's output intentionally changes (it should never change silently —
that is the point of the fixtures).
"""

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tests"))
# pure-host generation: never take the chip
for _k in list(os.environ):
    if _k.startswith("TPU_"):
        os.environ.pop(_k)
os.environ["JAX_PLATFORMS"] = "cpu"

FIXDIR = os.path.join(REPO, "tests", "fixtures")


def main():
    from test_proof_golden import RECIPES, _prove_bytes

    os.makedirs(FIXDIR, exist_ok=True)
    for name, build in RECIPES.items():
        ckt = build()
        blob, _ = _prove_bytes(ckt)
        path = os.path.join(FIXDIR, name + ".hex")
        with open(path, "w") as f:
            f.write(blob.hex() + "\n")
        print(f"wrote {path} ({len(blob)} bytes, "
              f"n=2^{ckt.n.bit_length() - 1})")


if __name__ == "__main__":
    main()
