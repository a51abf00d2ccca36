"""CLI: `python -m distributed_plonk_tpu.analysis [--strict] [...]`.

Exit status 0 iff every selected pass is clean — the one-command proof
obligation `scripts/ci.sh analyze` runs and bench.py records as
`analysis_clean`. Four passes:

  lint       AST hazard lints over the package (JIT cache keys, f32
             promotion, lock discipline incl. the LOCK03 order graph,
             metric/log/knob glossaries, wire-tag conformance)
  contracts  the named carry side-condition inequalities, evaluated
             for both field specs
  bounds     jaxpr interval propagation over every registry entry:
             machine arithmetic == exact integer semantics (no
             overflow, no inexact f32, declared output ranges hold)
  values     exact evaluation of every registry entry's value
             contract: the kernel's integer semantics equal its
             algebraic claim (mont_mul really is a*b*R^-1 mod p, the
             NTT really matches the polynomial oracle, ...)

`--changed-only` keys bounds/values/contracts on the mtimes of the
kernel modules each registry family traces (state in
.analysis_state.json at the repo root, refreshed only after a fully
clean run); lints always run — they cover the whole package and cost
well under a second. Runs on CPU (tracing + exact host evaluation,
nothing executes on a device), so it is safe anywhere the repo
imports.
"""

import argparse
import json
import os
import sys
import time

_REPO = os.path.normpath(os.path.join(os.path.dirname(__file__),
                                      "..", ".."))
_PKG = os.path.join(_REPO, "distributed_plonk_tpu")
_STATE_FILE = os.path.join(_REPO, ".analysis_state.json")

# registry-entry name prefix -> package-relative kernel modules whose
# change invalidates that family (what the entries actually trace
# through). Files in _GLOBAL_DEPS invalidate every family: they define
# the analyzers themselves, the field constants, or the oracles the
# value contracts compare against.
_ENTRY_MODULES = {
    "field/": ("backend/field_jax.py", "backend/field_pallas.py"),
    "ntt/": ("backend/ntt_jax.py",
             "backend/field_jax.py", "backend/field_pallas.py",
             "poly.py"),
    "msm/": ("backend/msm_jax.py", "backend/msm_pallas.py",
             "backend/field_jax.py", "backend/field_pallas.py",
             "backend/curve_jax.py", "backend/curve_pallas.py"),
    "curve/": ("backend/curve_jax.py", "backend/curve_pallas.py",
               "backend/field_jax.py"),
    "eval/": ("backend/prover_jax.py", "backend/field_jax.py"),
}
_GLOBAL_DEPS = ("constants.py", "backend/limbs.py",
                "analysis/bounds.py", "analysis/values.py",
                "analysis/registry.py")


def _dep_mtimes():
    files = set(_GLOBAL_DEPS)
    for deps in _ENTRY_MODULES.values():
        files |= set(deps)
    out = {}
    for rel in sorted(files):
        p = os.path.join(_PKG, rel)
        if os.path.exists(p):
            out[rel] = os.stat(p).st_mtime
    return out


def _changed_scope():
    """(names_filter, contracts_needed, mtimes) for --changed-only.

    names_filter: None = every entry; [] = nothing changed, skip the
    registry passes; else the list of changed family prefixes."""
    mtimes = _dep_mtimes()
    try:
        with open(_STATE_FILE) as f:
            old = json.load(f)
    except (OSError, ValueError):
        return None, True, mtimes  # no clean baseline: run everything
    changed = {rel for rel, t in mtimes.items() if old.get(rel) != t}
    changed |= set(old) - set(mtimes)  # deleted module: distrust all
    if changed & set(_GLOBAL_DEPS) or set(old) - set(mtimes):
        return None, True, mtimes
    names = [pfx for pfx, deps in sorted(_ENTRY_MODULES.items())
             if changed & set(deps)]
    contracts = any("field_jax" in rel for rel in changed)
    return names, contracts, mtimes


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="python -m distributed_plonk_tpu.analysis",
        description="static kernel verifier: jaxpr interval bounds, "
                    "exact value contracts, carry contracts, and AST "
                    "hazard lints")
    ap.add_argument("--strict", action="store_true",
                    help="treat unhandled primitives / warnings as errors")
    ap.add_argument("--only",
                    choices=("bounds", "values", "lint", "contracts"),
                    help="run a single pass (default: all)")
    ap.add_argument("--kernel", action="append",
                    help="substring filter on registry entry names "
                         "(repeatable; bounds and values passes)")
    ap.add_argument("--changed-only", action="store_true",
                    help="skip registry families whose kernel modules "
                         "are unchanged since the last fully clean run "
                         "(mtime state in .analysis_state.json; lints "
                         "always run)")
    ap.add_argument("--list", action="store_true",
                    help="list registry entries and exit")
    ap.add_argument("-q", "--quiet", action="store_true",
                    help="only print failures and the summary line")
    args = ap.parse_args(argv)

    if args.changed_only and args.kernel:
        ap.error("--changed-only and --kernel are mutually exclusive "
                 "(an explicit filter defeats the staleness tracking)")

    # tracing must not wait on (or disturb) an accelerator runtime; the
    # env var only takes effect when jax has not been imported yet, which
    # is the normal `python -m` path
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    if args.list:
        # enumeration only: no passes run, nothing else interleaves
        from .registry import build_registry
        for e in build_registry():
            print(e.name)
        return 0

    names = args.kernel
    contracts_wanted = True
    state = None
    if args.changed_only:
        names, contracts_wanted, state = _changed_scope()
        if names == []:
            if not args.quiet:
                print("changed-only: no kernel module changed since "
                      "the last clean run")
        elif names is not None and not args.quiet:
            print(f"changed-only: {' '.join(names)}")

    failures = 0
    t0 = time.monotonic()

    if args.only in (None, "lint"):
        from .lint import run_lints
        findings = run_lints()
        for f in findings:
            print(f"LINT FAIL {f}")
        if not args.quiet:
            print(f"lint: {len(findings)} finding(s)")
        failures += len(findings)

    if args.only in (None, "contracts") and contracts_wanted:
        from .bounds import check_contracts
        bad = check_contracts()
        for v in bad:
            print(f"CONTRACT FAIL {v}")
        if not args.quiet:
            from ..backend.field_jax import CARRY_CONTRACTS
            print(f"contracts: {len(CARRY_CONTRACTS)} checked for "
                  f"Fr+Fq, {len(bad)} violated")
        failures += len(bad)

    skip_registry = args.changed_only and names == []

    if args.only in (None, "bounds") and not skip_registry:
        from .registry import run_bounds

        checked_box = [0]

        def progress(name, violations):
            checked_box[0] += 1
            if violations:
                print(f"BOUNDS FAIL {name}: "
                      f"{len(violations)} violation(s)")
                for v in violations:
                    print(f"  {v}")
            elif not args.quiet:
                print(f"ok {name}")

        # when the contracts pass already ran above, don't double-run
        # (or double-count) it here; under --only bounds the contracts
        # still run and COUNT — a violated contract must never print
        # CLEAN just because the pass selection filtered it
        contracts_here = args.only == "bounds" and contracts_wanted
        violations, _ = run_bounds(strict=args.strict, names=names,
                                   progress=progress,
                                   contracts=contracts_here)
        for v in violations:
            if v.kernel.startswith("contract/"):
                print(f"CONTRACT FAIL {v}")
        if not args.quiet:
            print(f"bounds: {checked_box[0]} kernel(s) checked, "
                  f"{len(violations)} violation(s)")
        failures += len(violations)

    if args.only in (None, "values") and not skip_registry:
        from .registry import run_values

        vchecked_box = [0]

        def vprogress(name, violations):
            vchecked_box[0] += 1
            if violations:
                print(f"VALUE FAIL {name}: "
                      f"{len(violations)} violation(s)")
                for v in violations:
                    print(f"  {v}")
            elif not args.quiet:
                print(f"ok {name} (value)")

        violations, _ = run_values(strict=args.strict, names=names,
                                   progress=vprogress)
        if not args.quiet:
            print(f"values: {vchecked_box[0]} contract(s) checked, "
                  f"{len(violations)} violation(s)")
        failures += len(violations)

    dt = time.monotonic() - t0
    verdict = "CLEAN" if failures == 0 else f"{failures} FAILURE(S)"
    print(f"analysis: {verdict} in {dt:.1f}s")

    # refresh the staleness baseline only after a FULLY clean full-pass
    # run: a partial pass selection or any failure must leave the old
    # baseline in place so nothing is ever skipped past a failure
    if args.changed_only and failures == 0 and args.only is None \
            and state is not None:
        try:
            with open(_STATE_FILE, "w") as f:
                # the PRE-run snapshot: a module edited mid-run stays
                # stale and re-proves next time
                json.dump(state, f, indent=0, sort_keys=True)
        except OSError:
            pass  # read-only checkout: fast mode just stays cold
    return 0 if failures == 0 else 1


if __name__ == "__main__":
    sys.exit(main())
