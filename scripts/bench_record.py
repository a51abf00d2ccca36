"""Normalized bench trajectory records + the regression tolerance table.

The perf history used to be shape-inconsistent: `BENCH_r*.json` wraps the
bench line under `parsed` with driver fields around it, `bench_artifacts/`
holds per-tool one-off files, and nothing downstream could diff runs
without knowing every historical format. This module is the fix (ISSUE 15
satellite): ONE schema-versioned record per run,

    {"schema": 1, "source": "bench", "run": 5, "ts": ..., "basis": "chip",
     "keys": {"proofs_per_s": 1.38, "fleet_heal_s": 2.3, ...}}

appended as one JSONL line to `bench_artifacts/trajectory.jsonl` by
bench.py and scripts/add_bench.py at the end of every run, and read back
by scripts/bench_compare.py (which also knows how to normalize the legacy
BENCH_r*.json files, so the committed history stays comparable).

Basis awareness is part of the schema: "chip" lines (the device probe
passed) are only ever compared against chip lines, "degraded" (host-CPU
fallback) against degraded — a run without the chip must never read as a
10x kernel regression.

The WATCH table is the per-key regression contract: direction + relative
tolerance for every key the gate cares about. Tolerances are deliberately
loose on wall-clock keys (host-basis timings on a loaded 1-core box swing
hard) and tight on booleans (a canary flipping false is always loud).
"""

import fnmatch
import json
import os
import time

SCHEMA = 1
TRAJECTORY = os.path.join("bench_artifacts", "trajectory.jsonl")

# keys that never carry perf information (driver bookkeeping, error text)
_SKIP_KEYS = {"metric", "unit", "degraded", "schema", "n", "cmd", "rc"}


def _flatten(obj, prefix="", out=None):
    """Nested dicts -> {"a/b": v} with only numeric/bool leaves kept."""
    if out is None:
        out = {}
    for k, v in obj.items():
        if k in _SKIP_KEYS and not prefix:
            continue
        name = f"{prefix}{k}"
        if isinstance(v, dict):
            _flatten(v, prefix=name + "/", out=out)
        elif isinstance(v, bool):
            out[name] = v
        elif isinstance(v, (int, float)) and v is not None:
            out[name] = v
    return out


def basis_of(data):
    """"chip" | "degraded" for one bench-line dict (the device probe
    verdict is the `degraded` flag bench.py stamps); tool lines
    (add_bench) carry an explicit jax backend name instead."""
    if data.get("degraded"):
        return "degraded"
    backend = data.get("backend")
    if isinstance(backend, str) and backend != "tpu":
        return "degraded"
    return "chip"


def normalize(source, data, run=None, ts=None):
    """One bench-line dict (bench.py's printed JSON, add_bench's results,
    a legacy BENCH_r*.json `parsed` payload) -> the schema-1 record."""
    keys = _flatten(data)
    # the headline metric/value pair becomes a stable key so the gate
    # can watch it across runs without knowing each run's metric name
    metric, value = data.get("metric"), data.get("value")
    if metric and isinstance(value, (int, float)) \
            and not isinstance(value, bool):
        keys[f"headline/{metric}"] = value
    return {"schema": SCHEMA, "source": source, "run": run,
            "ts": round(ts if ts is not None else time.time(), 3),
            "basis": basis_of(data), "keys": keys}


def append(record, repo=None, path=None):
    """Append one record to the trajectory (one JSON line); best-effort —
    a read-only checkout must not fail the bench."""
    path = path or os.path.join(repo or os.getcwd(), TRAJECTORY)
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "a") as f:
            f.write(json.dumps(record, separators=(",", ":"),
                               sort_keys=True) + "\n")
        return path
    except OSError:
        return None


def load_trajectory(repo):
    """All history, oldest first: legacy BENCH_r*.json (normalized) then
    trajectory.jsonl records. Unparseable entries are skipped — the
    compare gate must never crash on a foreign line."""
    records = []
    names = sorted(n for n in os.listdir(repo)
                   if n.startswith("BENCH_") and n.endswith(".json"))
    for name in names:
        try:
            with open(os.path.join(repo, name)) as f:
                wrap = json.load(f)
            parsed = wrap.get("parsed")
            if isinstance(parsed, dict):
                records.append(normalize("bench", parsed,
                                         run=wrap.get("n"), ts=0))
        except (OSError, ValueError):
            continue
    path = os.path.join(repo, TRAJECTORY)
    if os.path.exists(path):
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                if isinstance(rec, dict) and rec.get("schema") == SCHEMA \
                        and isinstance(rec.get("keys"), dict):
                    records.append(rec)
    return records


# -- the per-key regression contract ------------------------------------------
# (direction, relative tolerance): "higher" keys may not DROP by more
# than tol (fraction of the previous value), "lower" keys may not GROW
# by more than tol, "true" keys must stay truthy. First match wins, so
# the specific per-key entries come before the pattern families (note
# "*_per_s" must be matched before the "*_s" family catches it).

WATCH = [
    # canary booleans: flipping false is a regression at ANY tolerance
    ("analysis_clean", ("true", 0)),
    ("service_verified", ("true", 0)),
    ("service_warm_done", ("true", 0)),
    ("service_restart_recovery_ok", ("true", 0)),
    ("fleet_chaos_proof_ok", ("true", 0)),
    ("fleet_healed_ok", ("true", 0)),
    ("sdc_detected_ok", ("true", 0)),
    ("batch_prove_byte_identical", ("true", 0)),
    ("self_verify_bytes_identical", ("true", 0)),
    ("trace_ctx_adopted", ("true", 0)),
    ("autoscale_canary_ok", ("true", 0)),
    ("aggregate_ok", ("true", 0)),
    ("pipeline_byte_identical", ("true", 0)),
    # serving throughput + kernel A/Bs (ratios are basis-stable)
    ("pipeline_speedup_vs_lockstep", ("higher", 0.4)),
    ("pipelined_proofs_per_s", ("higher", 0.5)),
    ("proofs_per_s", ("higher", 0.5)),
    ("batch_prove_speedup_vs_sequential", ("higher", 0.4)),
    ("aggregate_verify_speedup_vs_sequential", ("higher", 0.5)),
    ("autotune_speedup_vs_defaults", ("higher", 0.5)),
    ("ntt_radix4_speedup_vs_radix2", ("higher", 0.5)),
    ("*_vs_host_oracle", ("higher", 0.5)),
    ("vs_baseline", ("higher", 0.5)),
    ("*_per_s", ("higher", 0.5)),
    ("mfu_*", ("higher", 0.5)),
    ("f32_fma_tflops_measured", ("higher", 0.5)),
    # robustness canaries: heal/recovery latencies (host-noisy: loose)
    ("fleet_heal_s", ("lower", 1.5)),
    ("sdc_heal_s", ("lower", 1.5)),
    ("fleet_chaos_s", ("lower", 1.5)),
    ("self_verify_overhead_pct", ("lower", 1.0)),
    ("service_roundtrip_warm_s", ("lower", 1.5)),
    ("slo_p95_standard_s", ("lower", 1.5)),
    ("headline/prove_2p13_wall_clock", ("lower", 0.5)),
    ("headline/*_throughput", ("higher", 0.5)),
]


def watch_rule(key):
    for pat, rule in WATCH:
        if fnmatch.fnmatchcase(key, pat):
            return rule
    return None


def compare(prev, cur, scale=1.0):
    """Regressions of `cur` vs `prev` (two schema-1 records of the SAME
    basis): [{key, prev, cur, change, tol, direction}]. Keys absent from
    either side, or outside the WATCH table, are skipped — the gate only
    speaks where the contract does."""
    out = []
    pk, ck = prev.get("keys") or {}, cur.get("keys") or {}
    for key, cv in sorted(ck.items()):
        rule = watch_rule(key)
        if rule is None or key not in pk:
            continue
        direction, tol = rule
        pv = pk[key]
        tol = tol * scale
        if direction == "true":
            if bool(pv) and not bool(cv):
                out.append({"key": key, "prev": pv, "cur": cv,
                            "change": "flipped false", "tol": 0,
                            "direction": direction})
            continue
        if isinstance(pv, bool) or isinstance(cv, bool) \
                or not isinstance(pv, (int, float)) \
                or not isinstance(cv, (int, float)) or pv == 0:
            continue
        rel = (cv - pv) / abs(pv)
        if direction == "higher" and rel < -tol:
            out.append({"key": key, "prev": pv, "cur": cv,
                        "change": round(rel, 4), "tol": tol,
                        "direction": direction})
        elif direction == "lower" and rel > tol:
            out.append({"key": key, "prev": pv, "cur": cv,
                        "change": round(rel, 4), "tol": tol,
                        "direction": direction})
    return out


def latest_of_basis(records, basis, before=None, source=None):
    """Most recent record of `basis` (optionally excluding the tail
    element `before` compares against). With `source`, only records of
    that source pair — a loadgen soak line and a bench line share no
    watched keys, so letting one shadow the other's predecessor would
    make the gate vacuous."""
    pool = records if before is None else records[:before]
    for rec in reversed(pool):
        if rec.get("basis") == basis and \
                (source is None or rec.get("source") == source):
            return rec
    return None
