"""Kernel autotuner + the KernelConfig resolution layer (ROADMAP dir. 4).

The two superlinear PLONK kernels (MSM, NTT) and the field multiplier
each grew several dispatchable variants (PRs 3/5/8): radix-2/4 XLA vs
fused Pallas stage cores, onehot/put bucket updates, f32/u32/MXU
multiplier paths, VMEM budgets, window width c, chunk budgets — all
selected by ~25 hand-set `DPT_*` env knobs tuned for one box. This
module replaces hand tuning with FFTW/ATLAS-style empirical
calibration: measure the concrete candidate space at the prover's real
launch shapes ONCE per machine, persist the winning configuration (a
`KernelPlan`), and load it forever after (store/calibration.py keys the
plan artifact by `machine_fingerprint()` so it warm-syncs to joining
workers like any other store artifact).

Two halves:

KernelConfig resolution layer (import-light — no jax/numpy at module
scope, so the host-oracle service can load a plan without touching
XLA). Precedence at every per-call `resolve()` site in
ntt_jax/ntt_pallas/msm_jax/msm_pallas/field_jax/field_pallas:

    explicit DPT_* env knob (or a test-patched module attr)
      > active KernelPlan cell          (nearest calibrated shape)
        > the built-in platform default (exact pre-autotune behavior)

so an operator's explicit knob is an OVERRIDE, not the primary
interface, and with no plan active every kernel path is bit- and
counter-identical to the pre-autotune tree. `set_active_plan` bumps a
process-wide revision that `cache_key()` folds into every kernel memo
key (NttPlan._fns, MsmContext chunk/calibration caches, the mesh/fleet
kernel caches) — a mid-process plan reload can therefore never serve a
compiled variant traced under the previous plan.

Autotuner: per (kind, domain_size) cell, enumerates candidates FROM THE
DISPATCH RESOLVERS THEMSELVES (each candidate is applied as a temporary
plan and read back through `_active_radix`/`_kernel_mode`/… — a
candidate the resolvers coerce elsewhere, e.g. one pinned by an env
knob or an unsupported platform, dedups onto what would actually run,
so the space cannot drift from what the kernels accept), measures each
at the real launch shape, and gates every winner on BIT-IDENTITY to the
parity core's output (radix-2 XLA NTT / XLA put bucket scan / u32
multiplier) — a fast-but-wrong candidate is rejected, never adopted.
MSM cells additionally record the measured adds/s rate, which
`MsmContext._chunk_lanes` reads back: chunk shapes are then identical
from the first call, so the AOT pass covers them and the PR 3/5
"post-calibration chunk shapes recompile at serve time" remainder
closes structurally.
"""

import contextlib
import hashlib
import json
import os
import platform
import threading
import time

PLAN_VERSION = 1


def machine_fingerprint():
    """Stable 12-hex id of what XLA:CPU AOT entries actually depend on:
    the architecture + CPU feature flags of this host. Shared by the
    persistent-compile-cache partitioning (field_jax re-exports it) and
    the calibration-plan artifact key — one identity for everything a
    machine compiles or measures."""
    cpu = ""
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith("flags"):
                    cpu = line
                    break
    except OSError:
        pass
    return hashlib.sha256(
        f"{platform.machine()}|{cpu}".encode()).hexdigest()[:12]


class KernelPlan:
    """A calibrated kernel configuration for one machine fingerprint.

    cells: {(kind, domain_size): {"params": {...}, ...}} with kind in
    ("ntt", "msm", "field"); params hold the winning knob values under
    the names the resolvers look up ("kernel", "radix", "vmem_mb",
    "rows", "bucket_update", "c", "group_max", "adds_per_s", "mul",
    "lane_tile"). JSON serialization is canonical (sorted keys), so a
    plan round-trips through the content-addressed store byte-for-byte.
    """

    def __init__(self, fingerprint, cells=None, meta=None):
        self.fingerprint = fingerprint
        self.cells = {}
        for key, cell in (cells or {}).items():
            if not isinstance(key, tuple):
                kind, _, size = key.partition(":")
                key = (kind, int(size))
            cell = dict(cell)
            if "params" not in cell:
                cell = {"params": cell}
            self.cells[(key[0], int(key[1]))] = cell
        self.meta = dict(meta or {})

    def cell(self, kind, n):
        return self.cells.get((kind, int(n)))

    def lookup(self, kind, param, n=None):
        """Winning value of `param` for `kind` at the calibrated cell
        nearest to domain size `n` (log2 distance, ties to the larger
        cell); n=None picks the largest calibrated cell — serving at
        scale favors the big-shape winner. None when uncalibrated."""
        sizes = [s for (k, s), c in self.cells.items()
                 if k == kind and param in c.get("params", {})]
        if not sizes:
            return None
        if n is None:
            size = max(sizes)
        else:
            nb = max(int(n), 1).bit_length()
            size = min(sizes,
                       key=lambda s: (abs(max(s, 1).bit_length() - nb), -s))
        return self.cells[(kind, size)]["params"][param]

    def to_json_bytes(self):
        cells = {f"{k}:{s}": c for (k, s), c in self.cells.items()}
        return json.dumps(
            {"version": PLAN_VERSION, "fingerprint": self.fingerprint,
             "meta": self.meta, "cells": cells},
            sort_keys=True, indent=1).encode()

    @classmethod
    def from_json_bytes(cls, blob):
        """Parse a stored plan; None for a foreign/future version (the
        caller recalibrates rather than misparsing)."""
        try:
            d = json.loads(blob.decode())
        except (ValueError, UnicodeDecodeError):
            return None
        if d.get("version") != PLAN_VERSION:
            return None
        return cls(d.get("fingerprint", ""), d.get("cells", {}),
                   d.get("meta", {}))


# --- active-plan registry (the per-process KernelConfig source) --------------

_plan_lock = threading.Lock()
_active_plan = None
_plan_revision = 0


def active_plan():
    return _active_plan


def plan_revision():
    """Monotonic counter bumped by every set_active_plan — folded into
    kernel memo keys via cache_key so plan reloads invalidate them."""
    return _plan_revision


def set_active_plan(plan):
    """Install `plan` (a KernelPlan, or None = knob-free defaults) as
    the process-wide KernelConfig source. Returns the new revision."""
    global _active_plan, _plan_revision
    with _plan_lock:
        _active_plan = plan
        _plan_revision += 1
        return _plan_revision


def cache_key(*parts):
    """THE shared kernel-memo cache-key helper: the resolved-mode parts
    plus the current plan revision. Every memo that caches a compiled
    variant keyed on resolved knobs (NttPlan._fns / _pallas_tabs,
    MsmContext._chunk_fns / _chunk_calls / _finish_fns / the adds-per-s
    calibration key, the mesh and fleet kernel caches) builds its key
    here, so a mid-process plan reload misses every stale entry instead
    of serving an executable traced under the previous plan (env knobs
    never change mid-process; plans do)."""
    return tuple(parts) + (_plan_revision,)


def plan_param(kind, param, n=None):
    """Active plan's winner for (kind, param) near domain size n, or
    None (no plan / uncalibrated). Lock-free read: CPython attribute
    loads are atomic and a racing reload just resolves one call on the
    outgoing plan, whose memo entries its revision bump already
    retired."""
    p = _active_plan
    if p is None:
        return None
    return p.lookup(kind, param, n)


def env_or_plan(env_name, kind, param, default, n=None, cast=None):
    """Per-call knob resolution for env-read knobs: explicit env wins,
    then the active plan, then the built-in default."""
    v = os.environ.get(env_name)
    if v is not None:
        return cast(v) if cast is not None else v
    p = plan_param(kind, param, n)
    if p is None:
        return default
    if cast is not None:
        try:
            return cast(p)
        except (TypeError, ValueError):
            # a malformed plan value must never break dispatch — fall
            # back to the built-in default (the plan is machine state,
            # not operator input; only explicit knobs may raise)
            return default
    return p


def attr_or_plan(attr_value, default_value, env_name, kind, param, n=None,
                 cast=None):
    """Per-call knob resolution for module-attr knobs (the env-latched,
    test/registry-patchable kind): the attr wins whenever it was pinned
    — the env var is set, or the attr was patched away from its
    built-in default — otherwise the active plan's winner, else the
    attr (which still holds the default)."""
    if attr_value != default_value or env_name in os.environ:
        return attr_value
    p = plan_param(kind, param, n)
    if p is None:
        return attr_value
    if cast is not None:
        try:
            return cast(p)
        except (TypeError, ValueError):
            # malformed plan value: keep the default (see env_or_plan)
            return attr_value
    return p


@contextlib.contextmanager
def plan_override(cells, fingerprint="override"):
    """Temporarily install a plan built from `cells` ({(kind, n):
    params}) — the Autotuner's candidate-application mechanism; env-
    pinned knobs still win (candidates are deduped against what the
    resolvers actually report). Restores the previous plan (and bumps
    the revision again) on exit."""
    prev = _active_plan
    set_active_plan(KernelPlan(fingerprint, dict(cells)))
    try:
        yield
    finally:
        set_active_plan(prev)


class _NullMetrics:
    def inc(self, name, by=1):
        pass

    def gauge(self, name, value):
        pass

    def observe(self, name, seconds):
        pass


# --- the autotuner -----------------------------------------------------------

class Autotuner:
    """Empirical per-cell calibration (see module docstring).

    shapes: evaluation-domain sizes (powers of two) to calibrate at —
    the REAL launch widths: the NTT cell measures the Montgomery-
    boundary kernel at (16, n); the MSM cell builds an (n + 3)-wide
    base context (the prover's blinded-handle widths are n+2/n+3) and
    commits an (n + 2)-wide Montgomery coefficient handle; the field
    cell measures a jitted mont_mul at (16, n) lanes.

    budget_s bounds the WHOLE run: once spent, remaining candidates and
    cells are skipped (cells already decided keep their winners; a cell
    whose parity reference never ran is simply absent — uncalibrated
    cells resolve to the built-in defaults, so a truncated run is
    always safe, just less tuned).
    """

    PARITY = {"ntt": {"kernel": "xla", "radix": 2},
              "msm": {"kernel": "xla", "bucket_update": "put"},
              "field": {"mul": "u32"}}

    def __init__(self, shapes, budget_s=None, metrics=None,
                 kinds=("ntt", "msm", "field"), seed=0xD7):
        self.shapes = sorted({int(s) for s in shapes})
        if budget_s is None:
            budget_s = float(os.environ.get("DPT_AUTOTUNE_BUDGET_S", "120"))
        self.budget_s = float(budget_s)
        self.metrics = metrics if metrics is not None else _NullMetrics()
        self.kinds = tuple(kinds)
        self.seed = seed
        self._deadline = None
        self._data = {}
        self._errors = {}  # "kind:n" -> [{candidate, error}] of this run

    # -- public entry ---------------------------------------------------------

    def run(self, aot=False):
        """Measure every cell within budget; returns the KernelPlan.
        aot=True additionally pre-lowers/compiles the winners' kernel
        variants (NttPlan.aot_compile / MsmContext.aot_compile) with
        the fresh plan ACTIVE, so the executables that land in the
        persistent compile cache are exactly the ones the plan will
        dispatch at serve time."""
        t0 = time.monotonic()
        self._deadline = t0 + self.budget_s
        self.metrics.inc("autotune_runs")
        plan = KernelPlan(machine_fingerprint())
        self._errors = {}
        for n in self.shapes:
            for kind in self.kinds:
                cell = self._tune_cell(kind, n)
                if cell is not None:
                    plan.cells[(kind, n)] = cell
                    self.metrics.inc("autotune_cells")
        plan.meta = {
            "created": round(time.time(), 3),
            "budget_s": self.budget_s,
            "run_s": round(time.monotonic() - t0, 3),
            "shapes": self.shapes,
            "platform": self._backend_platform(),
            "candidate_errors": {k: v for k, v in self._errors.items()
                                 if v},
        }
        if aot:
            prev = active_plan()
            set_active_plan(plan)
            try:
                plan.meta["aot"] = self._aot_winners(plan)
            finally:
                set_active_plan(prev)
        self.metrics.observe("autotune_run_s", time.monotonic() - t0)
        return plan

    # -- cell machinery -------------------------------------------------------

    def _out_of_budget(self):
        return self._deadline is not None \
            and time.monotonic() > self._deadline

    def _tune_cell(self, kind, n):
        """Measure one (kind, n) cell: parity core first (fixes the
        bit-identity reference), then the deduped candidate grid.
        Returns the cell record, or None (budget ran out before the
        reference, or nothing measured)."""
        if self._out_of_budget():
            return None
        candidates = [dict(self.PARITY[kind])] + self._candidates(kind, n)
        seen = set()
        rejected = set()
        measured = []  # (seconds, sig_tuple, resolved_params, aux)
        ref = None
        parity_s = None
        rejects = 0
        errors = self._errors.setdefault(f"{kind}:{n}", [])
        for cand in candidates:
            if ref is not None and self._out_of_budget():
                break
            resolved = self._resolved(kind, n, cand)
            sig = tuple(sorted(resolved.items()))
            if sig in seen:
                continue
            seen.add(sig)
            try:
                with plan_override({(kind, n): cand}):
                    out, dt, aux = self._run_candidate(kind, n, cand)
            except Exception as e:  # noqa: BLE001 - a candidate that
                # cannot build/trace/run loses its place in the grid,
                # not the calibration pass — but what it raised rides the
                # plan's meta["candidate_errors"], so a kernel the
                # compiler refuses is never a silent skip
                errors.append({"candidate": dict(cand), "error": repr(e)})
                self.metrics.inc("autotune_candidate_errors")
                if ref is None:
                    # the PARITY CORE itself failed: without a
                    # bit-identity reference no winner can be gated, and
                    # letting the next successful candidate become the
                    # reference would gate correct candidates against a
                    # possibly-wrong kernel — abandon the cell (defaults
                    # stay in force)
                    return None
                continue
            self.metrics.inc("autotune_measure_runs")
            if ref is None:
                # the first successful measurement is the parity core by
                # construction (candidates[0]); its output is the
                # reference every winner must match bit for bit
                ref = out
                parity_s = dt
            elif out != ref:
                rejects += 1
                rejected.add(sig)
                self.metrics.inc("autotune_parity_rejects")
                continue
            measured.append((dt, sig, resolved, aux))
        if not measured:
            return None
        measured.sort(key=lambda m: m[0])
        best_s, _sig, params, aux = measured[0]
        params = dict(params)
        params.update(aux or {})
        cell = {"params": params,
                "best_s": round(best_s, 6),
                "parity_s": round(parity_s, 6),
                "candidates": len(measured),
                "parity_rejects": rejects,
                "errors": len(errors)}
        # default_s: what the knob-free defaults would have run (the
        # resolved empty-candidate config) — the per-cell record of what
        # the plan is worth on this machine
        default_sig = tuple(sorted(self._resolved(kind, n, {}).items()))
        for dt, sig, _p, _a in measured:
            if sig == default_sig:
                cell["default_s"] = round(dt, 6)
                if best_s > 0:
                    cell["speedup_vs_default"] = round(dt / best_s, 3)
                break
        if "default_s" not in cell and default_sig not in rejected:
            # the knob-free default config was never measured (budget
            # truncation or a candidate error cut the grid short): an
            # undecided cell must NOT persist — its "winner" could be
            # just the slow parity reference, and a persisted plan would
            # then make every future start SLOWER than running with no
            # plan at all. (If the default was measured and REJECTED as
            # wrong, any bit-correct winner beats it — keep the cell.)
            return None
        return cell

    def _run_candidate(self, kind, n, cand):
        """Measure ONE candidate (already applied as the active plan by
        the caller): returns (output_bytes, seconds_per_call, aux_params).
        The single monkeypatch seam the parity-gate tests use."""
        if kind == "ntt":
            return self._run_ntt(n)
        if kind == "msm":
            return self._run_msm(n)
        return self._run_field(n)

    def _timed(self, fn, sync):
        """Warm (compile) once, then time `reps` calls; reps shrink to 1
        on slow platforms so calibration respects its budget."""
        t0 = time.perf_counter()
        out = fn()
        sync(out)
        warm_s = time.perf_counter() - t0
        reps = 1 if warm_s > 1.0 else 3
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn()
        sync(out)
        return out, (time.perf_counter() - t0) / reps

    # -- candidate grids ------------------------------------------------------

    @staticmethod
    def _msm_padded(n):
        """padded_n of the MSM context _run_msm actually measures: n + 3
        bases (the prover's blinded-handle width), padded even."""
        return (n + 3) + ((n + 3) % 2)

    def _backend_platform(self):
        import jax

        return jax.default_backend()

    def _pallas_ok(self, kind):
        """Pallas kernels join the candidate grid only where they can
        actually win. The fused multiplier: on TPU, where it compiles in
        seconds. The fused NTT and MSM kernels: not by platform — on the
        v5e one MSM shape takes Mosaic 387 s and the NTT kernel has not
        yet come back at all (CHANGES.md PR 21), either of which would
        eat a whole calibration budget in one uninterruptible compile.
        DPT_AUTOTUNE_INTERPRET=1 forces every kind in for harness tests
        (interpret mode is orders of magnitude off the XLA paths)."""
        if os.environ.get("DPT_AUTOTUNE_INTERPRET") == "1":
            return True
        return kind == "field" and self._backend_platform() == "tpu"

    def _candidates(self, kind, n):
        if kind == "ntt":
            from . import ntt_jax

            grid = [{"kernel": "xla", "radix": r}
                    for r in ntt_jax.RADIX_CHOICES]
            if self._pallas_ok(kind):
                for vmem in (2, 6, 12):
                    for rows in (16, 64):
                        grid.append({"kernel": "pallas", "radix": 4,
                                     "vmem_mb": vmem, "rows": rows})
            return grid
        if kind == "msm":
            from . import msm_jax

            grid = []
            kernels = ["xla"] + (["pallas"] if self._pallas_ok(kind)
                                 else [])
            # the measured context is (n + 3) bases padded even (the
            # prover's blinded-handle width; MsmContext.padded_n) —
            # c_batch applies from 256 padded points up. _resolved uses
            # the same width, so c candidates dedup iff the real context
            # would ignore c.
            wide = self._msm_padded(n) >= 256
            for kern in kernels:
                updates = msm_jax.BUCKET_UPDATE_CHOICES \
                    if kern == "xla" else ("onehot",)
                for up in updates:
                    for c in (msm_jax.C_CHOICES if wide else (None,)):
                        for gmax in (512, 1024):
                            cand = {"kernel": kern, "bucket_update": up,
                                    "group_max": gmax}
                            if c is not None:
                                cand["c"] = c
                            if kern == "pallas":
                                cand["vmem_mb"] = 6
                            grid.append(cand)
            return grid
        from . import field_jax as FJ

        grid = [{"mul": m} for m in ("f32", "u32")]
        if self._pallas_ok(kind):
            for tile in (256, 512, 1024):
                grid.append({"mul": "pallas", "lane_tile": tile})
        del FJ
        return grid

    def _resolved(self, kind, n, cand):
        """Read the candidate BACK through the dispatch resolvers (with
        the candidate applied as the plan): what would actually run.
        Env-pinned dimensions and platform coercions collapse here, so
        duplicate configurations are measured once and the plan records
        reality, not intent."""
        with plan_override({(kind, n): cand}):
            if kind == "ntt":
                from . import ntt_jax, ntt_pallas

                kern = ntt_jax._active_kernel(n=n)
                sig = {"kernel": kern,
                       "radix": ntt_jax._active_radix(n=n)}
                if kern == "pallas":
                    sig["vmem_mb"] = ntt_pallas._vmem_mb(n)
                    sig["rows"] = ntt_pallas._rows_knob(n)
                return sig
            if kind == "msm":
                from . import msm_jax

                kern = msm_jax._kernel_mode(n)
                sig = {"kernel": kern,
                       "group_max": msm_jax._group_max_knob(n)}
                if kern == "xla":
                    sig["bucket_update"] = "onehot" \
                        if msm_jax._use_onehot_update(n) else "put"
                else:
                    from . import msm_pallas

                    sig["vmem_mb"] = msm_pallas._vmem_mb()
                padded = self._msm_padded(n)
                if padded >= 256:
                    sig["c"] = msm_jax._c_batch_knob(padded)
                return sig
            from . import field_jax as FJ

            # mirror mont_mul's REAL dispatch order: the _use_pallas
            # gate (which also coerces a 'pallas' candidate below
            # _PALLAS_MIN_LANES back to the XLA path) first, then the
            # f32/u32 split — so a candidate the dispatch would coerce
            # dedups onto what actually runs instead of being measured
            # as a distinct (identical) configuration
            if FJ._use_pallas((FJ.FR.n_limbs, n)):
                mode = "pallas"
            else:
                mode = "f32" if FJ._f32_active(n) else "u32"
            sig = {"mul": mode}
            if mode == "pallas":
                from . import field_pallas as FP

                sig["lane_tile"] = FP.lane_tile()
            return sig

    # -- per-kind measurement -------------------------------------------------

    def _fr_mont_limbs(self, count, seed_off=0):
        import numpy as np

        from ..constants import FR_LIMBS, FR_MONT_R, R_MOD
        from .limbs import ints_to_limbs

        rng = np.random.default_rng(self.seed + seed_off)
        vals = rng.integers(1, 1 << 62, size=count, dtype=np.int64)
        return ints_to_limbs([int(v) * FR_MONT_R % R_MOD for v in vals],
                             FR_LIMBS)

    def _run_ntt(self, n):
        import numpy as np
        import jax.numpy as jnp

        from . import ntt_jax

        key = ("ntt", n)
        if key not in self._data:
            self._data[key] = jnp.asarray(self._fr_mont_limbs(n))
        v = self._data[key]
        plan = ntt_jax.get_plan(n)
        fn = plan.kernel(boundary="mont")
        out, dt = self._timed(lambda: fn(v),
                              lambda x: np.asarray(x[:, :1]))
        return np.asarray(out).tobytes(), dt, None

    def _run_msm(self, n):
        import numpy as np
        import jax.numpy as jnp

        from ..constants import G1_GEN_X, G1_GEN_Y
        from . import msm_jax

        key = ("msm", n)
        if key not in self._data:
            # real prover widths: an (n + 3)-wide key (the permutation
            # poly's blinded width), an (n + 2)-wide coefficient handle
            self._data[key] = (
                [(G1_GEN_X, G1_GEN_Y)] * (n + 3),
                jnp.asarray(self._fr_mont_limbs(n + 2, seed_off=1)))
        bases, handle = self._data[key]
        ctx = msm_jax.MsmContext(bases)
        pt, dt = self._timed(lambda: ctx.msm_mont_limbs(handle),
                             lambda x: None)
        aux = None
        if dt > 0:
            windows = -(-msm_jax.SCALAR_BITS // ctx.c_batch)
            aux = {"adds_per_s": round(windows * ctx.padded_n / dt, 1)}
        return repr(pt).encode(), dt, aux

    def _run_field(self, n):
        import numpy as np
        import jax

        from . import field_jax as FJ

        key = ("field", n)
        if key not in self._data:
            import jax.numpy as jnp

            self._data[key] = (jnp.asarray(self._fr_mont_limbs(n, 2)),
                               jnp.asarray(self._fr_mont_limbs(n, 3)))
        a, b = self._data[key]
        # a fresh jit wrapper per candidate: the mul-path branch is taken
        # at trace time, and reusing one wrapper would serve candidate
        # A's executable to candidate B at the same shape
        fn = jax.jit(lambda x, y: FJ.mont_mul(FJ.FR, x, y))
        out, dt = self._timed(lambda: fn(a, b),
                              lambda x: np.asarray(x[:, :1]))
        return np.asarray(out).tobytes(), dt, None

    # -- AOT ------------------------------------------------------------------

    def _aot_winners(self, plan):
        """Pre-lower/compile the winners' kernel variants (plan active —
        the caller set it) so the persistent compile cache holds exactly
        what serving will dispatch; executables land under whatever
        cache dir the process configured (the store-owned one for
        scripts/autotune.py and serve startup)."""
        from . import ntt_jax

        report = {}
        for (kind, n), _cell in sorted(plan.cells.items()):
            if self._out_of_budget():
                report["truncated"] = True
                break
            try:
                if kind == "ntt":
                    chunk = max(1, min(8, (1 << 21) // n))
                    report[f"ntt:{n}"] = ntt_jax.get_plan(n).aot_compile(
                        batch_sizes=(chunk,) if chunk > 1 else ())
                elif kind == "msm":
                    bases, _h = self._data.get(("msm", n), (None, None))
                    if bases is not None:
                        from . import msm_jax

                        ctx = msm_jax.MsmContext(bases)
                        report[f"msm:{n}"] = ctx.aot_compile(
                            batch_sizes=(1, 2),
                            digit_widths=(n + 2, n + 3))
            except Exception as e:  # noqa: BLE001 - AOT is an
                # accelerator, never a calibration failure
                report[f"{kind}:{n}"] = {"error": repr(e)}
        return report
