"""Seeded known-bad kernel variants: the analyzer's self-test corpus.

A verifier that has never rejected anything is indistinguishable from
one that checks nothing.  This module builds registry Entries around
deliberately broken variants of the production kernels — each mirrors
the REAL kernel body (same helpers, same shapes, same constants) with
exactly ONE seeded defect — plus lint sources seeding the concurrency
and config bug classes.  `check_mutants()` asserts every mutant is
rejected by the pass that owns its bug class under `--strict`, and that
the value-class mutants are INVISIBLE to the interval pass alone:
those are precisely the bugs a bounds analysis cannot see, which is
why the value pass exists.

Bug classes (one Mutant each; `caught_by` names the owning pass):

  dropped-carry-lane    f32 mont_mul assembles the high half without
                        c_t (the t-mod-R carry into column L).  Every
                        limb still fits 16 bits -> bounds-CLEAN; the
                        product value is wrong whenever a*b's low half
                        overflows R.                caught_by: value
  skipped-carry-sweep   u32 mont_mul feeds raw uncarried product
                        columns (< 2^30) into the next column product:
                        u32 overflow.               caught_by: bounds
  off-by-one-limb-shift high half taken from mp_cols[l-1 : 2l-1]
                        instead of [l : 2l].  Sweeps still emit 16-bit
                        limbs -> bounds-clean; the value is shifted
                        garbage.                    caught_by: value
  wrong-modulus         Fr mont_mul built from a FieldSpec whose
                        modulus is p + 2^16 (with its own consistent
                        Montgomery inverse): a perfectly well-formed
                        reduction — for the wrong field.  Same limb
                        ranges -> bounds-clean.     caught_by: value
  swapped-twiddle       the n=32 NTT with its power table rotated one
                        lane: every gathered stage twiddle is stale.
                        Table entries are still canonical limbs ->
                        bounds-clean; the transform no longer matches
                        the poly oracle.            caught_by: value

Lint-side mutants (module constants, checked via lint.lint_source):
LOCK03_MUTANT (a two-class lock-order cycle -> deadlock) and
ENV01_MUTANT (a DPT_* knob read that is not in the constants.py
glossary).  tests/test_analysis.py drives all of this in tier-1.
"""

import numpy as np

from . import registry as R
from .bounds import limb_rows

U16 = (1 << 16) - 1


class Mutant:
    """One seeded defect: a registry Entry plus the pass that owns it.

    caught_by "value": Entry.check() (bounds) must be CLEAN and
    Entry.check_values() must reject.  caught_by "bounds":
    Entry.check() must reject."""

    def __init__(self, entry, caught_by, bug):
        self.entry = entry
        self.caught_by = caught_by
        self.bug = bug

    @property
    def name(self):
        return self.entry.name


def _mont_mul_f32_mutant(spec, a, b, drop_carry=False, off_by_one=False):
    """field_jax.mont_mul's f32/MXU branch, re-assembled from the real
    helpers, with one switchable defect.  With all switches off this IS
    the production body (kept that way so a mutant failure can't be an
    artifact of the harness drifting from the kernel)."""
    from ..backend import field_jax as FJ
    l = spec.n_limbs
    t_cols = FJ._mul_columns_f32(a, b, 2 * l)
    t_lo, c_t = FJ._carry_sweep(t_cols[:l])
    m_cols = FJ._mul_columns_const(spec.ninv_toeplitz, t_lo, l)
    m, _ = FJ._carry_sweep(m_cols)
    mp_cols = FJ._mul_columns_const(spec.mod_toeplitz, m, 2 * l)
    _, c_lo = FJ._carry_sweep(mp_cols[:l] + t_lo)
    hi_mp = mp_cols[l - 1:2 * l - 1] if off_by_one else mp_cols[l:]
    carry_in = c_lo if drop_carry else c_t + c_lo
    hi = (hi_mp + t_cols[l:]).at[0].add(carry_in)
    return FJ._cond_sub_mod(spec, hi)


def _wrong_modulus_spec():
    """An internally consistent FieldSpec for the WRONG prime: Fr's
    modulus nudged up one limb unit, with the matching -p^-1 mod R so
    the Montgomery algebra is flawless — only the field is wrong."""
    from ..backend import field_jax as FJ
    p_bad = FJ.FR.mod + (1 << 16)
    R = 1 << (16 * FJ.FR.n_limbs)
    inv_bad = pow((-p_bad) % R, -1, R)
    return FJ.FieldSpec("FrBad", p_bad, FJ.FR.n_limbs,
                        FJ.FR.mod, inv_bad)  # r2 unused by mont_mul


def _mont_mul_u32_skip_sweep(spec, a, b):
    """field_jax.mont_mul's u32 branch with the t-mod-R carry sweep
    skipped: raw product columns (< 2^30) flow into the m = t*(-p^-1)
    column product, whose u32 partial products then overflow."""
    from ..backend import field_jax as FJ
    l = spec.n_limbs
    t_cols = FJ._mul_columns_u32(a, b, 2 * l)
    t_lo = t_cols[:l]  # MUTANT: _carry_sweep skipped
    ninv = FJ._bcast_const(spec.ninv_limbs, a.ndim)
    m, _ = FJ._carry_sweep(FJ._mul_columns_u32(t_lo, ninv, l))
    p = FJ._bcast_const(spec.mod_limbs, a.ndim)
    mp_cols = FJ._mul_columns_u32(m, p, 2 * l)
    _, c_lo = FJ._carry_sweep(mp_cols[:l] + t_lo)
    hi = (mp_cols[l:] + t_cols[l:]).at[0].add(c_lo)
    return FJ._cond_sub_mod(spec, hi)


def _field_mutants():
    from ..backend import field_jax as FJ
    spec = FJ.FR
    l = spec.n_limbs
    pair = (limb_rows(l, 8), limb_rows(l, 8))
    limbs_out = [(0, U16)]

    def entry(name, fn, value=True):
        val = R._field_value(spec, "mont_mul", 2) if value else None
        return R.Entry(name, fn, pair, limbs_out, value=val)

    return [
        Mutant(entry("field/mutant_dropped_carry_lane_f32",
                     lambda a, b: _mont_mul_f32_mutant(
                         spec, a, b, drop_carry=True)),
               "value", "dropped-carry-lane"),
        Mutant(entry("field/mutant_skipped_carry_sweep_u32",
                     lambda a, b: _mont_mul_u32_skip_sweep(spec, a, b)),
               "bounds", "skipped-carry-sweep"),
        Mutant(entry("field/mutant_off_by_one_limb_shift_f32",
                     lambda a, b: _mont_mul_f32_mutant(
                         spec, a, b, off_by_one=True)),
               "value", "off-by-one-limb-shift"),
        Mutant(entry("field/mutant_wrong_modulus_f32",
                     lambda a, b, bad=_wrong_modulus_spec():
                     _mont_mul_f32_mutant(bad, a, b)),
               "value", "wrong-modulus"),
    ]


def _ntt_mutant():
    from ..backend import ntt_jax as NTT
    # fresh NttPlan, not get_plan: the mutated consts must not poison
    # the shared plan's memo
    plan = NTT.NttPlan(32)
    fn, consts = plan.traced_kernel(False, False, boundary="mont",
                                    radix=4)
    bad = {k: np.asarray(v) for k, v in consts.items()}
    bad["pow"] = np.roll(bad["pow"], 1, axis=1)  # MUTANT: stale twiddles
    entry = R.Entry("ntt/mutant_swapped_twiddle_n32", fn,
                    (limb_rows(16, 32), bad), [(0, U16)],
                    value=R._ntt_value(32, False, False, bad))
    return Mutant(entry, "value", "swapped-twiddle")


def build_mutants():
    """All seeded kernel mutants (list of Mutant)."""
    return _field_mutants() + [_ntt_mutant()]


def check_mutants(progress=None):
    """Run every mutant through both passes under --strict semantics and
    return a list of error strings — NON-EMPTY means the analyzer lost
    a bug class it is contractually able to catch (or a value-class
    mutant stopped being bounds-clean, i.e. the harness no longer
    demonstrates the interval pass's blind spot).  [] == the analyzer
    still rejects every seeded defect for the right reason."""
    errors = []
    for m in build_mutants():
        bounds_v = m.entry.check(strict=True)
        value_v = m.entry.check_values(strict=True)
        if m.caught_by == "bounds":
            if not bounds_v:
                errors.append(f"{m.name} ({m.bug}): bounds pass no "
                              f"longer rejects this mutant")
        else:
            if bounds_v:
                errors.append(
                    f"{m.name} ({m.bug}): expected bounds-clean (the "
                    f"interval pass cannot see this bug class) but got: "
                    f"{bounds_v[0]}")
            if not value_v:
                errors.append(f"{m.name} ({m.bug}): value pass no "
                              f"longer rejects this mutant")
        if progress is not None:
            progress(m, bounds_v, value_v)
    return errors


# -- lint-side mutants ---------------------------------------------------------

# Two classes, each calling into the other under its own lock: the
# classic AB/BA lock-order cycle LOCK03's graph closure must find.
LOCK03_MUTANT = '''
import threading


class Scheduler:
    def __init__(self, ledger):
        self._lock = threading.Lock()
        self.ledger = ledger
        self.active = 0

    def promote(self, job):
        with self._lock:
            self.active += 1
            self.ledger.record(job)   # MUTANT: held call into Ledger

    def drain(self):
        with self._lock:
            self.active = 0


class Ledger:
    def __init__(self, sched):
        self._lock = threading.Lock()
        self.sched = sched
        self.rows = 0

    def record(self, job):
        with self._lock:
            self.rows += 1

    def audit(self):
        with self._lock:
            self.sched.drain()        # back edge -> AB/BA cycle
'''

# Same classes with the back edge moved outside the lock: the cycle is
# broken, so LOCK03 must stay silent.
LOCK03_FIXED = LOCK03_MUTANT.replace(
    "        with self._lock:\n"
    "            self.sched.drain()        # back edge -> AB/BA cycle",
    "        with self._lock:\n"
    "            rows = self.rows\n"
    "        self.sched.drain()\n"
    "        return rows")

# A non-reentrant lock re-acquired through a held self-call: the
# single-class LOCK03 self-deadlock form.
LOCK03_SELF_MUTANT = '''
import threading


class Journal:
    def __init__(self):
        self._lock = threading.Lock()
        self.entries = 0

    def compact(self):
        with self._lock:
            self.truncate()           # MUTANT: re-acquires self._lock

    def truncate(self):
        with self._lock:
            self.entries = 0
'''

# A DPT_* knob read the constants.py glossary does not document.
ENV01_MUTANT = '''
import os


def fanout():
    return int(os.environ.get("DPT_MUTANT_UNDOCUMENTED_KNOB", "4"))
'''

# Glossary text that documents the knob: ENV01 must accept it (shape
# mirrors the real constants.py knob table: name column + >= 2 spaces).
ENV01_GLOSSARY = """Knobs:

    DPT_MUTANT_UNDOCUMENTED_KNOB  fan-out width (default 4).
"""
