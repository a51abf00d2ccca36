"""Static verifier: seeded mutants are caught, the production surface is
clean, and the CLI exit code tracks violations.

The mutants mirror the bug classes the verifier exists for:
- DROPPED CARRY SWEEP: uncarried columns flow into the next product ->
  u32 product overflow the interval pass must flag;
- WIDENED SHIFT: a byte-column recombine shifted past its headroom;
- PYTHON FLOAT in a traced kernel: silent f32 promotion;
- REMOVED LOCK: shared-state write outside the lock scope (AST lint);
- STALE JIT CACHE KEY: a cached trace depending on a non-key parameter.

analysis/mutants.py carries the VALUE-class corpus on top (dropped
carry lane, off-by-one limb shift, wrong modulus constant, swapped
twiddle table — each bounds-clean and rejected only by the value pass —
plus the lock-order-cycle and undocumented-knob lint sources); the
harness tests below assert every one of those is still rejected for
the right reason.

Each must produce >= 1 violation / finding; the real kernels and the
real repo must produce none (the `--strict` contract ci.sh analyze
enforces over the FULL registry — here a representative subset keeps
tier-1 cheap).
"""

import numpy as np
import pytest

import jax.numpy as jnp

from distributed_plonk_tpu.analysis import bounds as B
from distributed_plonk_tpu.analysis import lint as L
from distributed_plonk_tpu.analysis import registry as R
from distributed_plonk_tpu.analysis.__main__ import main as cli_main
from distributed_plonk_tpu.backend import field_jax as FJ

U16 = (1 << 16) - 1


# --- seeded kernel mutants (each must be caught) ------------------------------

def test_mutant_dropped_carry_sweep_is_caught():
    spec = FJ.FR
    l = spec.n_limbs

    def mont_mul_dropped_sweep(a, b):
        t_cols = FJ._mul_columns_u32(a, b, 2 * l)
        t_lo = t_cols[:l]  # MUTANT: carry sweep dropped
        ninv = FJ._bcast_const(spec.ninv_limbs, a.ndim)
        m, _ = FJ._carry_sweep(FJ._mul_columns_u32(t_lo, ninv, l))
        p = FJ._bcast_const(spec.mod_limbs, a.ndim)
        mp_cols = FJ._mul_columns_u32(m, p, 2 * l)
        _, c_lo = FJ._carry_sweep(mp_cols[:l] + t_lo)
        hi = (mp_cols[l:] + t_cols[l:]).at[0].add(c_lo)
        return FJ._cond_sub_mod(spec, hi)

    v = B.check_fn("mutant", mont_mul_dropped_sweep,
                   (B.limb_rows(l, 4), B.limb_rows(l, 4)))
    assert v and any("range exceeded" in x.message for x in v)


def test_mutant_widened_shift_is_caught():
    def combine_widened(col8):
        c = col8.astype(jnp.uint32)
        return c[0::2] + (c[1::2] << 16)  # MUTANT: << 8 widened to << 16

    v = B.check_fn("mutant", combine_widened,
                   (B.Bound((32, 4), jnp.float32, 0, 96 * 255 ** 2),))
    assert v and any("shift_left" == x.prim for x in v)


def test_mutant_python_float_is_caught():
    v = B.check_fn("mutant", lambda a: (a * 1.5).astype(jnp.uint32),
                   (B.limb_rows(16, 4),))
    assert v and any("integer-valued" in x.message for x in v)


def test_floor_remainder_chain_is_bounded_and_mutants_caught():
    """The pow2-rescale/floor provenance rules (the lazy-carry local
    rounds): the exact x - floor(x*2^-8)*256 remainder proves < 256,
    while (a) a mismatched restore base and (b) a non-pow2 scale are
    NOT granted the remainder bound / exactness."""
    import numpy as np_

    def local_round(cols):
        hi = jnp.floor(cols * np_.float32(1.0 / 256.0))
        return cols - hi * np_.float32(256.0)

    f32_in = (B.Bound((8, 4), jnp.float32, 0, 1 << 22),)
    assert B.check_fn("ok", local_round, f32_in,
                      out_bounds=[(0, 255)]) == []

    def wrong_base(cols):  # MUTANT: restores with 512, not 256
        hi = jnp.floor(cols * np_.float32(1.0 / 256.0))
        return cols - hi * np_.float32(512.0)

    v = B.check_fn("mutant", wrong_base, f32_in, out_bounds=[(0, 255)])
    assert v and any(x.prim == "output" for x in v)

    def not_pow2(cols):  # MUTANT: 1/320 scaling is NOT exact in f32
        return jnp.floor(cols * np_.float32(1.0 / 320.0))

    v = B.check_fn("mutant", not_pow2, f32_in)
    assert v and any("integer-valued" in x.message for x in v)


def test_mutant_unbounded_scan_carry_is_caught():
    from jax import lax

    def grows(v):
        def body(c, _):
            return c + v, None
        out, _ = lax.scan(body, v, None, length=8)
        return out

    v = B.check_fn("mutant", grows,
                   (B.Bound((4,), jnp.uint32, 0, 1 << 30),))
    assert v and any("stabilize" in x.message or "range exceeded"
                     in x.message for x in v)


def test_declared_output_bound_is_enforced():
    # a kernel that leaks 17-bit values violates the limb postcondition
    v = B.check_fn("mutant", lambda a: a + a,
                   (B.limb_rows(16, 4),), out_bounds=[(0, U16)])
    assert v and any(x.prim == "output" for x in v)


def test_mutant_pallas_stale_scratch_is_caught(monkeypatch):
    """Inside the fused bucket kernel's pallas_call jaxpr: dropping the
    group-product scratch zeroing (stale f32 columns accumulate across
    the ~12 products of an add AND across grid steps) must be flagged —
    the interpreter enters the kernel jaxpr, models the VMEM refs as
    interval cells, and runs the grid to a fixpoint."""
    import jax.numpy as jnp_
    from distributed_plonk_tpu.backend import curve_pallas as CP

    def band_no_zero(t_ref, a_bytes, b_bytes, w):  # MUTANT: no reset
        nb = a_bytes.shape[0]
        for i in range(nb):
            t_ref[i:i + nb, :w] += a_bytes[i][None, :] * b_bytes
        return t_ref[:, :w]

    monkeypatch.setattr(CP, "_band_mul_w", band_no_zero)
    entry = next(e for e in R.build_registry()
                 if e.name == "msm/bucket_pallas_signed_c7_packed")
    # the kernel wrapper is a module-level jit: drop its cached traces so
    # the mutant actually traces here and the clean suite re-traces after
    import jax
    jax.clear_caches()
    try:
        v = entry.check(strict=True)
    finally:
        jax.clear_caches()
    assert v and any("exactness" in x.message or "stabilize" in x.message
                     or "range exceeded" in x.message for x in v)


# --- AST lint mutants ---------------------------------------------------------

_LOCK_MUTANT = '''
import threading
class Cache:
    def __init__(self):
        self._lock = threading.Lock()
        self.entries = {}
    def put(self, k, v):
        with self._lock:
            self.entries[k] = v
    def evict_all(self):   # MUTANT: lock removed
        self.entries = {}
'''

_LOCK_CLEAN = _LOCK_MUTANT.replace(
    "    def evict_all(self):   # MUTANT: lock removed\n"
    "        self.entries = {}",
    "    def evict_all(self):\n"
    "        with self._lock:\n"
    "            self.entries = {}")

_JIT_MUTANT = '''
import jax
from functools import partial
class Kernels:
    def fn(self, n, width):
        if n not in self._fns:
            self._fns[n] = jax.jit(partial(extract, width=width))
        return self._fns[n]
'''

_PROM_MUTANT = "def k(x):\n    return x * 2.0\n"


def test_mutant_removed_lock_is_caught():
    f = L.lint_source(_LOCK_MUTANT)
    assert any(x.code == "LOCK01" for x in f)
    assert not L.lint_source(_LOCK_CLEAN)


def test_lock02_unlocked_write_vs_locked_read():
    src = '''
import threading
class Pool:
    def __init__(self):
        self._lock = threading.Lock()
        self.stopping = False
    def gate(self):
        with self._lock:
            return self.stopping
    def stop(self):
        self.stopping = True
'''
    f = L.lint_source(src)
    assert any(x.code == "LOCK02" for x in f)


def test_mutant_stale_jit_cache_key_is_caught():
    f = L.lint_source(_JIT_MUTANT)
    assert any(x.code == "JIT01" and "width" in x.message for x in f)
    # keying on width fixes it
    fixed = _JIT_MUTANT.replace("self._fns[n]",
                                "self._fns[(n, width)]")
    assert not L.lint_source(fixed)


def test_mutant_float_literal_lint_and_pragma():
    assert any(x.code == "PROM01" for x in L.lint_source(_PROM_MUTANT))
    suppressed = _PROM_MUTANT.replace(
        "x * 2.0", "x * 2.0  # analysis: ok(host-only scale)")
    assert not L.lint_source(suppressed)


def test_lock_held_helper_methods_do_not_false_positive():
    src = '''
import threading
class Store:
    def __init__(self):
        self._lock = threading.Lock()
        self.seq = 0
    def bump(self):
        self.seq += 1          # only ever called under the lock
    def put(self):
        with self._lock:
            self.bump()
'''
    assert not L.lint_source(src)


# --- seeded mutant harness (analysis/mutants.py) ------------------------------

from distributed_plonk_tpu.analysis import mutants as M


def test_mutant_harness_every_bug_class_rejected():
    """The ISSUE-19 acceptance gate: >= 5 distinct seeded kernel bug
    classes, each rejected under --strict by the pass that owns it —
    and each value-class mutant PROVEN bounds-clean, demonstrating the
    interval pass's blind spot is real (check_mutants errors on both
    kinds of drift)."""
    seen = []
    errors = M.check_mutants(progress=lambda m, bv, vv: seen.append(m))
    assert errors == []
    assert len(seen) >= 5
    assert len({m.bug for m in seen}) >= 5
    assert any(m.bug == "dropped-carry-lane" for m in seen)


def test_mutant_lock_order_cycle_is_caught():
    f = L.lint_source(M.LOCK03_MUTANT)
    assert any(x.code == "LOCK03" and "lock-order cycle" in x.message
               for x in f)
    # the same classes with the back edge hoisted out of the lock: the
    # cycle is broken and LOCK03 must stay silent
    fixed = L.lint_source(M.LOCK03_FIXED)
    assert not any(x.code == "LOCK03" for x in fixed)


def test_mutant_self_deadlock_is_caught():
    f = L.lint_source(M.LOCK03_SELF_MUTANT)
    assert any(x.code == "LOCK03" and "re-acquired" in x.message
               for x in f)
    # an RLock is re-entrant: the identical call shape is fine
    relock = M.LOCK03_SELF_MUTANT.replace("threading.Lock()",
                                          "threading.RLock()")
    assert not any(x.code == "LOCK03" for x in L.lint_source(relock))


def test_mutant_undocumented_knob_is_caught():
    f = L.lint_source(M.ENV01_MUTANT, kinds=("env",))
    assert any(x.code == "ENV01" and "DPT_MUTANT_UNDOCUMENTED_KNOB"
               in x.message for x in f)
    # documenting the knob in the glossary clears it
    assert not L.lint_source(M.ENV01_MUTANT, kinds=("env",),
                             knob_glossary_doc=M.ENV01_GLOSSARY)


def test_wildcard_knob_glossary_entries():
    doc = "Knobs:\n\n    DPT_TTL_*  per-class TTL overrides.\n"
    src = 'import os\nv = os.environ.get("DPT_TTL_GOLD_S")\n'
    assert not L.lint_source(src, kinds=("env",), knob_glossary_doc=doc)
    other = 'import os\nv = os.environ.get("DPT_OTHER")\n'
    assert any(x.code == "ENV01" for x in
               L.lint_source(other, kinds=("env",),
                             knob_glossary_doc=doc))


# --- carry contracts ----------------------------------------------------------

def test_carry_contracts_hold_for_both_fields():
    assert B.check_contracts() == []


def test_carry_contract_catches_bad_field_layout():
    # a modulus too large for its limb count breaks the 2p <= R claim
    class BadSpec:
        name = "Bad"
        mod = (1 << 255) + 1   # 2p > 2^256 = R at 16 limbs
        n_limbs = 16

    v = B.check_contracts(specs=(BadSpec,))
    assert v and any("cond_sub_fits" in x.kernel for x in v)


# --- the production surface is clean ------------------------------------------

def test_repo_lints_clean():
    assert [str(f) for f in L.run_lints()] == []


@pytest.mark.parametrize("subset", [
    ("field/fr_mont_mul", "field/carry_sweep", "field/fr_add"),
    ("ntt/n32_radix4_inv0_coset1_mont", "ntt/n32_radix2"),
    ("msm/digits_signed_c7_L66", "msm/bucket_scan_signed_onehot_packed"),
    ("msm/bucket_pallas_signed_c7_packed",),
    ("ntt/n32_radix4_batch3_coset", "field/fr_mont_mul_pallas_lazy"),
    ("curve/proj_add",),
])
def test_registry_subset_clean(subset):
    # the FULL registry is ci.sh analyze's job (~80 s); tier-1 proves a
    # representative slice of every kernel family stays clean
    seen = []
    violations, checked = R.run_bounds(
        strict=True, names=list(subset),
        progress=lambda name, v: seen.append(name))
    assert checked >= len(subset), (subset, seen)
    assert [str(v) for v in violations] == []


# --- CLI exit codes -----------------------------------------------------------

def test_cli_exit_zero_on_clean_lint_pass():
    assert cli_main(["--only", "lint", "-q"]) == 0


def test_cli_exit_nonzero_on_mutant_registry(monkeypatch):
    mutant = R.Entry("mutant/overflow", lambda a: a * a,
                     (B.Bound((4,), jnp.uint32, 0, 1 << 20),))
    monkeypatch.setattr(R, "build_registry", lambda: [mutant])
    assert cli_main(["--only", "bounds", "--strict", "-q"]) == 1
