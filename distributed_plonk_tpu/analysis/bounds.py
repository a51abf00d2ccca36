"""Jaxpr abstract interpretation: per-value integer magnitude intervals.

The kernel half of the static verifier (`python -m
distributed_plonk_tpu.analysis`). Every hot kernel in this prover is
correct only under hand-reasoned magnitude bounds — 16x16-bit limb
products fit a uint32, byte-column sums stay exact in f32, carry sweeps
receive values that fit their limb count. This module re-derives those
bounds mechanically: it traces a kernel with `jax.make_jaxpr` at
representative shapes and pushes an interval `[lo, hi]` per traced value
through the primitive vocabulary the kernels use, reporting a violation
wherever

  (a) an integer op's true-math result can leave its dtype's range
      (silent modular wraparound — the overflow class a dropped carry
      sweep or widened shift introduces),
  (b) a float value can stop being an exactly-represented integer
      (f32 values must stay < 2^24, bf16 operands < 2^8, and float
      inputs must be integer-valued — the exactness contract the
      MXU/byte-product multiplier path rests on), or
  (c) a forbidden dtype appears (f64/x64: nothing in the limb pipeline
      may silently promote), or a declared output bound is exceeded.

Control flow: `lax.scan` / `lax.while_loop` bodies are interpreted to a
carry fixpoint (join-until-stable, bounded iterations) — a carry whose
bound keeps growing is itself reported (`scan carry bounds do not
stabilize`). `jit` / custom-call wrappers are entered transparently.

Pallas kernels: a `pallas_call` eqn is entered too — the kernel IS a
jaxpr. Every input/output/scratch ref becomes one interval cell
(_RefCell: full-coverage writes replace, partial writes join,
read-before-any-write is the full dtype range), `pl.when` branches run
from a shared entry state and join their exits, `program_id` is bounded
by the enclosing grid, and the grid itself is a join-until-stable
fixpoint (VMEM scratch persists across grid steps exactly like a scan
carry). Outputs take their cells' stabilized bounds.

Precision notes (sound, documented weakenings):
- Intervals collapse array extent: one `[lo, hi]` per value, with exact
  intervals for concrete constants (twiddle/exponent tables).
- The one-hot bucket gather (`sum(where(dg == iota, plane, 0), axis)`)
  is recognized structurally — eq-against-iota yields a mask with at
  most one hit per reduced lane, so the masked sum's bound is the
  plane's bound, not plane * buckets.
- `scatter-add` assumes each output element receives at most one
  update (true for the kernels' `.at[idx].add` uses: unique indices).

What intervals cannot prove — that a *value spread across limb columns*
fits its limb count (the zero-carry-out claims of `_carry_sweep`
callers rest on modular number theory: `v < 2p <= R`, `(t + m*p)/R <
2p`) — is promoted instead into `field_jax.CARRY_CONTRACTS`, explicit
inequalities over the actual field constants that `check_contracts`
evaluates for every spec. Together: intervals prove no op overflows for
ANY input the declared bounds admit; the contracts prove the documented
zero-carry side conditions hold for these moduli.
"""

import math

import numpy as np

import jax
import jax.extend.core
import jax.numpy as jnp


# dtypes whose appearance anywhere in a kernel trace is a violation:
# the limb pipeline is 32-bit; an x64 or double promotion is always an
# accident (jax x64 is globally off, but a trace-level check catches a
# kernel that flips it or a numpy f64 constant leaking in)
_FORBIDDEN_DTYPES = {"float64", "int64", "uint64", "complex64", "complex128"}

# largest integer magnitude each float dtype represents EXACTLY
# (2^mantissa_bits); values at or under this bound round-trip, so
# integer arithmetic staged through these dtypes stays exact as long as
# every intermediate (including dot_general accumulations) fits
_FLOAT_EXACT_MAX = {
    "float32": 1 << 24,
    "bfloat16": 1 << 8,
    "float16": 1 << 11,
}


def _dtype_range(dtype):
    d = np.dtype(dtype)
    if d.kind == "b":
        return 0, 1
    if d.kind in "ui":
        info = np.iinfo(d)
        return int(info.min), int(info.max)
    return -math.inf, math.inf


class AbsVal:
    """Abstract value: dtype + magnitude interval + exactness/shape tags.

    lo/hi are Python ints (or +-inf / floats for float dtypes) bounding
    every element. `exact` means "provably an exactly-represented
    integer" (always true for int/bool dtypes; tracked for floats).
    `bcast_axes` are axes along which the value is known constant;
    `iota_axis` marks a broadcasted_iota; `onehot_axes` are axes along
    which at most one element is nonzero (everything else exactly 0).
    `zero` marks a provably all-zero value.
    """

    __slots__ = ("dtype", "shape", "lo", "hi", "exact",
                 "bcast_axes", "iota_axis", "onehot_axes", "pow2",
                 "anchor", "anchor_kind")

    def __init__(self, dtype, shape, lo, hi, exact=True,
                 bcast_axes=frozenset(), iota_axis=None,
                 onehot_axes=frozenset(), pow2=0):
        self.dtype = np.dtype(dtype)
        self.shape = tuple(shape)
        self.lo = lo
        self.hi = hi
        self.exact = exact
        self.bcast_axes = frozenset(bcast_axes)
        self.iota_axis = iota_axis
        self.onehot_axes = frozenset(onehot_axes)
        # pow2 < 0: the value is m * 2^pow2 with m an exactly-represented
        # f32 integer — an exponent-only rescale of an exact value (the
        # lazy-carry kernels' cols * 2^-8). anchor/anchor_kind track the
        # x -> x*2^-k -> floor -> *2^k -> x - that remainder chain (the
        # lazy local rounds' base-2^k digit split, which plain interval
        # arithmetic cannot bound below 2^k): "scaled" = x * 2^-k,
        # "floordiv" = floor(x * 2^-k), "floormul" = floor(x * 2^-k)*2^k,
        # each anchored to id(x). Every rule that constructs a fresh
        # AbsVal drops the tags (conservative, sound).
        self.pow2 = pow2
        self.anchor = None
        self.anchor_kind = None

    @property
    def zero(self):
        return self.lo == 0 and self.hi == 0

    def __repr__(self):
        return (f"AbsVal({self.dtype}, {self.shape}, "
                f"[{self.lo}, {self.hi}], exact={self.exact})")


def from_concrete(x):
    """AbsVal of a concrete numpy array / scalar (exact interval)."""
    a = np.asarray(x)
    if a.size == 0:
        lo, hi = 0, 0
    elif a.dtype.kind == "b":
        lo, hi = int(a.min()), int(a.max())
    elif a.dtype.kind in "ui":
        lo, hi = int(a.min()), int(a.max())
    else:
        lo, hi = float(a.min()), float(a.max())
    exact = True
    if a.dtype.kind == "f" and a.size:
        exact = bool(np.all(a == np.floor(a)))
    return AbsVal(a.dtype, a.shape, lo, hi, exact=exact)


class Bound:
    """Declared input interval for a traced argument: shape + dtype +
    [lo, hi] over every element (the kernel's documented precondition,
    e.g. '16-bit limb rows' = Bound(shape, uint32, 0, 2**16 - 1))."""

    def __init__(self, shape, dtype, lo, hi):
        self.shape = tuple(shape)
        self.dtype = np.dtype(dtype)
        self.lo = lo
        self.hi = hi

    def absval(self):
        return AbsVal(self.dtype, self.shape, self.lo, self.hi)

    def spec(self):
        return jax.ShapeDtypeStruct(self.shape, self.dtype)


def limb_rows(*shape):
    """16-bit limb array bound (the standard kernel input contract)."""
    return Bound(shape, jnp.uint32, 0, (1 << 16) - 1)


class _RefCell:
    """Abstract state of one Pallas ref (input block / output block /
    VMEM scratch): a single interval covering every element the ref has
    ever held, or BOTTOM (None) before the first write. A full-coverage
    write replaces the interval (strong update); a partial write joins
    (the untouched region keeps its old bound); a partial write to
    BOTTOM widens to the full dtype range — sound for kernels that may
    read what they never wrote."""

    __slots__ = ("dtype", "shape", "val")

    def __init__(self, dtype, shape, val=None):
        self.dtype = np.dtype(dtype)
        self.shape = tuple(shape)
        self.val = val  # AbsVal or None (= bottom / uninitialized)

    def read(self, dtype, shape):
        if self.val is None:
            lo, hi = _dtype_range(dtype)
            return AbsVal(dtype, shape, lo, hi,
                          exact=np.dtype(dtype).kind != "f")
        return AbsVal(dtype, shape, self.val.lo, self.val.hi,
                      exact=self.val.exact)

    def write(self, val, full):
        norm = AbsVal(self.dtype, self.shape, val.lo, val.hi,
                      exact=val.exact)
        if full:
            self.val = norm
        elif self.val is None:
            lo, hi = _dtype_range(self.dtype)
            self.val = AbsVal(self.dtype, self.shape, lo, hi,
                              exact=self.dtype.kind != "f")
        else:
            self.val = _join(self.val, norm)


class Violation:
    def __init__(self, kernel, prim, message, where=""):
        self.kernel = kernel
        self.prim = prim
        self.message = message
        self.where = where

    def __str__(self):
        loc = f" @ {self.where}" if self.where else ""
        return f"[{self.kernel}] {self.prim}: {self.message}{loc}"


def _source_of(eqn):
    try:
        from jax._src import source_info_util
        return source_info_util.summarize(eqn.source_info)
    except Exception:  # pragma: no cover - jax internals moved
        return ""


def _join(a, b):
    """Least upper bound of two AbsVals of one variable (same aval)."""
    return AbsVal(a.dtype, a.shape, min(a.lo, b.lo), max(a.hi, b.hi),
                  exact=a.exact and b.exact,
                  bcast_axes=a.bcast_axes & b.bcast_axes,
                  iota_axis=a.iota_axis if a.iota_axis == b.iota_axis
                  else None,
                  onehot_axes=a.onehot_axes & b.onehot_axes,
                  pow2=a.pow2 if a.pow2 == b.pow2 else 0)


def _pow2_exponent(v):
    """k if v is a single-valued positive power-of-two constant 2^k,
    else None (the exact-rescale side condition of the mul rule)."""
    if v.lo != v.hi or not v.lo > 0:
        return None
    m, e = math.frexp(float(v.lo))
    return e - 1 if m == 0.5 else None


def _stable(prev, new):
    return new.lo >= prev.lo and new.hi <= prev.hi


# primitives that only move data (intervals and exactness pass through
# unchanged; structural tags are dropped conservatively)
_SHAPE_ONLY = {
    "reshape", "transpose", "squeeze", "expand_dims", "rev", "slice",
    "dynamic_slice", "copy", "stop_gradient", "gather", "real",
    "reduce_max", "reduce_min", "device_put", "sharding_constraint",
    "optimization_barrier", "reduce_precision", "dynamic_update_slice",
    "sort", "pad", "concatenate",
}

# calls to enter transparently (sub-jaxpr under params['jaxpr'] or
# params['call_jaxpr'])
_CALL_PRIMS = {"jit", "closed_call", "core_call", "custom_jvp_call",
               "custom_vjp_call", "custom_vjp_call_jaxpr", "remat",
               "checkpoint", "named_call"}

_MAX_FIXPOINT_ITERS = 8


class Interpreter:
    def __init__(self, kernel_name, strict=True):
        self.kernel = kernel_name
        self.strict = strict
        self.violations = []
        self.warnings = []
        self._check = True  # False while searching for a loop fixpoint
        self._grids = []    # enclosing pallas_call grids (program_id bound)

    # -- reporting ------------------------------------------------------------

    def _flag(self, eqn, msg):
        if self._check:
            self.violations.append(
                Violation(self.kernel, eqn.primitive.name, msg,
                          _source_of(eqn)))

    def _warn(self, eqn, msg):
        if self._check:
            self.warnings.append(
                Violation(self.kernel, eqn.primitive.name, msg,
                          _source_of(eqn)))

    # -- environment ----------------------------------------------------------

    def _read(self, env, var):
        if isinstance(var, jax.extend.core.Literal):
            return from_concrete(var.val)
        return env[var]

    def _out(self, eqn, i=0):
        aval = eqn.outvars[i].aval
        return aval.dtype, tuple(aval.shape)

    def _mk(self, eqn, lo, hi, exact=True, i=0, **tags):
        dtype, shape = self._out(eqn, i)
        return AbsVal(dtype, shape, lo, hi, exact=exact, **tags)

    # -- dtype / overflow checks ----------------------------------------------

    def _check_dtype(self, eqn, v):
        if v.dtype.name in _FORBIDDEN_DTYPES:
            self._flag(eqn, f"forbidden dtype {v.dtype.name} "
                            "(x64/double promotion in an integer kernel)")

    def _arith_result(self, eqn, lo, hi, exact_in=True, i=0):
        """Bound-check an arithmetic result against its dtype and return
        the (possibly clamped) AbsVal."""
        dtype, shape = self._out(eqn, i)
        d = np.dtype(dtype)
        self._check_dtype(eqn, AbsVal(dtype, shape, lo, hi))
        if d.kind in "uib":
            dlo, dhi = _dtype_range(d)
            if hi > dhi or lo < dlo:
                self._flag(eqn, f"{d.name} range exceeded: result in "
                                f"[{lo}, {hi}] vs dtype [{dlo}, {dhi}] "
                                "(silent modular wraparound)")
                return AbsVal(dtype, shape, max(lo, dlo),
                              min(hi, dhi))
            return AbsVal(dtype, shape, lo, hi)
        # float result: must remain an exactly-representable integer
        exact_max = _FLOAT_EXACT_MAX.get(d.name)
        exact = exact_in
        if not exact_in:
            self._flag(eqn, f"{d.name} value is not provably integer-"
                            "valued (float contamination in an integer "
                            "kernel)")
        elif exact_max is not None and max(abs(lo), abs(hi)) > exact_max:
            self._flag(eqn, f"{d.name} exactness lost: |result| can reach "
                            f"{max(abs(lo), abs(hi))} > {exact_max} "
                            f"(2^{exact_max.bit_length() - 1} integer "
                            "round-trip bound)")
            exact = False
        return AbsVal(dtype, shape, lo, hi, exact=exact)

    # -- the interpreter ------------------------------------------------------

    def run(self, closed_jaxpr, in_vals):
        """Interpret a ClosedJaxpr given AbsVals for its invars; returns
        AbsVals for its outvars."""
        jaxpr = closed_jaxpr.jaxpr
        env = {}
        for var, const in zip(jaxpr.constvars, closed_jaxpr.consts):
            env[var] = from_concrete(const)
        assert len(jaxpr.invars) == len(in_vals), \
            (len(jaxpr.invars), len(in_vals))
        for var, val in zip(jaxpr.invars, in_vals):
            env[var] = val
        self._run_eqns(jaxpr.eqns, env)
        return [self._read(env, v) for v in jaxpr.outvars]

    def _run_eqns(self, eqns, env):
        for eqn in eqns:
            ins = [self._read(env, v) for v in eqn.invars]
            outs = self._eqn(eqn, ins, env)
            if not isinstance(outs, (list, tuple)):
                outs = [outs]
            for var, val in zip(eqn.outvars, outs):
                if isinstance(val, AbsVal):
                    self._check_dtype(eqn, val)
                env[var] = val

    def _subjaxpr(self, eqn):
        p = eqn.params
        sub = p.get("jaxpr") or p.get("call_jaxpr") or p.get("fun_jaxpr")
        if sub is None and "branches" in p:
            return None
        if sub is not None and not hasattr(sub, "consts"):
            sub = jax.extend.core.ClosedJaxpr(sub, ())
        return sub

    def _eqn(self, eqn, ins, env):
        name = eqn.primitive.name

        if name in _CALL_PRIMS:
            sub = self._subjaxpr(eqn)
            if sub is None:
                return self._fallback(eqn, ins)
            n = len(sub.jaxpr.invars)
            return self.run(sub, ins[len(ins) - n:])

        if name == "scan":
            return self._scan(eqn, ins)
        if name == "while":
            return self._while(eqn, ins)
        if name == "cond":
            return self._cond(eqn, ins)

        handler = getattr(self, "_p_" + name.replace("-", "_"), None)
        if handler is not None:
            return handler(eqn, ins)
        if name in _SHAPE_ONLY:
            return self._shape_only(eqn, ins)
        return self._fallback(eqn, ins)

    def _fallback(self, eqn, ins):
        """Unknown primitive: full dtype range (sound), and in strict
        mode a violation — silent imprecision would let a kernel rewrite
        smuggle an unvetted op past the verifier."""
        msg = (f"unhandled primitive '{eqn.primitive.name}' "
               "(add a transfer rule to analysis/bounds.py)")
        if self.strict:
            self._flag(eqn, msg)
        else:
            self._warn(eqn, msg)
        outs = []
        for i in range(len(eqn.outvars)):
            dtype, shape = self._out(eqn, i)
            lo, hi = _dtype_range(dtype)
            outs.append(AbsVal(dtype, shape, lo, hi,
                               exact=np.dtype(dtype).kind != "f"))
        return outs

    def _shape_only(self, eqn, ins):
        v = ins[0]
        dtype, shape = self._out(eqn)
        lo, hi = v.lo, v.hi
        exact = v.exact
        if eqn.primitive.name in ("pad", "concatenate",
                                  "dynamic_update_slice", "sort"):
            for o in ins[1:]:
                lo, hi = min(lo, o.lo), max(hi, o.hi)
                exact = exact and o.exact
        outs = [AbsVal(dtype, shape, lo, hi, exact=exact)]
        # extra outputs (e.g. argsort's index operand through `sort`)
        # need not share the data interval: full dtype range, sound
        for i in range(1, len(eqn.outvars)):
            d, s = self._out(eqn, i)
            dlo, dhi = _dtype_range(d)
            outs.append(AbsVal(d, s, dlo, dhi,
                               exact=np.dtype(d).kind != "f"))
        return outs

    # -- elementwise arithmetic -----------------------------------------------

    def _p_add(self, eqn, ins):
        a, b = ins
        return self._arith_result(eqn, a.lo + b.lo, a.hi + b.hi,
                                  a.exact and b.exact)

    def _p_sub(self, eqn, ins):
        a, b = ins
        if (b.anchor_kind == "floormul" and b.anchor == id(a)
                and a.lo >= 0 and a.exact):
            # x - floor(x * 2^-k) * 2^k for x >= 0: the base-2^k
            # remainder, in [0, 2^k) (the lazy-carry local rounds'
            # digit split; every op in the chain was proved exact)
            return self._arith_result(eqn, 0, (1 << (-b.pow2)) - 1)
        return self._arith_result(eqn, a.lo - b.hi, a.hi - b.lo,
                                  a.exact and b.exact)

    def _p_mul(self, eqn, ins):
        a, b = ins
        prods = [a.lo * b.lo, a.lo * b.hi, a.hi * b.lo, a.hi * b.hi]
        d = np.dtype(self._out(eqn)[0])
        if d.kind == "f":
            for x, y in ((a, b), (b, a)):
                k = _pow2_exponent(y)
                if k is None:
                    continue
                if (k < 0 and x.exact and x.pow2 == 0 and x.lo >= 0
                        and np.dtype(x.dtype).kind == "f"):
                    # exact power-of-two down-scaling (the lazy-carry
                    # local rounds' cols * 2^-8): exponent-only, the
                    # mantissa — already proved f32-exact via x.exact —
                    # is untouched, so the value is exactly m * 2^k even
                    # though no longer integer-valued. Tag for the floor
                    # rule instead of flagging here.
                    out = AbsVal(d, self._out(eqn)[1], min(prods),
                                 max(prods), exact=False, pow2=k)
                    out.anchor = id(x)
                    out.anchor_kind = "scaled"
                    return out
                if (k > 0 and x.anchor_kind == "floordiv"
                        and x.pow2 == -k and x.exact):
                    # floor(x * 2^-k) * 2^k: restore the anchor so the
                    # subtraction rule can recognize the remainder
                    out = self._arith_result(eqn, min(prods), max(prods))
                    out.anchor = x.anchor
                    out.anchor_kind = "floormul"
                    out.pow2 = x.pow2
                    return out
        return self._arith_result(eqn, min(prods), max(prods),
                                  a.exact and b.exact)

    def _p_neg(self, eqn, ins):
        (a,) = ins
        return self._arith_result(eqn, -a.hi, -a.lo, a.exact)

    def _p_abs(self, eqn, ins):
        (a,) = ins
        lo = 0 if a.lo <= 0 <= a.hi else min(abs(a.lo), abs(a.hi))
        return self._arith_result(eqn, lo, max(abs(a.lo), abs(a.hi)),
                                  a.exact)

    def _p_max(self, eqn, ins):
        a, b = ins
        return self._arith_result(eqn, max(a.lo, b.lo), max(a.hi, b.hi),
                                  a.exact and b.exact)

    def _p_min(self, eqn, ins):
        a, b = ins
        return self._arith_result(eqn, min(a.lo, b.lo), min(a.hi, b.hi),
                                  a.exact and b.exact)

    def _p_clamp(self, eqn, ins):
        lo_v, x, hi_v = ins
        return self._arith_result(eqn, max(x.lo, lo_v.lo),
                                  min(x.hi, hi_v.hi), x.exact)

    def _p_sign(self, eqn, ins):
        return self._mk(eqn, -1, 1)

    def _p_floor(self, eqn, ins):
        # floor of an exact value (or of a pow2-tagged exact rescale) is
        # an exact integer; _arith_result re-checks the f32 magnitude
        # bound. floor of anything else is integer-valued but its
        # pre-round error is unknowable — flag like any inexact float.
        # Used by field_pallas' lazy-carry local rounds.
        (a,) = ins
        lo, hi = int(math.floor(a.lo)), int(math.floor(a.hi))
        out = self._arith_result(eqn, lo, hi,
                                 exact_in=a.exact or a.pow2 < 0)
        if a.anchor_kind == "scaled" and a.pow2 < 0:
            out.anchor = a.anchor
            out.anchor_kind = "floordiv"
            out.pow2 = a.pow2
        return out

    def _p_round(self, eqn, ins):
        (a,) = ins
        return self._arith_result(eqn, int(math.floor(a.lo)),
                                  int(math.ceil(a.hi)),
                                  exact_in=a.exact or a.pow2 < 0)

    def _p_integer_pow(self, eqn, ins):
        (a,) = ins
        y = eqn.params["y"]
        vals = [a.lo ** y, a.hi ** y] + ([0] if a.lo <= 0 <= a.hi else [])
        return self._arith_result(eqn, min(vals), max(vals), a.exact)

    def _p_rem(self, eqn, ins):
        a, b = ins
        if b.lo >= 1:
            # C-style rem with positive divisors: sign follows the
            # dividend, |result| < divisor and |result| <= |dividend|
            m = b.hi - 1
            lo = 0 if a.lo >= 0 else max(-m, a.lo)
            hi = 0 if a.hi <= 0 else min(m, a.hi)
            return self._arith_result(eqn, lo, hi, a.exact)
        return self._fallback(eqn, ins)

    def _p_div(self, eqn, ins):
        a, b = ins
        d = np.dtype(self._out(eqn)[0])
        if d.kind in "ui" and b.lo == b.hi and b.lo > 0:
            n = b.lo

            def q(v):  # lax.div truncates toward ZERO (not floor)
                return -((-v) // n) if v < 0 else v // n

            return self._arith_result(eqn, q(a.lo), q(a.hi), True)
        # float division: exactness is not preserved in general
        lo, hi = _dtype_range(d)
        return self._arith_result(eqn, lo, hi, exact_in=False)

    # -- bitwise / shifts ------------------------------------------------------

    def _bits_hi(self, hi):
        return (1 << int(hi).bit_length()) - 1 if hi > 0 else 0

    def _p_and(self, eqn, ins):
        a, b = ins
        if a.lo < 0 or b.lo < 0:
            dlo, dhi = _dtype_range(self._out(eqn)[0])
            return self._mk(eqn, dlo, dhi)
        return self._mk(eqn, 0, min(a.hi, b.hi))

    def _p_or(self, eqn, ins):
        a, b = ins
        if a.lo < 0 or b.lo < 0:
            dlo, dhi = _dtype_range(self._out(eqn)[0])
            return self._mk(eqn, dlo, dhi)
        return self._mk(eqn, max(a.lo, b.lo),
                        max(self._bits_hi(a.hi), self._bits_hi(b.hi)))

    def _p_xor(self, eqn, ins):
        a, b = ins
        if a.lo < 0 or b.lo < 0:
            dlo, dhi = _dtype_range(self._out(eqn)[0])
            return self._mk(eqn, dlo, dhi)
        return self._mk(eqn, 0,
                        max(self._bits_hi(a.hi), self._bits_hi(b.hi)))

    def _p_not(self, eqn, ins):
        d = np.dtype(self._out(eqn)[0])
        if d.kind == "b":
            return self._mk(eqn, 0, 1)
        dlo, dhi = _dtype_range(d)
        return self._mk(eqn, dlo, dhi)

    def _p_shift_left(self, eqn, ins):
        a, s = ins
        if s.lo < 0:
            return self._fallback(eqn, ins)
        # true-math bound: wraparound past the dtype is the violation a
        # widened shift introduces
        lo = a.lo << s.lo if a.lo >= 0 else a.lo << s.hi
        hi = a.hi << s.hi if a.hi >= 0 else a.hi << s.lo
        return self._arith_result(eqn, lo, hi, a.exact)

    def _p_shift_right_logical(self, eqn, ins):
        a, s = ins
        if a.lo < 0:
            dlo, dhi = _dtype_range(self._out(eqn)[0])
            return self._mk(eqn, 0, dhi)
        return self._mk(eqn, a.lo >> s.hi, a.hi >> s.lo)

    def _p_shift_right_arithmetic(self, eqn, ins):
        a, s = ins
        return self._mk(eqn, min(a.lo >> s.lo, a.lo >> s.hi),
                        max(a.hi >> s.lo, a.hi >> s.hi))

    # -- comparisons / select --------------------------------------------------

    def _cmp(self, eqn, ins):
        a, b = ins
        onehot = frozenset()
        # eq against a broadcasted_iota along axis k, where the other
        # operand is constant along k (size-1 axis or broadcast): at
        # most one index matches per lane => one-hot mask along k
        if eqn.primitive.name == "eq":
            for x, y in ((a, b), (b, a)):
                k = x.iota_axis
                if k is None:
                    continue
                const_along_k = (k in y.bcast_axes
                                 or (k < len(y.shape) and y.shape[k] == 1)
                                 or y.lo == y.hi)
                if const_along_k:
                    onehot = onehot | {k}
        return self._mk(eqn, 0, 1, onehot_axes=onehot)

    _p_eq = _cmp
    _p_ne = _cmp
    _p_ge = _cmp
    _p_gt = _cmp
    _p_le = _cmp
    _p_lt = _cmp

    def _p_select_n(self, eqn, ins):
        pred, *cases = ins
        lo = min(c.lo for c in cases)
        hi = max(c.hi for c in cases)
        exact = all(c.exact for c in cases)
        onehot = frozenset()
        # where(mask, v, 0): if the mask is one-hot along k and the
        # mostly-selected FALSE case (index 0) is exactly zero, the
        # result is zero outside one slot along k — a later sum over k
        # needs no axis multiplier
        if len(cases) == 2 and pred.onehot_axes and cases[0].zero:
            onehot = pred.onehot_axes
        return self._mk(eqn, lo, hi, exact=exact, onehot_axes=onehot)

    # -- structure -------------------------------------------------------------

    def _p_broadcast_in_dim(self, eqn, ins):
        (a,) = ins
        dims = eqn.params["broadcast_dimensions"]
        dtype, shape = self._out(eqn)
        bcast = set(range(len(shape))) - set(dims)
        for i, d in enumerate(dims):
            if a.shape[i] == 1 and shape[d] != 1:
                bcast.add(d)
        for ax in a.bcast_axes:
            if ax < len(dims):
                bcast.add(dims[ax])
        iota_axis = None
        if a.iota_axis is not None and a.iota_axis < len(dims):
            d = dims[a.iota_axis]
            if shape[d] == a.shape[a.iota_axis]:
                iota_axis = d
        onehot = frozenset(dims[ax] for ax in a.onehot_axes
                           if ax < len(dims)
                           and shape[dims[ax]] == a.shape[ax])
        return AbsVal(dtype, shape, a.lo, a.hi, exact=a.exact,
                      bcast_axes=frozenset(bcast), iota_axis=iota_axis,
                      onehot_axes=onehot)

    def _p_iota(self, eqn, ins):
        dim = eqn.params["dimension"]
        dtype, shape = self._out(eqn)
        bcast = frozenset(i for i in range(len(shape)) if i != dim)
        return AbsVal(dtype, shape, 0, max(shape[dim] - 1, 0),
                      bcast_axes=bcast, iota_axis=dim)

    def _p_convert_element_type(self, eqn, ins):
        (a,) = ins
        dtype, shape = self._out(eqn)
        d = np.dtype(dtype)
        if d.kind in "uib":
            if np.dtype(a.dtype).kind == "f" and not a.exact:
                self._flag(eqn, "float -> int conversion of a value that "
                                "is not provably integer-valued")
            dlo, dhi = _dtype_range(d)
            lo = dlo if a.lo == -math.inf else int(math.floor(a.lo))
            hi = dhi if a.hi == math.inf else int(math.ceil(a.hi))
            return self._arith_result(eqn, lo, hi, True)
        return self._arith_result(eqn, a.lo, a.hi, a.exact)

    # -- reductions ------------------------------------------------------------

    def _reduce_count(self, eqn, v):
        """Number of summed elements per output lane, discounting axes
        where at most one element is nonzero (one-hot gather)."""
        n = 1
        for ax in eqn.params["axes"]:
            if ax in v.onehot_axes:
                continue
            n *= v.shape[ax]
        return max(n, 1)

    def _p_reduce_sum(self, eqn, ins):
        (a,) = ins
        n = self._reduce_count(eqn, a)
        full = 1
        for ax in eqn.params["axes"]:
            full *= a.shape[ax]
        if n != full:  # one-hot axes: elements off the hot slot are 0
            lo = min(0, a.lo) * n
            hi = max(0, a.hi) * n
        else:
            lo, hi = a.lo * n, a.hi * n
        return self._arith_result(eqn, lo, hi, a.exact)

    def _p_cumsum(self, eqn, ins):
        (a,) = ins
        n = a.shape[eqn.params["axis"]]
        return self._arith_result(eqn, min(a.lo, a.lo * n),
                                  max(a.hi, a.hi * n), a.exact)

    def _p_cumprod(self, eqn, ins):
        (a,) = ins
        n = a.shape[eqn.params["axis"]]
        vals = [a.lo ** n, a.hi ** n, a.lo, a.hi] \
            + ([0] if a.lo <= 0 <= a.hi else [])
        return self._arith_result(eqn, min(vals), max(vals), a.exact)

    def _p_reduce_and(self, eqn, ins):
        return self._mk(eqn, 0, 1)

    def _p_reduce_or(self, eqn, ins):
        return self._mk(eqn, 0, 1)

    def _p_argmax(self, eqn, ins):
        dtype, shape = self._out(eqn)
        (a,) = ins
        size = 1
        for ax in eqn.params["axes"]:
            size *= a.shape[ax]
        return AbsVal(dtype, shape, 0, max(size - 1, 0))

    _p_argmin = _p_argmax

    def _p_dot_general(self, eqn, ins):
        a, b = ins
        ((lc, rc), _) = eqn.params["dimension_numbers"]
        k = 1
        for ax in lc:
            k *= a.shape[ax]
        prods = [a.lo * b.lo, a.lo * b.hi, a.hi * b.lo, a.hi * b.hi]
        lo, hi = min(prods) * k, max(prods) * k
        # operand exactness: each input must already be exact in ITS
        # dtype (checked where it was produced); the accumulation is
        # checked against the OUTPUT dtype here
        return self._arith_result(eqn, lo, hi, a.exact and b.exact)

    # -- scatter ---------------------------------------------------------------

    def _p_scatter(self, eqn, ins):
        op, idx, upd = ins
        dtype, shape = self._out(eqn)
        return AbsVal(dtype, shape, min(op.lo, upd.lo),
                      max(op.hi, upd.hi), exact=op.exact and upd.exact)

    def _p_scatter_add(self, eqn, ins):
        op, idx, upd = ins
        # assumes unique scatter indices (every kernel use is
        # .at[const].add or put_along_axis with distinct rows)
        return self._arith_result(eqn, op.lo + min(upd.lo, 0),
                                  op.hi + max(upd.hi, 0),
                                  op.exact and upd.exact)

    # -- control flow ----------------------------------------------------------

    def _scan(self, eqn, ins):
        p = eqn.params
        sub = p["jaxpr"]
        if not hasattr(sub, "consts"):
            sub = jax.extend.core.ClosedJaxpr(sub, ())
        nc, nk = p["num_consts"], p["num_carry"]
        consts = ins[:nc]
        carry = list(ins[nc:nc + nk])
        xs = []
        for x in ins[nc + nk:]:
            xs.append(AbsVal(x.dtype, x.shape[1:], x.lo, x.hi,
                             exact=x.exact))
        carry, ys = self._loop_fixpoint(eqn, sub, consts, carry, xs)
        outs = list(carry)
        length = p["length"]
        for y in ys:
            outs.append(AbsVal(y.dtype, (length,) + y.shape, y.lo, y.hi,
                               exact=y.exact))
        return outs

    def _while(self, eqn, ins):
        p = eqn.params
        body = p["body_jaxpr"]
        cn, bn = p["cond_nconsts"], p["body_nconsts"]
        cond_consts = ins[:cn]
        body_consts = ins[cn:cn + bn]
        carry = list(ins[cn + bn:])
        carry, _ = self._loop_fixpoint(eqn, body, body_consts, carry, [])
        # the cond body's ops obey the same rules — checked AT THE
        # STABILIZED carry bounds (which include the initial ones), so a
        # condition that overflows on a late iteration is still caught
        self.run(p["cond_jaxpr"], cond_consts + carry)
        return carry

    def _cond(self, eqn, ins):
        branches = eqn.params["branches"]
        pred, ops = ins[0], ins[1:]
        # Pallas kernels pass VMEM refs into cond branches (pl.when):
        # run every branch from the same entry cell state and join the
        # exit states. A branch that never writes a cell contributes
        # BOTTOM, which joins as identity — i.e. the analysis assumes a
        # cell read after the cond was initialized by SOME branch or an
        # earlier grid pass (the when(step==0) init idiom); a kernel
        # that truly reads never-written scratch is its own bug.
        cells = [o for o in ops if isinstance(o, _RefCell)]
        snap = [c.val for c in cells]
        exits = [None] * len(cells)
        outs = None
        for br in branches:
            for c, v in zip(cells, snap):
                c.val = v
            res = self.run(br, list(ops))
            for i, c in enumerate(cells):
                if exits[i] is None:
                    exits[i] = c.val
                elif c.val is not None:
                    exits[i] = _join(exits[i], c.val)
            outs = res if outs is None else [
                _join(a, b) for a, b in zip(outs, res)]
        for c, v in zip(cells, exits):
            c.val = v
        return outs

    # -- pallas kernels --------------------------------------------------------

    def _p_program_id(self, eqn, ins):
        axis = eqn.params.get("axis", 0)
        hi = (1 << 31) - 1
        if self._grids and axis < len(self._grids[-1]):
            g = self._grids[-1][axis]
            if isinstance(g, int):
                hi = max(g - 1, 0)
        return self._mk(eqn, 0, hi)

    def _p_num_programs(self, eqn, ins):
        axis = eqn.params.get("axis", 0)
        if self._grids and axis < len(self._grids[-1]) \
                and isinstance(self._grids[-1][axis], int):
            g = self._grids[-1][axis]
            return self._mk(eqn, g, g)
        return self._mk(eqn, 1, (1 << 31) - 1)

    def _p_get(self, eqn, ins):
        if not isinstance(ins[0], _RefCell):
            return self._fallback(eqn, ins)
        dtype, shape = self._out(eqn)
        return ins[0].read(dtype, shape)

    def _p_swap(self, eqn, ins):
        if not isinstance(ins[0], _RefCell):
            return self._fallback(eqn, ins)
        cell, val = ins[0], ins[1]
        dtype, shape = self._out(eqn)
        old = cell.read(dtype, shape)
        # a slice whose element count equals the ref's covers the whole
        # ref (slice extents can never exceed an axis), so the write is
        # strong; anything smaller joins with the region it left intact
        numel = 1
        for d in shape:
            numel *= d
        ref_numel = 1
        for d in cell.shape:
            ref_numel *= d
        cell.write(val, full=(numel == ref_numel))
        return old

    def _p_addupdate(self, eqn, ins):
        if not isinstance(ins[0], _RefCell):
            return self._fallback(eqn, ins)
        cell, val = ins[0], ins[1]
        old = cell.read(cell.dtype, cell.shape)
        acc = AbsVal(cell.dtype, cell.shape, old.lo + val.lo,
                     old.hi + val.hi, exact=old.exact and val.exact)
        self._check_dtype(eqn, acc)
        d = np.dtype(cell.dtype)
        if d.kind in "uib":
            dlo, dhi = _dtype_range(d)
            if acc.hi > dhi or acc.lo < dlo:
                self._flag(eqn, f"{d.name} range exceeded in ref "
                                f"accumulate: [{acc.lo}, {acc.hi}]")
        else:
            exact_max = _FLOAT_EXACT_MAX.get(d.name)
            if exact_max is not None and \
                    max(abs(acc.lo), abs(acc.hi)) > exact_max:
                self._flag(eqn, f"{d.name} exactness lost in ref "
                                f"accumulate: |result| can reach "
                                f"{max(abs(acc.lo), abs(acc.hi))}")
        cell.write(acc, full=False)
        return []

    def _p_pallas_call(self, eqn, ins):
        """Interpret the kernel jaxpr (it IS a jaxpr) under the same
        interval rules, with one _RefCell per input/output/scratch ref
        and the grid modeled as a join-until-stable fixpoint — VMEM
        scratch persists across grid steps, so cells carry over exactly
        like scan carries. Outputs take their cells' stabilized bounds.
        """
        p = eqn.params
        sub = p.get("jaxpr")
        gm = p.get("grid_mapping")
        if sub is None or gm is None or \
                getattr(gm, "num_index_operands", 0):
            return self._fallback(eqn, ins)
        if not hasattr(sub, "consts"):
            sub = jax.extend.core.ClosedJaxpr(sub, ())
        n_in = gm.num_inputs
        grid = tuple(gm.grid or ())
        invars = sub.jaxpr.invars
        ops_in = ins[len(ins) - n_in:] if n_in else []
        cells = []
        for i, var in enumerate(invars):
            inner = getattr(var.aval, "inner_aval", var.aval)
            cell = _RefCell(inner.dtype, inner.shape)
            if i < n_in:
                v = ops_in[i]
                cell.val = AbsVal(inner.dtype, inner.shape, v.lo, v.hi,
                                  exact=v.exact)
            cells.append(cell)
        prev_check = self._check
        self._grids.append(grid)
        try:
            for _ in range(_MAX_FIXPOINT_ITERS):
                self._check = False
                before = [c.val for c in cells]
                self.run(sub, list(cells))
                stable = True
                for c, b in zip(cells, before):
                    if c.val is None:
                        continue
                    if b is None or not _stable(b, c.val):
                        stable = False
                        c.val = c.val if b is None else _join(b, c.val)
                if stable:
                    break
            else:
                self._check = prev_check
                self._flag(eqn, "pallas grid fixpoint: ref bounds do "
                                "not stabilize after "
                                f"{_MAX_FIXPOINT_ITERS} widening "
                                "iterations (unbounded accumulation "
                                "across grid steps)")
                for c in cells:
                    lo, hi = _dtype_range(c.dtype)
                    c.val = AbsVal(c.dtype, c.shape, lo, hi,
                                   exact=c.dtype.kind != "f")
            self._check = prev_check
            self.run(sub, list(cells))
        finally:
            self._grids.pop()
            self._check = prev_check
        outs = []
        for i in range(len(eqn.outvars)):
            dtype, shape = self._out(eqn, i)
            outs.append(cells[n_in + i].read(dtype, shape))
        return outs

    def _loop_fixpoint(self, eqn, body, consts, carry, xs):
        """Interpret a loop body until the carry intervals stop growing
        (violations are only collected on the final, stable pass)."""
        prev_check = self._check
        ys = []
        for it in range(_MAX_FIXPOINT_ITERS):
            self._check = False
            outs = self.run(body, list(consts) + list(carry) + list(xs))
            new_carry = outs[:len(carry)]
            ys = outs[len(carry):]
            if all(_stable(c, n) for c, n in zip(carry, new_carry)):
                break
            carry = [_join(c, n) for c, n in zip(carry, new_carry)]
        else:
            self._check = prev_check
            self._flag(eqn, "loop carry bounds do not stabilize after "
                            f"{_MAX_FIXPOINT_ITERS} widening iterations "
                            "(a carried value's magnitude grows every "
                            "step — unbounded accumulation)")
            # widen to dtype range for the reporting pass
            carry = [AbsVal(c.dtype, c.shape, *_dtype_range(c.dtype),
                            exact=np.dtype(c.dtype).kind != "f")
                     for c in carry]
        self._check = prev_check
        outs = self.run(body, list(consts) + list(carry) + list(xs))
        return outs[:len(carry)], outs[len(carry):]


def check_fn(name, fn, args, out_bounds=None, strict=True):
    """Trace `fn` at the declared argument bounds and interval-check the
    whole jaxpr. `args` is a pytree of Bound / concrete numpy arrays
    (concrete values get exact intervals — constant tables). Returns a
    list of Violations (empty = proven clean at these shapes).
    `out_bounds`: optional list of (lo, hi) per flattened output, the
    kernel's declared POSTcondition."""
    flat, treedef = jax.tree_util.tree_flatten(args)
    specs = []
    in_vals = []
    for leaf in flat:
        if isinstance(leaf, Bound):
            specs.append(leaf.spec())
            in_vals.append(leaf.absval())
        else:
            arr = np.asarray(leaf)
            specs.append(jax.ShapeDtypeStruct(arr.shape, arr.dtype))
            in_vals.append(from_concrete(arr))
    spec_tree = jax.tree_util.tree_unflatten(treedef, specs)
    closed = jax.make_jaxpr(
        lambda *a: fn(*a))(*spec_tree)
    interp = Interpreter(name, strict=strict)
    outs = interp.run(closed, in_vals)
    if out_bounds is not None:
        # fail closed: a postcondition list that doesn't cover every
        # output would silently leave the extras unchecked
        assert len(out_bounds) == len(outs), \
            (name, len(out_bounds), len(outs))
        for i, ((lo, hi), v) in enumerate(zip(out_bounds, outs)):
            if v.lo < lo or v.hi > hi:
                interp.violations.append(Violation(
                    name, "output",
                    f"output {i} bound [{v.lo}, {v.hi}] exceeds the "
                    f"declared contract [{lo}, {hi}]"))
    return interp.violations


def check_contracts(specs=None):
    """Evaluate field_jax.CARRY_CONTRACTS — the promoted zero-carry /
    exactness side conditions — against the actual field constants.
    Returns a list of Violations (empty = every contract holds)."""
    from ..backend import field_jax as FJ

    if specs is None:
        specs = (FJ.FR, FJ.FQ)
    out = []
    for spec in specs:
        for c in FJ.CARRY_CONTRACTS:
            try:
                ok = bool(c["holds"](spec))
            except Exception as e:  # pragma: no cover - malformed contract
                ok = False
                out.append(Violation(f"contract/{c['name']}", spec.name,
                                     f"contract raised: {e!r}"))
                continue
            if not ok:
                out.append(Violation(
                    f"contract/{c['name']}", spec.name,
                    f"DOES NOT HOLD for {spec.name}: {c['claim']}"))
    return out
