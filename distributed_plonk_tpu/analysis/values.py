"""Value-semantics pass: prove what registered kernels COMPUTE, not
just what ranges they stay in.

The bounds pass (bounds.py) walks a kernel's jaxpr with interval +
exactness abstract values and proves the machine arithmetic never
wraps, never rounds, and never leaves its declared limb ranges.  That
makes the machine semantics EQUAL to exact integer semantics — but it
says nothing about WHICH integer function the kernel computes.  A
dropped carry lane in `mont_mul`'s high-half assembly stays comfortably
inside every interval (the lane is < 2^16 either way) while silently
changing the product mod p.  On the u32 path that bug is caught
operationally by parity tests; on the f32/MXU path nothing checks it.

This module closes that gap with a second interpreter over the SAME
traced jaxpr: an exact big-integer/rational evaluator.  Every cell is a
numpy object array of Python ints (or `fractions.Fraction` for the f32
byte-product intermediates — exact binary fractions, so `floor(x *
2**-8)` means exactly what the lazy-carry local rounds claim).  Because
the bounds pass has already proven machine == exact-integer semantics,
evaluating the jaxpr exactly and checking an algebraic contract at
sampled points IS a statement about the machine kernel:

    bounds pass   ⊢  machine semantics == exact semantics
    value pass    ⊢  exact semantics   ⊨  value contract
    ───────────────────────────────────────────────────────
                  ⊢  machine kernel satisfies the contract

Contracts are per-entry (registry.Entry.value_contract) and algebraic:
`value(out) ≡ value(a)·value(b)·R⁻¹ (mod p)` for Montgomery background
multipliers, `value(limbs) + carry·2^(16·K) == value(cols)` EXACTLY for
`_carry_sweep`, `value(out) = DFT·value(in) (mod p)` for the NTT stage
pipelines (Fr-linearity makes the plain-Python poly oracle apply to raw
limb values in both Montgomery and plain boundaries), and so on.
Sample points are seeded-random field elements plus the corner values
0, 1, p-1 — a dropped carry lane / off-by-one limb shift / wrong
modulus constant is not a measure-zero bug, it changes the value at
almost every point, so a handful of samples rejects each class (the
mutant harness in analysis/mutants.py demonstrates this).

Nothing here executes on a device: the interpreter consumes the jaxpr
that `jax.make_jaxpr` produced on abstract inputs and evaluates it in
pure Python (the one exception: `gather` index arithmetic is resolved
by binding the real primitive on concrete int32 POSITION arrays — host
numpy, still no kernel values near a device).
"""

import math
import operator
from fractions import Fraction

import numpy as np
import jax
import jax.extend.core
import jax.numpy as jnp
import jax.tree_util as jtu

from .bounds import Violation, _CALL_PRIMS

__all__ = [
    "Violation", "UnsupportedPrim", "ExactInterpreter", "to_exact",
    "run_exact", "check_value", "limb_value", "limbs_from_int",
    "rand_fe", "mont_r", "elementwise", "mismatch_report",
]

_MAX_WHILE_ITERS = 1 << 20


class UnsupportedPrim(Exception):
    """A primitive (or primitive mode) the exact evaluator cannot model
    faithfully.  Strict mode turns this into a Violation: silently
    skipping an op would let a kernel rewrite smuggle unvetted
    arithmetic past the value pass."""


# -- exact value conversion ----------------------------------------------------

def _exact_scalar(v):
    if isinstance(v, (bool, np.bool_)):
        return bool(v)
    if isinstance(v, (int, np.integer)):
        return int(v)
    if isinstance(v, Fraction):
        return v
    f = float(v)
    if math.isnan(f) or math.isinf(f):
        raise UnsupportedPrim(f"non-finite constant {f!r}")
    if f.is_integer():
        return int(f)
    return Fraction(f)  # exact: binary float -> dyadic rational


_EXACTIFY = np.frompyfunc(_exact_scalar, 1, 1)


def to_exact(x):
    """numpy/jax array (or scalar) -> object ndarray of exact values:
    Python int / bool / Fraction (floats convert EXACTLY — a binary
    float is a dyadic rational)."""
    a = np.asarray(x)
    if a.dtype == object:
        return a.copy()
    return np.asarray(_EXACTIFY(a), dtype=object)


def _obj(x):
    return np.asarray(x, dtype=object)


def _to_index_array(x):
    """object array of exact ints -> int64 numpy array (for binding
    position/index primitives)."""
    a = _obj(x)
    out = np.empty(a.shape, dtype=np.int64)
    flat, of = a.reshape(-1), out.reshape(-1)
    for i in range(a.size):
        v = flat[i]
        if isinstance(v, Fraction):
            raise UnsupportedPrim("non-integer used as an index")
        of[i] = int(v)
    return out


def _ew(fn, *xs):
    """Elementwise with numpy broadcasting over object arrays."""
    xs = [_obj(x) for x in xs]
    return np.asarray(np.frompyfunc(fn, len(xs), 1)(*xs), dtype=object)


elementwise = _ew  # public alias for contract builders


def _scalar_of(x):
    a = _obj(x)
    if a.size != 1:
        raise UnsupportedPrim(f"expected scalar, got shape {a.shape}")
    return a.reshape(-1)[0]


def _block_size(dim):
    """One entry of a pallas BlockMapping.block_shape -> its int extent
    (jax 0.9 wraps each in pallas.Blocked; other block kinds — squeezed,
    element-indexed — are not modelled)."""
    from jax.experimental import pallas as pl
    if isinstance(dim, pl.Blocked):
        return int(dim.block_size)
    raise UnsupportedPrim(f"pallas block dim {dim!r} not modelled")


# -- exact scalar ops matching XLA integer semantics ---------------------------

def _srl(a, s):
    if a < 0:
        # logical shift on a negative value reinterprets the two's
        # complement bits; the exact value would diverge from the
        # machine and the bounds pass cannot have proven otherwise
        raise UnsupportedPrim("shift_right_logical on negative value")
    return a >> s


def _trunc_div(a, b):
    if isinstance(a, Fraction) or isinstance(b, Fraction):
        return a / b  # float path: exactness is the bounds pass's job
    q = abs(a) // abs(b)
    return -q if (a < 0) != (b < 0) else q


def _trunc_rem(a, b):
    return a - _trunc_div(a, b) * b


_ELEMENTWISE = {
    "add": operator.add,
    "sub": operator.sub,
    "mul": operator.mul,
    "neg": operator.neg,
    "max": max,
    "min": min,
    "and": operator.and_,
    "or": operator.or_,
    "xor": operator.xor,
    "not": lambda v: (not v) if isinstance(v, bool) else ~v,
    "eq": operator.eq,
    "ne": operator.ne,
    "lt": operator.lt,
    "le": operator.le,
    "gt": operator.gt,
    "ge": operator.ge,
    "floor": math.floor,
    "ceil": math.ceil,
    "abs": abs,
    "sign": lambda v: (v > 0) - (v < 0),
    "shift_left": lambda a, s: a << s,
    "shift_right_logical": _srl,
    "shift_right_arithmetic": lambda a, s: a >> s,
    "div": _trunc_div,
    "rem": _trunc_rem,
    "clamp": lambda lo, v, hi: min(max(v, lo), hi),
    "square": lambda v: v * v,
}

_IDENTITY = {
    "device_put", "copy", "stop_gradient", "sharding_constraint",
    "optimization_barrier", "reduce_precision", "convert_element_type",
    "real",
}


# -- the interpreter -----------------------------------------------------------

class ExactInterpreter:
    """Evaluate a ClosedJaxpr exactly on object arrays of Python
    ints/Fractions.  Control flow (scan/while/cond/pallas grids) runs
    concretely; VMEM refs are mutable object arrays."""

    def __init__(self, kernel_name):
        self.kernel = kernel_name
        self._grids = []  # (grid_tuple, current_index_tuple) stack

    # -- plumbing --------------------------------------------------------------

    def run(self, closed_jaxpr, in_vals):
        jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
        consts = getattr(closed_jaxpr, "consts", ())
        env = {}
        for var, const in zip(jaxpr.constvars, consts):
            env[var] = to_exact(const)
        if len(jaxpr.invars) != len(in_vals):
            raise UnsupportedPrim(
                f"arity mismatch: {len(jaxpr.invars)} invars, "
                f"{len(in_vals)} values")
        for var, val in zip(jaxpr.invars, in_vals):
            env[var] = _obj(val)
        for eqn in jaxpr.eqns:
            ins = [self._read(env, v) for v in eqn.invars]
            outs = self._eqn(eqn, ins)
            if not isinstance(outs, (list, tuple)):
                outs = [outs]
            for var, val in zip(eqn.outvars, outs):
                env[var] = _obj(val)
        return [self._read(env, v) for v in jaxpr.outvars]

    def _read(self, env, v):
        if isinstance(v, jax.extend.core.Literal):
            return to_exact(v.val)
        return env[v]

    def _sub(self, eqn):
        p = eqn.params
        sub = p.get("jaxpr") or p.get("call_jaxpr") or p.get("fun_jaxpr")
        if sub is not None and not hasattr(sub, "consts"):
            sub = jax.extend.core.ClosedJaxpr(sub, ())
        return sub

    def _eqn(self, eqn, ins):
        name = eqn.primitive.name
        if name in _CALL_PRIMS:
            sub = self._sub(eqn)
            if sub is None:
                raise UnsupportedPrim(f"call primitive '{name}' "
                                      "without a sub-jaxpr")
            n = len(sub.jaxpr.invars)
            return self.run(sub, ins[len(ins) - n:])
        if name in _ELEMENTWISE:
            return _ew(_ELEMENTWISE[name], *ins)
        if name in _IDENTITY:
            return self._convert(eqn, ins[0])
        handler = getattr(self, "_p_" + name.replace("-", "_"), None)
        if handler is None:
            raise UnsupportedPrim(
                f"unhandled primitive '{name}' in exact evaluation")
        return handler(eqn, ins)

    def _convert(self, eqn, x):
        dt = eqn.params.get("new_dtype")
        if dt is None:
            return x
        kind = np.dtype(dt).kind
        if kind in "iu":
            # truncation toward zero, exactly like XLA float->int;
            # int->narrower-int wrap is the bounds pass's problem (it
            # proves the value fits, so truncation == identity)
            return _ew(lambda v: int(v), x)
        if kind == "b":
            return _ew(lambda v: bool(v != 0), x)
        if kind == "f" or jnp.issubdtype(dt, jnp.floating):
            # int/Fraction value carried exactly (incl. bf16: the
            # bounds pass's float-exactness discipline is what makes
            # identity sound here)
            return x
        raise UnsupportedPrim(f"convert to unsupported dtype {dt}")

    # -- elementwise variants needing params -----------------------------------

    def _p_select_n(self, eqn, ins):
        which, *cases = ins
        return _ew(lambda w, *cs: cs[int(w)], which, *cases)

    def _p_integer_pow(self, eqn, ins):
        y = eqn.params["y"]
        return _ew(lambda v: v ** y, ins[0])

    def _p_is_finite(self, eqn, ins):
        return _ew(lambda v: True, ins[0])

    # -- structural ------------------------------------------------------------

    def _p_broadcast_in_dim(self, eqn, ins):
        shape = tuple(eqn.params["shape"])
        bdims = tuple(eqn.params["broadcast_dimensions"])
        a = ins[0]
        newshape = [1] * len(shape)
        for i, d in enumerate(bdims):
            newshape[d] = a.shape[i]
        return np.broadcast_to(a.reshape(newshape), shape).copy()

    def _p_reshape(self, eqn, ins):
        a = ins[0]
        dims = eqn.params.get("dimensions")
        if dims is not None:
            a = np.transpose(a, dims)
        return a.reshape(tuple(eqn.params["new_sizes"]))

    def _p_squeeze(self, eqn, ins):
        return np.squeeze(ins[0], axis=tuple(eqn.params["dimensions"]))

    def _p_expand_dims(self, eqn, ins):
        a = ins[0]
        for d in sorted(eqn.params["dimensions"]):
            a = np.expand_dims(a, d)
        return a

    def _p_transpose(self, eqn, ins):
        return np.transpose(ins[0], tuple(eqn.params["permutation"]))

    def _p_rev(self, eqn, ins):
        return np.flip(ins[0], axis=tuple(eqn.params["dimensions"]))

    def _p_slice(self, eqn, ins):
        p = eqn.params
        strides = p.get("strides") or (1,) * ins[0].ndim
        idx = tuple(slice(s, l, st) for s, l, st in
                    zip(p["start_indices"], p["limit_indices"], strides))
        return ins[0][idx].copy()

    def _p_dynamic_slice(self, eqn, ins):
        a, starts = ins[0], ins[1:]
        sizes = tuple(eqn.params["slice_sizes"])
        idx = []
        for d, (s, n) in enumerate(zip(starts, sizes)):
            s = int(_scalar_of(s))
            s = min(max(s, 0), a.shape[d] - n)  # XLA clamp semantics
            idx.append(slice(s, s + n))
        return a[tuple(idx)].copy()

    def _p_dynamic_update_slice(self, eqn, ins):
        a, u, starts = ins[0], ins[1], ins[2:]
        out = a.copy()
        idx = []
        for d, s in enumerate(starts):
            s = int(_scalar_of(s))
            s = min(max(s, 0), a.shape[d] - u.shape[d])
            idx.append(slice(s, s + u.shape[d]))
        out[tuple(idx)] = u
        return out

    def _p_concatenate(self, eqn, ins):
        return np.concatenate(ins, axis=eqn.params["dimension"])

    def _p_pad(self, eqn, ins):
        a, padval = ins[0], _scalar_of(ins[1])
        cfg = eqn.params["padding_config"]
        out_shape = tuple(
            lo + hi + n + max(n - 1, 0) * interior
            for n, (lo, hi, interior) in zip(a.shape, cfg))
        out = np.empty(out_shape, dtype=object)
        out[...] = padval
        pos_idx, src_idx = [], []
        for d, (lo, hi, interior) in enumerate(cfg):
            pos = lo + np.arange(a.shape[d]) * (interior + 1)
            keep = (pos >= 0) & (pos < out_shape[d])
            pos_idx.append(pos[keep])
            src_idx.append(np.arange(a.shape[d])[keep])
        if all(len(p) for p in pos_idx) or a.ndim == 0:
            out[np.ix_(*pos_idx)] = a[np.ix_(*src_idx)]
        return out

    def _p_iota(self, eqn, ins):
        shape = tuple(eqn.params["shape"])
        dim = eqn.params["dimension"]
        ar = to_exact(np.arange(shape[dim]))
        view = [1] * len(shape)
        view[dim] = shape[dim]
        return np.broadcast_to(ar.reshape(view), shape).copy()

    # -- reductions / contractions ---------------------------------------------

    def _p_reduce_sum(self, eqn, ins):
        return _obj(np.sum(ins[0], axis=tuple(eqn.params["axes"])))

    def _p_reduce_prod(self, eqn, ins):
        return _obj(np.prod(ins[0], axis=tuple(eqn.params["axes"])))

    def _p_reduce_max(self, eqn, ins):
        return _obj(np.maximum.reduce(
            ins[0], axis=tuple(eqn.params["axes"])[0]
            if len(eqn.params["axes"]) == 1 else None)) \
            if False else self._reduce_cmp(eqn, ins, max)

    def _p_reduce_min(self, eqn, ins):
        return self._reduce_cmp(eqn, ins, min)

    def _reduce_cmp(self, eqn, ins, fn):
        a = ins[0]
        for ax in sorted(eqn.params["axes"], reverse=True):
            a = _obj(np.frompyfunc(fn, 2, 1).reduce(a, axis=ax))
        return a

    def _p_reduce_and(self, eqn, ins):
        return _obj(np.all(ins[0], axis=tuple(eqn.params["axes"])))

    def _p_reduce_or(self, eqn, ins):
        return _obj(np.any(ins[0], axis=tuple(eqn.params["axes"])))

    def _p_argmax(self, eqn, ins):
        raise UnsupportedPrim("argmax has no exact-value story here")

    def _p_cumsum(self, eqn, ins):
        a, ax = ins[0], eqn.params["axis"]
        if eqn.params.get("reverse"):
            a = np.flip(a, axis=ax)
        out = np.cumsum(a, axis=ax)
        if eqn.params.get("reverse"):
            out = np.flip(out, axis=ax)
        return _obj(out)

    def _p_dot_general(self, eqn, ins):
        a, b = ins
        (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
        lc, rc, lb, rb = map(tuple, (lc, rc, lb, rb))
        lc2 = [d - sum(1 for bd in lb if bd < d) for d in lc]
        rc2 = [d - sum(1 for bd in rb if bd < d) for d in rc]
        if not lb:
            return _obj(np.tensordot(a, b, axes=(lc2, rc2)))
        lfree = [d for d in range(a.ndim) if d not in lc and d not in lb]
        rfree = [d for d in range(b.ndim) if d not in rc and d not in rb]
        out_shape = ([a.shape[d] for d in lb]
                     + [a.shape[d] for d in lfree]
                     + [b.shape[d] for d in rfree])
        out = np.empty(tuple(out_shape), dtype=object)
        for bpos in np.ndindex(*[a.shape[d] for d in lb]):
            ai = [slice(None)] * a.ndim
            bi = [slice(None)] * b.ndim
            for d, i in zip(lb, bpos):
                ai[d] = i
            for d, i in zip(rb, bpos):
                bi[d] = i
            out[bpos] = np.tensordot(a[tuple(ai)], b[tuple(bi)],
                                     axes=(lc2, rc2))
        return out

    # -- gather / scatter ------------------------------------------------------

    def _p_gather(self, eqn, ins):
        op, idx = ins
        # position-bind trick: run the REAL gather on flat positions
        # (host numpy int64, eager) and index the object array with the
        # result — index arithmetic stays primitive-faithful without
        # reimplementing XLA gather semantics
        pos = jnp.arange(op.size, dtype=jnp.int32).reshape(op.shape)
        out_pos = np.asarray(
            eqn.primitive.bind(
                pos, jnp.asarray(_to_index_array(idx).astype(np.int32)),
                **eqn.params))
        if out_pos.size and (out_pos.min() < 0
                             or out_pos.max() >= op.size):
            raise UnsupportedPrim(
                "gather out-of-bounds fill is not modelled")
        return op.reshape(-1)[out_pos]

    def _p_scatter_add(self, eqn, ins):
        return self._scatter(eqn, ins, combine="add")

    def _p_scatter(self, eqn, ins):
        return self._scatter(eqn, ins, combine="set")

    def _scatter(self, eqn, ins, combine):
        op, idx, upd = ins
        dn = eqn.params["dimension_numbers"]
        if (getattr(dn, "operand_batching_dims", ())
                or getattr(dn, "scatter_indices_batching_dims", ())):
            raise UnsupportedPrim("batched scatter dims not modelled")
        uwd = tuple(dn.update_window_dims)
        iwd = tuple(dn.inserted_window_dims)
        sdod = tuple(dn.scatter_dims_to_operand_dims)
        idx_np = _to_index_array(idx)
        if idx_np.ndim == 0:
            idx_np = idx_np.reshape(1)
        batch_shape, k = idx_np.shape[:-1], idx_np.shape[-1]
        usd = [d for d in range(upd.ndim) if d not in uwd]
        owd = [d for d in range(op.ndim) if d not in iwd]
        wsize = [1] * op.ndim
        for ud, od in zip(sorted(uwd), owd):
            wsize[od] = upd.shape[ud]
        out = op.copy()
        for bpos in np.ndindex(*batch_shape):
            start = idx_np[bpos]
            sv = [0] * op.ndim
            for j in range(k):
                sv[sdod[j]] = int(start[j])
            if any(sv[d] < 0 or sv[d] + wsize[d] > op.shape[d]
                   for d in range(op.ndim)):
                continue  # FILL_OR_DROP: out-of-bounds update dropped
            ui = [slice(None)] * upd.ndim
            for d, i in zip(usd, bpos):
                ui[d] = i
            u = _obj(upd[tuple(ui)])
            for wpos in np.ndindex(*u.shape):
                opos = list(sv)
                for od, w in zip(owd, wpos):
                    opos[od] += w
                if combine == "add":
                    out[tuple(opos)] = out[tuple(opos)] + u[wpos]
                else:
                    out[tuple(opos)] = u[wpos]
        return out

    # -- control flow (executed concretely) ------------------------------------

    def _p_scan(self, eqn, ins):
        p = eqn.params
        nc, nk = p["num_consts"], p["num_carry"]
        sub = p["jaxpr"]
        length = p["length"]
        consts, carry = list(ins[:nc]), list(ins[nc:nc + nk])
        xs = ins[nc + nk:]
        n_ys = len(sub.jaxpr.outvars) - nk
        order = range(length - 1, -1, -1) if p.get("reverse") \
            else range(length)
        collected = []
        for i in order:
            sliced = [_obj(x[i]) for x in xs]
            outs = self.run(sub, consts + carry + sliced)
            carry = [_obj(o) for o in outs[:nk]]
            collected.append(outs[nk:])
        if p.get("reverse"):
            collected.reverse()
        ys = []
        for j in range(n_ys):
            if collected:
                ys.append(_obj(np.stack([_obj(c[j]) for c in collected])))
            else:
                shape = tuple(eqn.outvars[nk + j].aval.shape)
                ys.append(np.empty(shape, dtype=object))
        return carry + ys

    def _p_while(self, eqn, ins):
        p = eqn.params
        cn, bn = p["cond_nconsts"], p["body_nconsts"]
        cc, bc = list(ins[:cn]), list(ins[cn:cn + bn])
        carry = [list(ins[cn + bn:])][0]
        for _ in range(_MAX_WHILE_ITERS):
            pred = _scalar_of(self.run(p["cond_jaxpr"], cc + carry)[0])
            if not pred:
                return carry
            carry = [_obj(o) for o in self.run(p["body_jaxpr"],
                                               bc + carry)]
        raise UnsupportedPrim("while loop exceeded the exact-evaluation "
                              "iteration cap")

    def _p_cond(self, eqn, ins):
        branches = eqn.params["branches"]
        i = int(_scalar_of(ins[0]))
        i = min(max(i, 0), len(branches) - 1)
        return self.run(branches[i], list(ins[1:]))

    # -- pallas ----------------------------------------------------------------

    def _p_pallas_call(self, eqn, ins):
        p = eqn.params
        inner = p["jaxpr"]
        if not hasattr(inner, "consts"):
            inner = jax.extend.core.ClosedJaxpr(inner, ())
        gm = p["grid_mapping"]
        if getattr(gm, "num_index_operands", 0):
            raise UnsupportedPrim("pallas index operands not modelled")
        grid = tuple(int(g) for g in gm.grid) or (1,)
        nin, nout = gm.num_inputs, gm.num_outputs
        bms = list(gm.block_mappings)
        outs = []
        for v in eqn.outvars:
            o = np.empty(tuple(v.aval.shape), dtype=object)
            o[...] = 0
            outs.append(o)
        scratch = []
        for v in inner.jaxpr.invars[nin + nout:]:
            s = np.empty(tuple(v.aval.shape), dtype=object)
            s[...] = 0
            scratch.append(s)
        operands = list(ins[:nin]) + outs

        def block_slices(bm, step):
            cj = bm.index_map_jaxpr
            bidx = self.run(cj, [_obj(i) for i in step])
            bshape = tuple(_block_size(d) for d in bm.block_shape)
            return tuple(
                slice(int(_scalar_of(b)) * n, int(_scalar_of(b)) * n + n)
                for b, n in zip(bidx, bshape))

        for step in np.ndindex(*grid):
            self._grids.append((grid, step))
            try:
                refs = []
                slcs = []
                for operand, bm in zip(operands, bms):
                    sl = block_slices(bm, step)
                    slcs.append(sl)
                    refs.append(operand[sl].copy())
                refs.extend(scratch)  # scratch persists across steps
                self.run(inner, refs)
                for j in range(nout):  # write out-blocks back
                    operands[nin + j][slcs[nin + j]] = refs[nin + j]
            finally:
                self._grids.pop()
        return outs

    def _ref_index(self, eqn, dyn):
        from jax._src.state.indexing import NDIndexer, Slice
        tree = eqn.params["tree"]
        leaves = [int(_scalar_of(x)) for x in dyn]
        nodes = jtu.tree_unflatten(tree, leaves)
        idx = []
        for nd in nodes:
            if isinstance(nd, NDIndexer):
                for s in nd.indices:
                    if isinstance(s, Slice):
                        idx.append(slice(int(s.start),
                                         int(s.start)
                                         + int(s.size) * int(s.stride),
                                         int(s.stride)))
                    elif isinstance(s, (int, np.integer)):
                        idx.append(int(s))
                    else:
                        raise UnsupportedPrim(
                            f"ref indexer {type(s).__name__} "
                            "not modelled")
            elif isinstance(nd, (int, np.integer)):
                idx.append(int(nd))
            else:
                raise UnsupportedPrim(
                    f"ref index node {type(nd).__name__} not modelled")
        return tuple(idx)

    def _p_get(self, eqn, ins):
        ref = ins[0]
        return _obj(ref[self._ref_index(eqn, ins[1:])]).copy()

    def _p_swap(self, eqn, ins):
        ref, val = ins[0], ins[1]
        idx = self._ref_index(eqn, ins[2:])
        old = _obj(ref[idx]).copy()
        ref[idx] = val
        return old

    def _p_addupdate(self, eqn, ins):
        ref, val = ins[0], ins[1]
        idx = self._ref_index(eqn, ins[2:])
        ref[idx] = ref[idx] + val
        return []

    def _p_program_id(self, eqn, ins):
        if not self._grids:
            raise UnsupportedPrim("program_id outside a pallas grid")
        return _obj(self._grids[-1][1][eqn.params["axis"]])

    def _p_num_programs(self, eqn, ins):
        if not self._grids:
            raise UnsupportedPrim("num_programs outside a pallas grid")
        return _obj(self._grids[-1][0][eqn.params["axis"]])

    def _p_debug_callback(self, eqn, ins):
        return []


# -- entry points --------------------------------------------------------------

def run_exact(name, fn, args):
    """Trace `fn` at the args' shapes/dtypes and evaluate the jaxpr
    exactly on the args' values.  `args` is a tuple (pytrees allowed)
    of concrete numpy arrays; returns the list of exact output object
    arrays."""
    specs = jtu.tree_map(
        lambda a: jax.ShapeDtypeStruct(np.asarray(a).shape,
                                       np.asarray(a).dtype), tuple(args))
    closed = jax.make_jaxpr(fn)(*specs)
    flat = [to_exact(x) for x in jtu.tree_leaves(tuple(args))]
    return ExactInterpreter(name).run(closed, flat)


def check_value(name, fn, sampler, contract, samples=2, seed=0,
                strict=True):
    """Evaluate `fn` exactly at `samples` seeded sample points and run
    `contract(args, outs)` on each; returns a list of Violations.

    sampler(rng) -> concrete args tuple; contract(args, outs) -> list of
    error strings ([] / None when satisfied).  outs are object arrays of
    exact ints (the bounds pass separately proves machine == exact, so a
    contract failure here is a statement about the machine kernel)."""
    violations = []
    for s in range(samples):
        rng = np.random.default_rng((seed << 16) ^ (0x5eed + s))
        args = sampler(rng)
        try:
            outs = run_exact(name, fn, args)
        except UnsupportedPrim as e:
            if strict:
                violations.append(
                    Violation(name, "value", str(e), f"sample {s}"))
            return violations
        for msg in (contract(args, outs) or ()):
            violations.append(
                Violation(name, "value", msg, f"sample {s}"))
    return violations


# -- value algebra helpers -----------------------------------------------------

def limb_value(cols, bits=16, axis=0):
    """value(cols) = Σ cols[i] · 2^(bits·i) along `axis`, exactly.
    Returns an object array of Python ints shaped like cols minus
    `axis`."""
    a = np.moveaxis(_obj(cols), axis, 0)
    out = np.empty(a.shape[1:], dtype=object)
    out[...] = 0
    for i in range(a.shape[0]):
        out = out + _ew(int, a[i]) * (1 << (bits * i))
    return out


def limbs_from_int(v, n_limbs, bits=16, dtype=np.uint32):
    """Split an int into `n_limbs` little-endian `bits`-bit limbs."""
    mask = (1 << bits) - 1
    return np.array([(int(v) >> (bits * i)) & mask
                     for i in range(n_limbs)], dtype=dtype)


def rand_fe(rng, p):
    """Uniform field element below p from a seeded Generator (numpy
    cannot draw 255-bit ints natively; compose from bytes)."""
    nbytes = (p.bit_length() + 7) // 8 + 8
    return int.from_bytes(bytes(rng.integers(0, 256, nbytes,
                                             dtype=np.uint8)),
                          "little") % p


def mont_r(spec):
    """The Montgomery radix R = 2^(16·n_limbs) for a field spec."""
    return 1 << (16 * spec.n_limbs)


def mismatch_report(tag, got, want, mod=None):
    """Compare two object arrays of ints (optionally mod `mod`);
    return [] when equal, else one message naming the first bad lane."""
    g, w = _obj(got), _obj(want)
    if mod is not None:
        g, w = _ew(lambda v: int(v) % mod, g), _ew(
            lambda v: int(v) % mod, w)
    if g.shape != w.shape:
        return [f"{tag}: shape mismatch {g.shape} vs {w.shape}"]
    bad = np.argwhere(_ew(operator.ne, g, w))
    if not len(bad):
        return []
    at = tuple(int(x) for x in bad[0])
    return [f"{tag}: value mismatch at lane {at}: "
            f"got {g[at]}, want {w[at]} "
            f"({len(bad)}/{g.size} lanes differ)"]
