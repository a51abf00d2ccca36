"""Kernel registry: the production entry points the verifier must prove.

One place enumerates every hot kernel with its REAL call shapes and the
documented input/output bounds, so `python -m distributed_plonk_tpu.analysis
--strict` is a single proof obligation covering:

- field mul/add/sub (Fr and Fq, BOTH multiplier paths — the default
  f32/MXU byte-product path and the u32 reference path),
- `_carry_sweep` at full-u32 input (its own contract: limbs < 2^16 out),
- the NTT stage pipeline for all 8 (inverse, coset, boundary) modes at
  odd AND even log2(n) (radix-4 default plus the radix-2 parity core),
- MSM digit extraction at the prover's real n+2/n+3 blinded handle
  widths (signed c=7, signed c=8, unsigned c=4 small-window),
- the bucket-update scan in every plane-update strategy the platform
  split can pick (onehot+packed, onehot unpacked, put),
- the MSM finish tail / plane folds, and the complete projective +
  Jacobian curve adds.

Shapes are representative, not production-sized: interval propagation is
width-generic for every rule except reduction/contraction counts, and
those are taken from the traced shape — the registry picks shapes whose
reduction widths EQUAL or EXCEED production's per-column term counts
(limb counts are fixed; scan lengths only repeat the same body). Entries
that depend on a module-level mode latch (DPT_FIELD_MUL,
DPT_BUCKET_UPDATE, DPT_PLANE_PACK) re-point the latch around the trace
so both sides of every platform split are verified regardless of the
machine running the check.
"""

from . import bounds as B
from . import values as V
from .bounds import Bound, limb_rows

import jax.numpy as jnp
import numpy as np

U16 = (1 << 16) - 1
U32 = (1 << 32) - 1


class ValueObligation:
    """A machine-checked value contract for a registry entry.

    sampler(rng) -> concrete args; contract(args, outs) -> error
    strings.  `fn` overrides the entry fn when the value pass needs a
    cheaper instantiation of the same kernel code (e.g. a smaller Horner
    chunk); `patches` are applied ON TOP of the entry's bounds patches
    (e.g. a narrow Pallas lane tile so the exact grid walk stays cheap —
    the kernel body is tile-width-generic, which the bounds pass proves
    at the real tile)."""

    def __init__(self, sampler, contract, samples=1, patches=(), fn=None):
        self.sampler = sampler
        self.contract = contract
        self.samples = samples
        self.patches = tuple(patches)
        self.fn = fn


class Entry:
    def __init__(self, name, fn, args, out_bounds=None, patches=(),
                 value=None):
        self.name = name
        self.fn = fn
        self.args = args
        self.out_bounds = out_bounds
        self.patches = tuple(patches)  # ((module, attr, value), ...)
        self.value = value             # ValueObligation | None

    def _patched(self, patches, thunk):
        saved = [(m, a, getattr(m, a)) for m, a, _ in patches]
        for m, a, v in patches:
            setattr(m, a, v)
        try:
            return thunk()
        finally:
            for m, a, v in saved:
                setattr(m, a, v)

    def check(self, strict=True):
        return self._patched(
            self.patches,
            lambda: B.check_fn(self.name, self.fn, self.args,
                               out_bounds=self.out_bounds, strict=strict))

    def check_values(self, strict=True, seed=0):
        """Run this entry's value contract (None when the entry declares
        no value obligation — e.g. curve group ops, whose value story is
        the field contracts they are composed from plus parity tests)."""
        if self.value is None:
            return None
        ob = self.value
        return self._patched(
            self.patches + ob.patches,
            lambda: V.check_value(self.name, ob.fn or self.fn,
                                  ob.sampler, ob.contract,
                                  samples=ob.samples, seed=seed,
                                  strict=strict))


# -- value samplers / contracts ------------------------------------------------
#
# Sample points are seeded-random field elements PLUS the corner values
# 0, 1, p-1 in fixed lanes: the injected bug classes (dropped carry
# lane, off-by-one limb shift, wrong modulus constant, swapped twiddle
# row) each change the computed value at almost every point, so a
# handful of samples rejects them — while the corners pin the
# conditional-subtract / carry-out edges random sampling would miss.

def _fe_lane_vals(rng, p, lanes):
    vals = [0, 1, p - 1][:lanes]
    return vals + [V.rand_fe(rng, p) for _ in range(lanes - len(vals))]


def _field_sampler(spec, nargs, lanes=5):
    L = spec.n_limbs

    def sample(rng):
        args = []
        for _ in range(nargs):
            vals = _fe_lane_vals(rng, spec.mod, lanes)
            rng.shuffle(vals)  # corners meet corners across samples
            args.append(np.stack([V.limbs_from_int(v, L) for v in vals],
                                 axis=1))
        return tuple(args)
    return sample


def _mod_contract(spec, op):
    """value(out) as a function of value(in) mod p, plus canonicality
    (out < p) — the algebraic claim each field kernel's docstring
    makes, now machine-checked."""
    p, R = spec.mod, V.mont_r(spec)
    rinv = pow(R, -1, p)
    fns = {
        "mont_mul": lambda a, b: a * b * rinv % p,
        "add": lambda a, b: (a + b) % p,
        "sub": lambda a, b: (a - b) % p,
        "neg": lambda a: -a % p,
        "to_mont": lambda a: a * R % p,
        "from_mont": lambda a: a * rinv % p,
    }
    fn = fns[op]
    nargs = fn.__code__.co_argcount

    def contract(args, outs):
        ins = [V.limb_value(V.to_exact(a)) for a in args[:nargs]]
        want = V.elementwise(lambda *vs: fn(*[int(x) for x in vs]), *ins)
        got = V.limb_value(outs[0])
        errs = V.mismatch_report(f"value(out) == {op}(value(in)) mod p",
                                 got, want)
        over = sum(int(g) >= p for g in got.reshape(-1))
        if over:
            errs.append(f"{op}: output not canonical (>= p) in "
                        f"{over} lane(s)")
        return errs
    return contract


def _field_value(spec, op, nargs, lanes=5, samples=2, patches=(),
                 fn=None):
    return ValueObligation(_field_sampler(spec, nargs, lanes),
                           _mod_contract(spec, op), samples=samples,
                           patches=patches, fn=fn)


def _carry_sweep_value():
    def sampler(rng):
        cols = rng.integers(0, 1 << 32, size=(16, 6), dtype=np.uint32)
        cols[:, 0] = 0          # corner: all-zero columns
        cols[:, 1] = U32        # corner: every column saturated
        return (cols,)

    def contract(args, outs):
        K = args[0].shape[0]
        vc = V.limb_value(V.to_exact(args[0]))
        vl = V.limb_value(outs[0])
        carry = V.elementwise(lambda c: int(c) << (16 * K), outs[1])
        return V.mismatch_report(
            "value(limbs) + carry*2^(16K) == value(cols)",
            vl + carry, vc)
    return ValueObligation(sampler, contract, samples=2)


def _roundtrip_value(shape):
    def sampler(rng):
        v = rng.integers(0, 1 << 16, size=shape, dtype=np.uint32)
        v.reshape(-1)[0] = 0
        v.reshape(-1)[1] = U16
        return (v,)

    def contract(args, outs):
        return V.mismatch_report("pack/unpack roundtrip identity",
                                 outs[0], V.to_exact(args[0]))
    return ValueObligation(sampler, contract, samples=2)


def _cumsum_value(spec, lanes=8):
    p, L = spec.mod, spec.n_limbs

    def sampler(rng):
        vals = _fe_lane_vals(rng, p, lanes)
        return (np.stack([V.limbs_from_int(v, L) for v in vals],
                         axis=1),)

    def contract(args, outs):
        vin = V.limb_value(V.to_exact(args[0]))
        got = V.limb_value(outs[0])
        acc, want = 0, []
        for x in vin.reshape(-1):
            acc = (acc + int(x)) % p
            want.append(acc)
        return V.mismatch_report("inclusive prefix sums mod p", got,
                                 np.array(want, dtype=object))
    return ValueObligation(sampler, contract, samples=2)


def _ntt_value(n, inverse, coset, cnp, batch=False, perm=None):
    """value(out) == DFT(value(in)) against the pure-Python poly
    oracle.  Fr-linearity of the transform makes the oracle apply to
    RAW limb values in both boundaries: Montgomery form is scaling by
    R, and the DFT commutes with scalar multiplication — so no
    boundary-specific expected values are needed.  `perm` (the
    defer_perm consts table) relates bit-reversed outputs back to
    natural order."""
    from .. import poly as P
    from ..constants import R_MOD
    dom = P.Domain(n)
    rows = 3 if batch else 1

    def sampler(rng):
        vals = [V.rand_fe(rng, R_MOD) for _ in range(rows * n)]
        vals[0], vals[1] = 0, 1  # corner lanes ride every sample
        arr = np.stack([V.limbs_from_int(v, 16) for v in vals], axis=1)
        shape = (16, rows, n) if batch else (16, n)
        return arr.reshape(shape), cnp

    def oracle(vs):
        if inverse and coset:
            return P.coset_ifft(dom, vs)
        if inverse:
            return P.ifft(dom, vs)
        if coset:
            return P.coset_fft(dom, vs)
        return P.fft(dom, vs)

    def contract(args, outs):
        vin = V.limb_value(V.to_exact(args[0])).reshape(-1, n)
        got = V.limb_value(outs[0]).reshape(-1, n)
        errs = []
        for b in range(vin.shape[0]):
            want = list(oracle([int(x) % R_MOD for x in vin[b]]))
            row = [int(x) % R_MOD for x in got[b]]
            if perm is not None:
                row = [row[i] for i in perm]
            if row != want:
                k = next(i for i in range(n) if row[i] != want[i])
                nbad = sum(r != w for r, w in zip(row, want))
                errs.append(f"row {b}: mismatch vs poly oracle at lane "
                            f"{k} ({nbad}/{n} lanes differ)")
        return errs
    return ValueObligation(sampler, contract, samples=1)


def _digits_value(Lw, c, bias):
    """Σ (digit_w - bias)·2^(c·w) reconstructs from_mont(handle)
    exactly, per lane, zero on padding — the recombination equation the
    bucket accumulation relies on (bias 0 = unsigned)."""
    from ..constants import R_MOD
    rinv = pow(1 << 256, -1, R_MOD)

    def sampler(rng):
        vals = _fe_lane_vals(rng, R_MOD, Lw)
        return (np.stack([V.limbs_from_int(v, 16) for v in vals],
                         axis=1),)

    def contract(args, outs):
        vin = [int(x) for x in
               V.limb_value(V.to_exact(args[0])).reshape(-1)]
        scal = [v * rinv % R_MOD for v in vin]
        d = outs[0]
        W, padded = d.shape
        errs = []
        for j in range(padded):
            want = scal[j] if j < len(scal) else 0
            rec = sum((int(d[w, j]) - bias) << (c * w) for w in range(W))
            if rec != want:
                errs.append(f"digit recombination wrong at lane {j}: "
                            f"sum((d-{bias})*2^({c}w)) = {rec}, "
                            f"scalar = {want}")
                break
        return errs
    return ValueObligation(sampler, contract, samples=1)


def _eval_value(Lc, batch=None, fn=None):
    """value(out) == Σ c_i·z^i in raw-value terms: coeffs/point arrive
    in Montgomery form (c_i = v_i·R⁻¹, z = vz·R⁻¹); poly_eval returns
    the Montgomery form of p(z), poly_eval_many the canonical value."""
    from ..constants import R_MOD
    R = 1 << 256
    rinv = pow(R, -1, R_MOD)

    def sampler(rng):
        def poly(vals):
            return np.stack([V.limbs_from_int(v, 16) for v in vals],
                            axis=1)
        if batch:
            ps = np.stack([poly(_fe_lane_vals(rng, R_MOD, Lc))
                           for _ in range(batch)])
            zs = np.stack([poly([V.rand_fe(rng, R_MOD)])
                           for _ in range(batch)])
            return ps, zs
        return (poly(_fe_lane_vals(rng, R_MOD, Lc)),
                poly([V.rand_fe(rng, R_MOD)]))

    def contract(args, outs):
        ax = 1 if batch else 0  # batched polys are (B, 16, L)
        vin = V.limb_value(V.to_exact(args[0]), axis=ax).reshape(-1, Lc)
        vz = V.limb_value(V.to_exact(args[1]), axis=ax).reshape(-1)
        got = V.limb_value(outs[0]).reshape(-1)
        errs = []
        for b in range(vin.shape[0]):
            cs = [int(x) * rinv % R_MOD for x in vin[b]]
            z = int(vz[b]) * rinv % R_MOD
            pz = 0
            for c in reversed(cs):
                pz = (pz * z + c) % R_MOD
            want = pz if batch else pz * R % R_MOD  # many() -> canonical
            if int(got[b]) != want:
                errs.append(f"poly {b}: p(z) value mismatch: "
                            f"got {int(got[b])}, want {want}")
        return errs
    return ValueObligation(sampler, contract, samples=1, fn=fn)


def _field_entries():
    from ..backend import field_jax as FJ

    out = []
    for spec in (FJ.FR, FJ.FQ):
        L = spec.n_limbs
        pair = (limb_rows(L, 8), limb_rows(L, 8))
        one = (limb_rows(L, 8),)
        limbs_out = [(0, U16)]
        n = spec.name.lower()
        for tag in ("f32", "u32"):  # f32/MXU default, u32 reference
            out.append(Entry(
                f"field/{n}_mont_mul_{tag}",
                lambda a, b, s=spec: FJ.mont_mul(s, a, b), pair,
                limbs_out, patches=[(FJ, "_MUL_MODE", tag)],
                value=_field_value(spec, "mont_mul", 2)))
        out.append(Entry(f"field/{n}_add",
                         lambda a, b, s=spec: FJ.add(s, a, b), pair,
                         limbs_out, value=_field_value(spec, "add", 2)))
        out.append(Entry(f"field/{n}_sub",
                         lambda a, b, s=spec: FJ.sub(s, a, b), pair,
                         limbs_out, value=_field_value(spec, "sub", 2)))
        out.append(Entry(f"field/{n}_neg",
                         lambda a, s=spec: FJ.neg(s, a), one, limbs_out,
                         value=_field_value(spec, "neg", 1)))
        out.append(Entry(f"field/{n}_to_mont",
                         lambda a, s=spec: FJ.to_mont(s, a), one,
                         limbs_out,
                         value=_field_value(spec, "to_mont", 1)))
        out.append(Entry(f"field/{n}_from_mont",
                         lambda a, s=spec: FJ.from_mont(s, a), one,
                         limbs_out,
                         value=_field_value(spec, "from_mont", 1)))
    # the sweep itself, at its weakest precondition (ANY u32 columns):
    # output limbs < 2^16 and a carry bounded by hi[-1] + 1; the value
    # obligation is the EQUATION its docstring used to state as prose —
    # value(limbs) + carry·2^(16K) == value(cols), exactly
    out.append(Entry("field/carry_sweep", FJ._carry_sweep,
                     (Bound((FJ.FR.n_limbs, 8), jnp.uint32, 0, U32),),
                     [(0, U16), (0, 1 << 16)],
                     value=_carry_sweep_value()))
    out.append(Entry("field/pack_unpack_limb_pairs",
                     lambda v: FJ.unpack_limb_pairs(FJ.pack_limb_pairs(v)),
                     (limb_rows(8, 16),), [(0, U16)],
                     value=_roundtrip_value((8, 16))))
    out.append(Entry("field/cumsum_mont",
                     lambda v: FJ.cumsum_mont(FJ.FR, v),
                     (limb_rows(16, 8),), [(0, U16)],
                     value=_cumsum_value(FJ.FR)))
    return out


def _field_pallas_entries():
    """The standalone fused-multiplier Pallas kernels (DPT_FIELD_MUL=
    pallas): lazy-carry VPU (the round-5 default) and MXU-Toeplitz
    variants, both fields, at the kernel's real lane tile. These were
    parity-tested only (tests/test_field_pallas.py) while the bounds
    pass couldn't see inside pallas_call; now their kernel jaxprs are
    proof obligations like the fused MSM/NTT kernels — closing the
    carried-forward Pallas obligation from PR 5 (strict-mul bodies were
    proved there via the MSM kernel; these are the remaining entry
    points, incl. the lazy local-round / bf16 band paths the MSM kernel
    does not embed)."""
    from ..backend import field_jax as FJ
    from ..backend import field_pallas as FP

    out = []
    for spec in (FJ.FR, FJ.FQ):
        L = spec.n_limbs
        pair = (limb_rows(L, FP.LANE_TILE), limb_rows(L, FP.LANE_TILE))
        n = spec.name.lower()
        for variant in ("lazy", "mxu"):
            # value obligation at a narrow lane tile (8): the kernel
            # body is tile-width-generic (one grid step per tile of the
            # SAME traced program — the bounds entry proves it at the
            # real tile), so the exact grid walk stays cheap while the
            # product contract still covers the lazy local rounds /
            # bf16 band paths
            out.append(Entry(
                f"field/{n}_mont_mul_pallas_{variant}",
                lambda a, b, s=spec: FP.mont_mul(s, a, b), pair,
                [(0, U16)], patches=[(FP, "_VARIANT", variant)],
                value=_field_value(spec, "mont_mul", 2, lanes=8,
                                   patches=[(FP, "LANE_TILE", 8)])))
    return out


def _ntt_entries():
    from ..backend import ntt_jax as NTT

    out = []
    # odd + even log2(n): n=32 exercises the radix-2 fixup stage, n=64
    # the peeled-last-radix-4 path; every (inverse, coset, boundary)
    # combination is a distinct fused program
    for n in (32, 64):
        plan = NTT.get_plan(n)
        for inverse in (False, True):
            for coset in (False, True):
                for boundary in ("mont", "plain"):
                    fn, consts = plan.traced_kernel(
                        inverse, coset, boundary=boundary, radix=4)
                    cnp = {k: np.asarray(v) for k, v in consts.items()}
                    # value obligations ride the n=32 programs: the
                    # stage pipeline is width-generic and n=64 costs
                    # 4x in exact evaluation for the same rule set;
                    # n=64 keeps its interval obligation plus the
                    # batch/defer_perm value entries below
                    val = (_ntt_value(n, inverse, coset, cnp)
                           if n == 32 else None)
                    out.append(Entry(
                        f"ntt/n{n}_radix4_inv{int(inverse)}"
                        f"_coset{int(coset)}_{boundary}",
                        fn, (limb_rows(16, n), cnp), [(0, U16)],
                        value=val))
        # radix-2 parity core (one mode per n keeps the sweep cheap; the
        # stage body is mode-independent modulo pre/post table muls,
        # which the inverse+coset variant includes)
        fn, consts = plan.traced_kernel(True, True, boundary="mont",
                                        radix=2)
        cnp = {k: np.asarray(v) for k, v in consts.items()}
        out.append(Entry(f"ntt/n{n}_radix2_inv1_coset1_mont", fn,
                         (limb_rows(16, n), cnp), [(0, U16)],
                         value=(_ntt_value(n, True, True, cnp)
                                if n == 32 else None)))
        # batched kernel (the prover's round-1/round-3 launches)
        fn, consts = plan.traced_kernel(False, True, radix=4, batch=True)
        cnp = {k: np.asarray(v) for k, v in consts.items()}
        out.append(Entry(f"ntt/n{n}_radix4_batch3_coset", fn,
                         (limb_rows(16, 3, n), cnp), [(0, U16)],
                         value=(_ntt_value(n, False, True, cnp,
                                           batch=True)
                                if n == 32 else None)))
    # deferred output permutation (DPT_R3_BITREV consumer-side fusion):
    # the forward batch kernel that SKIPS the bit-reversal gather — the
    # round-3 producer launches run this program, with the consuming
    # iNTT's input_perm paying the one remaining gather. Same limb
    # bounds as the permuted variant (a gather moves lanes, not values).
    fn, consts = NTT.get_plan(64).traced_kernel(
        False, True, radix=4, batch=True, defer_perm=True)
    cnp = {k: np.asarray(v) for k, v in consts.items()}
    # value obligation includes the output-order relation: the
    # kernel's bit-reversed rows, re-ordered by its OWN consts
    # permutation, must equal the natural-order oracle — a swapped
    # or stale perm table is a value finding, not just a lane move
    out.append(Entry("ntt/n64_radix4_batch3_coset_defer_perm", fn,
                     (limb_rows(16, 3, 64), cnp), [(0, U16)],
                     value=_ntt_value(64, False, True, cnp,
                                      batch=True,
                                      perm=np.asarray(cnp["perm"]))))
    return out


def _msm_entries():
    from ..backend import msm_jax as MSM

    out = []
    # digit extraction at the REAL blinded handle widths the prover
    # commits (domain n -> handles of width n+2 / n+3; jit caches per
    # exact width — the PR 3 bug class this registry pins)
    dom = 64
    for Lw in (dom + 2, dom + 3):
        out.append(Entry(
            f"msm/digits_signed_c7_L{Lw}",
            lambda h: MSM.signed_digits7_from_mont(h, padded_n=2 * dom),
            (limb_rows(16, Lw),), [(0, 127)],
            value=_digits_value(Lw, 7, 64)))
        out.append(Entry(
            f"msm/digits_signed_c8_L{Lw}",
            lambda h: MSM.signed_digits_from_mont(h, padded_n=2 * dom),
            (limb_rows(16, Lw),), [(0, 255)],
            value=_digits_value(Lw, 8, 128)))
        out.append(Entry(
            f"msm/digits_unsigned_c4_L{Lw}",
            lambda h: MSM.digits_from_mont(h, 4, padded_n=2 * dom),
            (limb_rows(16, Lw),), [(0, 15)],
            value=_digits_value(Lw, 4, 0)))

    # bucket-update scan: signed c=7 shape (the default batched
    # pipeline), under every plane-update strategy
    nc, Bt, W = 16, 2, 37
    scan_args = (limb_rows(24, nc), limb_rows(24, nc),
                 Bound((nc,), jnp.bool_, 0, 1),
                 Bound((Bt, W, nc), jnp.uint32, 0, 127))
    plane_out = [(0, U16)] * 3
    for mode, pack in (("onehot", True), ("onehot", False), ("put", False)):
        tag = f"{mode}{'_packed' if pack else ''}"
        out.append(Entry(
            f"msm/bucket_scan_signed_{tag}",
            lambda ax, ay, ainf, d: MSM.bucket_planes_batch_signed(
                ax, ay, ainf, d, group=1),
            scan_args, plane_out,
            patches=[(MSM, "_BUCKET_UPDATE", mode),
                     (MSM, "_PLANE_PACK", pack)]))
    # unsigned small-window scan (tiny keys, c=4: 64 windows x 16
    # buckets, digits < 16)
    uargs = (limb_rows(24, nc), limb_rows(24, nc),
             Bound((nc,), jnp.bool_, 0, 1),
             Bound((Bt, 64, nc), jnp.uint32, 0, 15))
    for mode, pack in (("onehot", True), ("put", False)):
        tag = f"{mode}{'_packed' if pack else ''}"
        out.append(Entry(
            f"msm/bucket_scan_unsigned_{tag}",
            lambda ax, ay, ainf, d: MSM.bucket_planes_batch(
                ax, ay, ainf, d, group=1),
            uargs, plane_out,
            patches=[(MSM, "_BUCKET_UPDATE", mode),
                     (MSM, "_PLANE_PACK", pack)]))

    # fused Pallas bucket kernel (DPT_MSM_KERNEL=pallas): the
    # pallas_call kernel jaxpr is interpreted with the SAME interval
    # rules (bounds._p_pallas_call) — one cell per VMEM plane ref, the
    # grid as a join-until-stable fixpoint. Registering it here also
    # covers the in-VMEM RCB15/mont-mul primitives it shares with
    # curve_pallas/field_pallas (the ROADMAP "Pallas kernels are
    # outside the bounds pass" gap, first bite). c=7 checks both plane
    # packings; c=8/c=4 pin the other digit widths.
    for c, W_, tag, pack in ((7, 37, "c7_packed", True),
                             (7, 37, "c7", False),
                             (8, 32, "c8_packed", True)):
        nb = 1 << (c - 1)
        out.append(Entry(
            f"msm/bucket_pallas_signed_{tag}",
            lambda ax, ay, ainf, d: MSM.bucket_planes_batch_signed(
                ax, ay, ainf, d, group=1),
            (limb_rows(24, nc), limb_rows(24, nc),
             Bound((nc,), jnp.bool_, 0, 1),
             Bound((Bt, W_, nc), jnp.uint32, 0, 2 * nb - 1)),
            plane_out,
            patches=[(MSM, "_MSM_KERNEL", "pallas"),
                     (MSM, "_PLANE_PACK", pack)]))
    out.append(Entry(
        "msm/bucket_pallas_unsigned_c4_packed",
        lambda ax, ay, ainf, d: MSM.bucket_planes_batch(
            ax, ay, ainf, d, group=1),
        uargs, plane_out,
        patches=[(MSM, "_MSM_KERNEL", "pallas"),
                 (MSM, "_PLANE_PACK", True)]))

    # finish tail (both bucket semantics) + cross-chunk fold
    out.append(Entry(
        "msm/finish_signed_c7",
        lambda bx, by, bz: MSM.finish(bx, by, bz, signed=True),
        tuple(limb_rows(24, 37, 64) for _ in range(3)), plane_out))
    out.append(Entry(
        "msm/finish_unsigned_c4",
        lambda bx, by, bz: MSM.finish(bx, by, bz, signed=False),
        tuple(limb_rows(24, 64, 16) for _ in range(3)), plane_out))
    out.append(Entry(
        "msm/fold_planes", MSM.fold_planes,
        tuple(limb_rows(4, 24, 8, 16) for _ in range(3)), plane_out))
    return out


def _curve_entries():
    from ..backend import curve_jax as CJ
    from ..backend import curve_pallas as CP

    pt = lambda: tuple(limb_rows(24, 8) for _ in range(3))
    coords_out = [(0, U16)] * 3
    # the standalone curve_pallas FULL-add kernel at its real lane tile:
    # the mixed-add body is proved through the fused MSM kernel (PR 5),
    # the full add (RCB15 algorithm 7 — cross-chunk folds, finish tail
    # doubling ladder on TPU) was parity-tested only. Closes the last
    # curve piece of the carried-forward Pallas proof obligation.
    ptp = lambda: tuple(limb_rows(24, CP.LANE_TILE) for _ in range(3))
    return [
        Entry("curve/proj_add", CJ.proj_add, (pt(), pt()), coords_out),
        Entry("curve/proj_add_mixed", CJ.proj_add_mixed,
              (pt(), (limb_rows(24, 8), limb_rows(24, 8)),
               Bound((8,), jnp.bool_, 0, 1)), coords_out),
        Entry("curve/proj_add_pallas_full", CP.proj_add, (ptp(), ptp()),
              coords_out),
        Entry("curve/jac_add", CJ.jac_add, (pt(), pt()), coords_out),
        Entry("curve/jac_double", CJ.jac_double, (pt(),), coords_out),
    ]


def _eval_entries():
    """The partial-evaluation (Horner-at-r) kernel: prover round 4's
    device evaluation (prover_jax.poly_eval — block Horner + log-depth
    power combine), which the result-integrity plane (ISSUE 13) now also
    uses as the distributed-EVAL serving kernel on jax workers and as
    the per-chunk shape duplicate-executed across workers. Proved at an
    exact-chunk width and at the prover's real blinded n+2 width (the
    chunked reshape pads internally — both the padded and unpadded
    tails are obligations)."""
    from ..backend import prover_jax as PJ

    out = []
    for L in (256, 66):  # one full chunk; the n=64 blinded n+2 width
        # the value obligation runs the SAME poly_eval at chunk=8 on a
        # 20-coeff poly: 3 Horner blocks + the log-depth power combine
        # + the padded tail are all exercised, without 256 exact scan
        # steps per sample (chunk is a real parameter of the real fn,
        # not a shadow implementation)
        out.append(Entry(
            f"eval/horner_at_r_n{L}",
            lambda p, z: PJ.poly_eval(p, z),
            (limb_rows(16, L), limb_rows(16, 1)), [(0, U16)],
            value=_eval_value(
                20, fn=lambda p, z: PJ.poly_eval(p, z, chunk=8))))
    # the batched round-4 launch shape (B polys, one point each)
    out.append(Entry(
        "eval/horner_at_r_batch4_n66",
        lambda p, z: PJ.poly_eval_many(p, z),
        (limb_rows(4, 16, 66), limb_rows(4, 16, 1)), [(0, U16)],
        value=_eval_value(5, batch=2)))
    return out


def build_registry():
    """All production entries (list of Entry)."""
    return (_field_entries() + _field_pallas_entries() + _ntt_entries()
            + _msm_entries() + _curve_entries() + _eval_entries())


def run_bounds(strict=True, names=None, progress=None, contracts=True):
    """Check every registry entry (+ the carry contracts unless the
    caller runs them separately). Returns (violations, entries_checked)."""
    violations = list(B.check_contracts()) if contracts else []
    entries = build_registry()
    checked = 0
    for e in entries:
        if names is not None and not any(s in e.name for s in names):
            continue
        v = e.check(strict=strict)
        checked += 1
        if progress is not None:
            progress(e.name, v)
        violations.extend(v)
    return violations, checked


def run_values(strict=True, names=None, progress=None):
    """Run every entry's value contract (entries without an obligation
    are skipped — curve group ops and the bucket scans, whose value
    story is the field contracts they compose plus parity tests).
    Returns (violations, entries_checked)."""
    violations = []
    checked = 0
    for e in build_registry():
        if names is not None and not any(s in e.name for s in names):
            continue
        v = e.check_values(strict=strict)
        if v is None:
            continue
        checked += 1
        if progress is not None:
            progress(e.name, v)
        violations.extend(v)
    return violations, checked
