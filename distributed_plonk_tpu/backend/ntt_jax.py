"""Single-device radix-4/radix-2 NTT/iNTT (+ coset variants) over Fr limbs.

Device replacement for `ark-poly`'s Radix2EvaluationDomain as the reference
workers use it (/root/reference/src/worker.rs:82-115): forward/inverse NTT
with optional coset pre/post scaling by the Fr multiplicative generator g=7.
Semantics are bit-identical to the host oracle in poly.py.

Design notes (TPU-first):
- Constant-geometry (Pease) dataflow at BOTH radices: every stage is the
  same program — butterfly equally-spaced sub-arrays and interleave the
  outputs — so the middle stages run as ONE `lax.scan` body and the
  traced/compiled program size is O(1) in n (the round-1 version unrolled
  log2(n) distinct reshaped stages and paid tens of seconds of XLA compile
  per domain). Input is natural order; the output is bit-reversed and one
  gather restores natural order.
- DEFAULT core is RADIX-4 with FUSED twiddles (`DPT_NTT_RADIX`, 2|4): one
  radix-4 stage is the exact composition of two radix-2 stages —
    out[4p+2b+c] = x0 + (-1)^b A x2 + (-1)^c B_b (x1 + (-1)^b A x3),
  x_j = v[p + j*n/4], A = w^e(s,p), B_0 = w^(e/2), B_1 = w^(e/2 + n/4)
  (stage-s radix-2 exponent e(s,p) = bitrev_s(p mod 2^s) * 2^(k-1-s); the
  identities e(s, p+n/4) = e(s,p) and e(s+1, 2p+b) = e(s,p)/2 + b*n/4 hold
  for s <= k-2, which every fused pair satisfies). The radix-2 kernel pays
  log2(n) full HBM round trips plus a per-stage (16, n/2) twiddle gather
  and measured ~2% MFU against the field-mul roofline (round-5 bench
  line); radix-4 HALVES the stage count (one fixup radix-2 stage when log2(n) is odd) and
  cuts per-two-stage twiddle gather volume from n to 3n/4 lanes at the
  same multiply/add count, because the fused-pair twiddles come from three
  precomputed exponent tables instead of being recombined on the fly.
- Scale fusion: the forward-coset pre-scale g^j folds into the FIRST
  radix-4 stage (the four quarters of the g^j table are exactly the four
  per-input scale tables, and the stage-0 twiddles are trivial: A = B = 1,
  C = w^(n/4)); the iNTT 1/n and inverse-coset g^-i scales ride the LAST
  stage's output pass, fused by XLA with the bit-reversal gather — no
  standalone O(n) table-multiply passes over HBM in any mode.
- The first/last stages are peeled out of the scan so their extra work
  (coset tables, output permutation + post-scale) fuses with the butterfly
  instead of forcing a scan-carry materialization; the peel count is
  constant, so compile size stays O(1) in n.
- Twiddles are looked up per stage from ONE Montgomery power table
  w^0..w^(n-1) via precomputed exponent matrices — the reference
  recomputes g.pow per element on the hot path
  (src/worker.rs:77-79,91-93 — a known inefficiency we do not copy).
- `run_stages`/`NttPlan.core_consts` are the shared stage-core API: the
  mesh 4-step NTT (parallel/ntt_mesh.py) and the fleet stage kernels
  (runtime/jax_stages.py) run the SAME butterflies as the single-device
  kernels, so a radix flip covers every path at once.
"""

import os

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from ..constants import R_MOD, FR_GENERATOR, FR_LIMBS, FR_MONT_R
from ..fields import fr_inv, fr_root_of_unity
from . import field_jax as FJ
from .field_jax import FR
from .limbs import ints_to_limbs, limbs_to_ints

RADIX_CHOICES = (2, 4)


def _active_radix(radix=None):
    """Resolve the stage radix: explicit argument > DPT_NTT_RADIX (2|4)
    > 4. Read per call — not latched at import — so the radix-2 path
    stays selectable for parity debugging without rebuilding plans
    (mirrors msm_jax's DPT_BUCKET_UPDATE knob)."""
    if radix is None:
        radix = int(os.environ.get("DPT_NTT_RADIX", "4"))
    if radix not in RADIX_CHOICES:
        raise ValueError(f"NTT radix must be 2 or 4, got {radix!r}")
    return radix


def _mont_table(xs):
    """Host ints -> (16, len) Montgomery-form limb table."""
    return ints_to_limbs([x * FR_MONT_R % R_MOD for x in xs], FR_LIMBS)


def _powers(base, count, start=1):
    out = [start % R_MOD]
    for _ in range(count - 1):
        out.append(out[-1] * base % R_MOD)
    return out


def _bitrev_perm(n):
    log_n = n.bit_length() - 1
    idx = np.arange(n, dtype=np.int64)
    rev = np.zeros(n, dtype=np.int64)
    for s in range(log_n):
        rev |= ((idx >> s) & 1) << (log_n - 1 - s)
    return rev.astype(np.int32)


def _stage_exponents(n):
    """(log n, n/2) int32: exponent of w_n for radix-2 stage s, pair p —
    e(s, p) = bitrev_s(p mod 2^s) * 2^(k-1-s)."""
    k = n.bit_length() - 1
    p = np.arange(n // 2, dtype=np.int64)
    exps = np.zeros((max(k, 1), max(n // 2, 1)), dtype=np.int64)
    for s in range(k):
        low = p & ((1 << s) - 1)
        rev = np.zeros_like(low)
        for b in range(s):
            rev |= ((low >> b) & 1) << (s - 1 - b)
        exps[s] = rev << (k - 1 - s)
    return exps[:k, : n // 2].astype(np.int32)


# --- stage bodies (Montgomery, (16, B, n) rows) ------------------------------

def _stage2(v, e, pow_tab):
    """One constant-geometry radix-2 stage: butterfly the two halves and
    interleave. e: (n/2,) int32 twiddle exponents into pow_tab."""
    n = v.shape[2]
    half = n // 2
    u = v[:, :, :half]
    t = v[:, :, half:]
    tw = pow_tab[:, e]  # (16, n/2) gathered stage twiddles
    t = FJ.mont_mul(FR, t, tw[:, None, :])
    hi = FJ.add(FR, u, t)
    lo = FJ.sub(FR, u, t)
    return jnp.stack([hi, lo], axis=3).reshape(v.shape)


def _stage4(v, e, pow_tab):
    """One constant-geometry radix-4 stage (two fused radix-2 stages):
    butterfly the four quarters and interleave by 4. e: (3, n/4) int32
    exponent rows [A, B, C] into pow_tab (see module docstring)."""
    n = v.shape[2]
    q = n // 4
    x0 = v[:, :, :q]
    x1 = v[:, :, q:2 * q]
    x2 = v[:, :, 2 * q:3 * q]
    x3 = v[:, :, 3 * q:]
    A = pow_tab[:, e[0]][:, None, :]
    B = pow_tab[:, e[1]][:, None, :]
    C = pow_tab[:, e[2]][:, None, :]
    t2 = FJ.mont_mul(FR, x2, A)
    t3 = FJ.mont_mul(FR, x3, A)
    y0 = FJ.add(FR, x0, t2)
    y1 = FJ.sub(FR, x0, t2)
    z0 = FJ.add(FR, x1, t3)
    z1 = FJ.sub(FR, x1, t3)
    bz = FJ.mont_mul(FR, z0, B)
    cz = FJ.mont_mul(FR, z1, C)
    o0 = FJ.add(FR, y0, bz)
    o1 = FJ.sub(FR, y0, bz)
    o2 = FJ.add(FR, y1, cz)
    o3 = FJ.sub(FR, y1, cz)
    return jnp.stack([o0, o1, o2, o3], axis=3).reshape(v.shape)


def _stage4_first(v, pow_tab):
    """FIRST radix-4 stage, plain: the stage-0 twiddles are trivial
    (A = B = 1, C = w^(n/4)), so the stage is add/sub plus ONE broadcast
    multiply. Peeled out of the scan to skip 3 of the generic stage's 4
    table multiplies and all 3 twiddle gathers — bit-identical, because
    the skipped multiplies are by the Montgomery ONE."""
    n = v.shape[2]
    q = n // 4
    x0 = v[:, :, :q]
    x1 = v[:, :, q:2 * q]
    x2 = v[:, :, 2 * q:3 * q]
    x3 = v[:, :, 3 * q:]
    y0 = FJ.add(FR, x0, x2)
    y1 = FJ.sub(FR, x0, x2)
    z0 = FJ.add(FR, x1, x3)
    z1 = FJ.sub(FR, x1, x3)
    i4 = pow_tab[:, q][:, None, None]  # w^(n/4)
    cz = FJ.mont_mul(FR, z1, i4)
    o0 = FJ.add(FR, y0, z0)
    o1 = FJ.sub(FR, y0, z0)
    o2 = FJ.add(FR, y1, cz)
    o3 = FJ.sub(FR, y1, cz)
    return jnp.stack([o0, o1, o2, o3], axis=3).reshape(v.shape)


def _stage4_coset_first(v, coset_tab, pow_tab):
    """FIRST radix-4 stage with the forward-coset pre-scale g^j fused in.

    Stage-0 twiddles are trivial (A = B = 1, C = w^(n/4)), so the fused
    stage is four per-quarter table multiplies — the quarters of the g^j
    coset table ARE the fused tables, no new precompute — plus one
    broadcast multiply by w^(n/4): 5 multiplies per output group where
    the unfused path paid 6 (4 stage + 2 pre-scale per two outputs) AND a
    full standalone HBM pass for the pre-scale."""
    n = v.shape[2]
    q = n // 4
    x0 = FJ.mont_mul(FR, v[:, :, :q], coset_tab[:, None, :q])
    x1 = FJ.mont_mul(FR, v[:, :, q:2 * q], coset_tab[:, None, q:2 * q])
    t2 = FJ.mont_mul(FR, v[:, :, 2 * q:3 * q], coset_tab[:, None, 2 * q:3 * q])
    t3 = FJ.mont_mul(FR, v[:, :, 3 * q:], coset_tab[:, None, 3 * q:])
    y0 = FJ.add(FR, x0, t2)
    y1 = FJ.sub(FR, x0, t2)
    z0 = FJ.add(FR, x1, t3)
    z1 = FJ.sub(FR, x1, t3)
    i4 = pow_tab[:, q][:, None, None]  # w^(n/4)
    cz = FJ.mont_mul(FR, z1, i4)
    o0 = FJ.add(FR, y0, z0)
    o1 = FJ.sub(FR, y0, z0)
    o2 = FJ.add(FR, y1, cz)
    o3 = FJ.sub(FR, y1, cz)
    return jnp.stack([o0, o1, o2, o3], axis=3).reshape(v.shape)


def _radix4_core(v, consts, coset_pre=False):
    """All butterfly stages of the radix-4 kernel on (16, B, n) rows in
    natural order; output is in bit-reversed order (no perm, no 1/n).

    Static structure: [fused-coset | trivial-twiddle first stage] ->
    lax.scan over the middle radix-4 stages -> [peeled last radix-4
    stage | radix-2 fixup stage when log2(n) is odd]. The first stage is
    ALWAYS peeled (its twiddles are trivial, or carry the coset tables);
    the last butterfly always runs OUTSIDE the scan so the caller's
    output permutation (+ inverse scales) fuses with it instead of
    re-reading a materialized scan carry."""
    exps4 = consts["exps4"]
    pow_tab = consts["pow"]
    m4 = exps4.shape[0]
    odd = "fix_exps" in consts
    t0 = 0
    if coset_pre:
        v = _stage4_coset_first(v, consts["pre"], pow_tab)
        t0 = 1
    elif m4 >= 1:
        v = _stage4_first(v, pow_tab)
        t0 = 1
    last4 = (not odd) and m4 > t0
    hi = m4 - 1 if last4 else m4
    if hi > t0:
        def stage(carry, e):
            return _stage4(carry, e, pow_tab), None
        v, _ = lax.scan(stage, v, exps4[t0:hi])
    if last4:
        v = _stage4(v, exps4[m4 - 1], pow_tab)
    if odd:
        v = _stage2(v, consts["fix_exps"], pow_tab)
    return v


def _radix2_core(v, exps, pow_tab):
    """All radix-2 butterfly stages on (16, B, n) rows in natural order;
    output in bit-reversed order (no perm, no 1/n)."""
    n = v.shape[2]
    if n == 1:
        return v

    def stage(carry, e):
        return _stage2(carry, e, pow_tab), None

    v, _ = lax.scan(stage, v, exps)
    return v


def batched_butterflies(v, perm, exps, pow_tab):
    """Constant-geometry radix-2 NTT core on a batch of rows.

    v: (16, B, n) Montgomery limbs in NATURAL order; perm: (n,) bit-reversal
    gather applied at the OUTPUT; exps: (log n, n/2) int32 stage exponents;
    pow_tab: (16, n) Montgomery powers of the (inverse) root of unity.
    Returns the (i)NTT in natural order (1/n scaling NOT included).
    Kept as the radix-2 parity/debug core; prefer `run_stages` +
    `NttPlan.core_consts`, which pick the active radix."""
    return _radix2_core(v, exps, pow_tab)[:, :, perm]


def run_stages(v, consts):
    """Shared stage core: (16, B, n) natural-order Montgomery rows ->
    (i)NTT in natural order (1/n scaling NOT included). The radix is
    carried by the table set (`NttPlan.core_consts`): radix-4 tables
    hold "exps4" (+ "fix_exps" for odd log2(n)), radix-2 tables hold
    "exps". Single-device kernels, the mesh 4-step NTT stages, and the
    fleet panel kernels all run their butterflies through this entry
    point, so one DPT_NTT_RADIX flip covers every path."""
    if "exps4" in consts:
        return _radix4_core(v, consts)[:, :, consts["perm"]]
    return batched_butterflies(v, consts["perm"], consts["exps"],
                               consts["pow"])


class NttPlan:
    """Precomputed tables + cached jitted kernels for one domain size."""

    def __init__(self, n):
        assert n >= 1 and n & (n - 1) == 0
        self.n = n
        self.log_n = n.bit_length() - 1
        w = fr_root_of_unity(n)
        w_inv = fr_inv(w) if n > 1 else 1

        self.perm = _bitrev_perm(n)
        self.exps = _stage_exponents(n)
        self.pow_fwd = _mont_table(_powers(w, max(n, 1)))
        self.pow_inv = _mont_table(_powers(w_inv, max(n, 1)))

        # radix-4 fused-twiddle exponents, derived from the radix-2 rows:
        # stage t fuses radix-2 stages (2t, 2t+1); row [A, B, C] =
        # [e(2t, p), e(2t, p)/2, e(2t, p)/2 + n/4] for p < n/4 (module
        # docstring identities). Odd log2(n) leaves radix-2 stage k-1 as
        # the fixup row.
        k = self.log_n
        if k >= 2:
            q = n // 4
            eA = self.exps[0:(k // 2) * 2:2, :q].astype(np.int64)
            self.exps4 = np.stack(
                [eA, eA >> 1, (eA >> 1) + q], axis=1).astype(np.int32)
            self.fix_exps = self.exps[k - 1] if k % 2 else None
        else:  # n <= 2: no radix-4 stage exists; kernels fall back to radix-2
            self.exps4 = None
            self.fix_exps = None

        g = FR_GENERATOR
        n_inv = fr_inv(n % R_MOD)
        self.coset_tab = _mont_table(_powers(g, n))
        # fused iNTT scale: n^-1 * g^-i (coset) / n^-1 (plain)
        self.inv_coset_tab = _mont_table(_powers(fr_inv(g), n, start=n_inv))
        self.n_inv_tab = _mont_table([n_inv])
        self._fns = {}

    def _effective_radix(self, radix=None):
        """Active radix for this plan: n <= 2 has no radix-4 stage, so the
        radix-2 body covers it (bit-identical either way)."""
        radix = _active_radix(radix)
        return radix if self.exps4 is not None else 2

    def core_consts(self, inverse=False, radix=None):
        """HOST (numpy) table set for `run_stages` at the active radix.
        Callers (mesh shard_map consts, fleet panel kernels) place these
        on device / build PartitionSpecs per entry; every entry is
        replicated-safe (O(n) tables, no per-shard content)."""
        pow_tab = self.pow_inv if inverse else self.pow_fwd
        if self._effective_radix(radix) == 4:
            out = {"perm": self.perm, "exps4": self.exps4, "pow": pow_tab}
            if self.fix_exps is not None:
                out["fix_exps"] = self.fix_exps
        else:
            out = {"perm": self.perm, "exps": self.exps, "pow": pow_tab}
        return out

    def _kernel_consts(self, inverse, coset, radix):
        """Traced-argument tables for one compiled kernel variant."""
        consts = {k: jnp.asarray(v)
                  for k, v in self.core_consts(inverse, radix).items()}
        if coset and not inverse:
            consts["pre"] = jnp.asarray(self.coset_tab)
        if inverse:
            consts["post"] = jnp.asarray(
                self.inv_coset_tab if coset else self.n_inv_tab)
        return consts

    def _apply_batched(self, v, consts, radix, defer_perm=False):
        """(16, B, n) Montgomery rows -> full (i)(coset)NTT: butterflies +
        output permutation + fused scales, radix-selected. The radix-4
        path peels the first/last stages so the coset tables ride the
        first butterfly and the perm gather + inverse scales fuse with
        the last one; the radix-2 path keeps the historical standalone
        pre/post table multiplies (parity/debug reference).

        defer_perm=True (forward launches only) SKIPS the output
        bit-reversal gather: the result stays in constant-geometry
        (bit-reversed) order and the CONSUMER absorbs the permutation —
        the round-3 pipeline keeps every accumulator plane bit-reversed
        and pays one gather at the consuming iNTT's input instead of one
        standalone O(n) pass per FFT launch (DPT_R3_BITREV)."""
        n = self.n
        if radix == 4:
            v = _radix4_core(v, consts, coset_pre="pre" in consts)
        else:
            if "pre" in consts:
                v = FJ.mont_mul(FR, v, consts["pre"][:, None, :])
            v = _radix2_core(v, consts["exps"], consts["pow"])
        if not defer_perm:
            v = v[:, :, consts["perm"]]
        if "post" in consts:
            assert not defer_perm, "defer_perm is forward-only (no post)"
            post = consts["post"]
            if post.shape[1] == 1:  # plain 1/n: broadcast symbolically
                post = jnp.broadcast_to(post, (FR_LIMBS, n))
            v = FJ.mont_mul(FR, v, post[:, None, :])
        return v

    def kernel(self, inverse=False, coset=False, boundary="mont", radix=None):
        """Jitted (16, n) -> (16, n) kernel.

        boundary="mont": input/output in Montgomery form (device-resident
        pipelines). boundary="plain": canonical-form input/output (host
        round-trips); conversion is fused into the same XLA program.

        The O(n) tables (permutation, exponents, power table, coset
        scales) are passed as traced arguments, not baked-in constants, so
        compiled programs and persistent-cache entries stay small. `radix`
        overrides DPT_NTT_RADIX; the memo is keyed on the resolved radix,
        so an env flip never reuses the other radix's executable.
        """
        radix = self._effective_radix(radix)
        key = (inverse, coset, boundary, radix)
        if key not in self._fns:
            plain = boundary == "plain"
            consts = self._kernel_consts(inverse, coset, radix)

            def fn(v, consts):
                if plain:
                    v = FJ.to_mont(FR, v)
                v = self._apply_batched(v[:, None, :], consts,
                                        radix)[:, 0, :]
                if plain:
                    v = FJ.from_mont(FR, v)
                return v

            self._fns[key] = (FJ.named_jit("ntt_plain", fn), consts)
        fn, consts = self._fns[key]
        return lambda v: fn(v, consts)

    def kernel_batch(self, inverse=False, coset=False, radix=None,
                     defer_perm=False):
        """Jitted (16, B, n) -> (16, B, n) Montgomery-boundary kernel: B
        polynomials in ONE launch (the prover's round-1/round-3 NTT batches;
        the reference fans these out as concurrent RPCs,
        dispatcher2.rs:294-321,382-414 — on device they are one program).
        Compiled once per (mode, radix, B). defer_perm=True emits
        the result in bit-reversed order (forward only — the consumer
        absorbs the permutation; see _apply_batched)."""
        radix = self._effective_radix(radix)
        if defer_perm and inverse:
            raise ValueError("defer_perm is forward-only")
        key = (inverse, coset, "batch_noperm" if defer_perm else "batch",
               radix)
        if key not in self._fns:
            consts = self._kernel_consts(inverse, coset, radix)

            def fn(v, consts):
                return self._apply_batched(v, consts, radix,
                                           defer_perm=defer_perm)

            self._fns[key] = (FJ.named_jit("ntt_batch", fn), consts)
        fn, consts = self._fns[key]
        return lambda v: fn(v, consts)

    def kernel_fused(self, inverse=False, coset=False, *, key,
                     prologue=None, epilogue=None, radix=None,
                     input_perm=False, defer_perm=False):
        """Jitted Montgomery-boundary batch kernel with caller-supplied
        pointwise stages fused into the SAME program:

            prologue(*pro_args) -> (16, B, n)  [optional]
            -> (i)(coset)NTT batch
            -> epilogue(result, *epi_args)     [optional]

        This is how round 3 loses its standalone O(n) passes: the gate /
        sigma quotient products run as the epilogue of the selector and
        sigma coset-FFT launches (XLA fuses them with the final stage /
        output permutation, so the (16, B, m) planes never round-trip
        HBM), and the quotient combine runs as the prologue of the coset
        iNTT (fusing into the first inverse stage's reads). `key` must
        uniquely identify the prologue/epilogue semantics — the traced
        closure is memoized under (key, mode) exactly like the plain
        kernels. Returns fn(pro_args, epi_args=()).

        Bit-reversal deferral (DPT_R3_BITREV): defer_perm=True leaves a
        FORWARD launch's output (and so the epilogue's input) in
        constant-geometry order — valid because the epilogues are pure
        pointwise folds, so they hold in any order the operands share.
        input_perm=True gathers the prologue's output through the
        bit-reversal permutation before the butterflies — the one place
        the deferred order returns to natural, fused into the consuming
        iNTT program's first stage reads instead of a standalone pass
        per producer launch."""
        radix = self._effective_radix(radix)
        ck = ("fused", key, inverse, coset, radix, input_perm, defer_perm)
        if ck not in self._fns:
            consts = self._kernel_consts(inverse, coset, radix)

            def fn(pro_args, epi_args, consts):
                v = prologue(*pro_args) if prologue is not None \
                    else pro_args[0]
                if input_perm:
                    v = v[:, :, consts["perm"]]
                v = self._apply_batched(v, consts, radix,
                                        defer_perm=defer_perm)
                if epilogue is not None:
                    return epilogue(v, *epi_args)
                return v

            # `key` contractually identifies the prologue/epilogue
            # semantics (docstring) — callers rebuild structurally
            # identical closures per key; folding closure ids into the
            # key would retrace every prove for nothing
            # the program is named after `key` (ntt_fused_r3gate_0_8,
            # ntt_fused_r3combine, ...): the quotient stream's programs
            # read apart from the plain NTTs in a device trace
            name = "ntt_fused_" + "_".join(
                str(part) for part in (key if isinstance(key, tuple)
                                       else (key,)))
            self._fns[ck] = (FJ.named_jit(name, fn), consts)  # analysis: ok(key identifies prologue/epilogue by contract)
        fn, consts = self._fns[ck]
        return lambda pro_args, epi_args=(): fn(tuple(pro_args),
                                                tuple(epi_args), consts)

    def traced_kernel(self, inverse=False, coset=False, boundary="mont",
                      radix=None, batch=False, defer_perm=False):
        """(jitted fn, consts dict) for one kernel variant — the raw
        pair behind `kernel`/`kernel_batch`'s memo. The static verifier
        (analysis/registry.py) traces `fn(v, consts)` through
        jax.make_jaxpr to interval-check the whole stage pipeline; AOT
        tooling can reuse it for explicit lower()/compile() too."""
        radix = self._effective_radix(radix)
        if batch:
            if boundary != "mont":
                raise ValueError(
                    "batch kernels are Montgomery-boundary only")
            self.kernel_batch(inverse, coset, radix=radix,
                              defer_perm=defer_perm)
            key = (inverse, coset,
                   "batch_noperm" if defer_perm else "batch", radix)
        elif defer_perm:
            raise ValueError("defer_perm needs batch=True")
        else:
            self.kernel(inverse, coset, boundary=boundary, radix=radix)
            key = (inverse, coset, boundary, radix)
        return self._fns[key]

    def aot_compile(self, batch_sizes=(), boundaries=("mont", "plain"),
                    radix=None):
        """Ahead-of-time lower + compile every (inverse, coset) kernel
        variant for this domain at the ACTIVE radix, plus `kernel_batch`
        at the given batch widths, WITHOUT running anything —
        `jit.lower(shapes).compile()` on ShapeDtypeStructs.

        The executables land in the persistent compilation cache
        (field_jax.configure_compile_cache), which is the point: a warmup
        process can pre-bake a store-owned cache so every later server
        start compiles nothing for this shape.
        Returns {"compiled": k, "failed": j, "errors": [...], "radix": r};
        `errors` holds what the compiler said for every variant counted
        in `failed`.
        """
        radix = self._effective_radix(radix)
        compiled = 0
        errors = []
        v_spec = jax.ShapeDtypeStruct((FR_LIMBS, self.n), jnp.uint32)

        def aot(fn, consts, spec):
            nonlocal compiled
            cspec = {k: jax.ShapeDtypeStruct(a.shape, a.dtype)
                     for k, a in consts.items()}
            try:
                fn.lower(spec, cspec).compile()
                compiled += 1
            except Exception as e:  # noqa: BLE001 - reported, never hidden
                errors.append(repr(e))

        for inverse in (False, True):
            for coset in (False, True):
                for boundary in boundaries:
                    fn, consts = self.traced_kernel(
                        inverse, coset, boundary=boundary, radix=radix)
                    aot(fn, consts, v_spec)
                for b in batch_sizes:
                    fn, consts = self.traced_kernel(
                        inverse, coset, radix=radix, batch=True)
                    aot(fn, consts,
                        jax.ShapeDtypeStruct((FR_LIMBS, b, self.n),
                                             jnp.uint32))
        return {"compiled": compiled, "failed": len(errors),
                "errors": errors, "radix": radix}

    # --- host-boundary convenience (int lists, zero-padded to n) -------------

    def run_ints(self, values, inverse=False, coset=False, radix=None):
        assert len(values) <= self.n
        padded = list(values) + [0] * (self.n - len(values))
        v = jnp.asarray(ints_to_limbs(padded, FR_LIMBS))
        out = self.kernel(inverse, coset, boundary="plain", radix=radix)(v)
        return limbs_to_ints(np.asarray(out))


_PLANS = {}


def get_plan(n):
    if n not in _PLANS:
        _PLANS[n] = NttPlan(n)
    return _PLANS[n]
