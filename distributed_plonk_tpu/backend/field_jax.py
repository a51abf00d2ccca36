"""Vectorized prime-field arithmetic for TPU: 16-bit limbs in uint32 lanes.

This is the device replacement for the reference's `ark-ff` field layer
(/root/reference/Cargo.toml:31-37). TPU integer units have no 64-bit multiply,
so elements are radix-2^16 little-endian limb vectors on the LEADING axis
(shape (L, *batch), see limbs.py): a 16x16-bit limb product fits a uint32
exactly, and column sums of <= 2*L such products stay under 2^23 < 2^32, so
schoolbook products accumulate carry-free before one exact carry sweep.

Multiplication is Montgomery (SOS variant: full product, one low half-product
by -p^-1 mod R, one full product by p, one shift) with R = 2^256 (Fr) /
2^384 (Fq) — the same Montgomery radix arkworks uses, so Montgomery-form
values are bit-compatible with the reference's in-memory representation.

All functions are shape-polymorphic over the batch dims and jit-safe (static
limb counts, no data-dependent control flow).
"""

import hashlib
import os
import platform

import numpy as np
import jax
import jax.numpy as jnp

# Persistent compilation cache: limb-arithmetic graphs are large (O(log n)
# fused stages, ~1k ops each) and compile time dominates cold-start
# wall-clock. The cache is partitioned per machine fingerprint: XLA:CPU
# AOT entries embed host CPU features, and loading another host's entries
# fails with "machine feature mismatch" warnings — separate subdirectories
# make every host build/read only its own entries.


def machine_fingerprint():
    """Stable 12-hex id of what XLA:CPU AOT entries actually depend on:
    the architecture + CPU feature flags of this host. Names the
    persistent compile cache's subdirectory, so a change of its value
    sends every run's set-up cold."""
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


def configure_compile_cache(base_dir, min_compile_secs=1.0):
    """Point JAX's persistent compile cache at `base_dir/<machine_fp>` and
    return the directory in use.

    JAX_COMPILATION_CACHE_DIR, when set, places the cache from outside the
    program: jax already read it into its config, so this returns that
    directory and sets no other — the guard lives HERE so no caller can
    bypass it. Called at import with the checkout-local default; the fleet
    worker's --store calls it through store.set_jax_cache_env's
    DPT_JAX_CACHE_DIR so synced compile-cache entries are the ones read."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    path = os.path.join(base_dir, machine_fingerprint())
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs", min_compile_secs)
    return path


def named_jit(name, fn, **jit_kwargs):
    """`jax.jit(fn, **jit_kwargs)` whose program is called `jit_<name>` in
    every profile, HLO dump and compile-cache key. jax names a program
    after the callable's `__name__`; a `functools.partial` has none
    (`jit__unknown`), a lambda is `<lambda>` and the NTT plans' inner
    kernels are all `fn`, so the device trace could not tell MSM from NTT.
    Use it at every jit of a partial, a lambda or an inner function; a
    function that already has a name of its own keeps it."""
    def named(*args, **kwargs):
        return fn(*args, **kwargs)
    named.__name__ = named.__qualname__ = name
    return jax.jit(named, **jit_kwargs)


configure_compile_cache(os.environ.get(
    "DPT_JAX_CACHE_DIR",
    os.path.normpath(os.path.join(
        os.path.dirname(__file__), "..", "..", ".jax_cache"))))
# A Pallas kernel's Mosaic body rides in its program's compile-cache key
# with the source locations of the trace. With full tracebacks those hold
# every frame down from whoever first called the program, so a program that
# a key build traces first and a later process reaches from a prove would
# be two cache entries, compiled again on every restart. Innermost frames
# only: the key is the program's own, whoever traced it.
jax.config.update("jax_include_full_tracebacks_in_locations", False)

from ..constants import (
    LIMB_BITS,
    LIMB_MASK,
    FR_LIMBS,
    FQ_LIMBS,
    R_MOD,
    Q_MOD,
    FR_MONT_R2,
    FR_MONT_INV,
    FQ_MONT_R2,
    FQ_MONT_INV,
)
from .limbs import int_to_limbs


def _const_bytes(value, n_bytes):
    """Host int -> (n_bytes,) radix-2^8 little-endian digits (numpy)."""
    return np.array([(value >> (8 * i)) & 0xFF for i in range(n_bytes)],
                    dtype=np.float32)


def _toeplitz_bytes(value, in_bytes, out_bytes):
    """Constant banded (Toeplitz) matrix T with T[k, i] = byte_{k-i}(value):
    T @ a8 gives the byte-column sums of value * a for any a presented as
    (in_bytes, *batch) radix-2^8 digits — i.e. multiplication by a constant
    is literally a matmul, which XLA tiles onto the MXU (bf16 x bf16 with
    f32 accumulation; every operand is an integer <= 255, every column sum
    <= 96 * 255^2 < 2^23, so the float path is exact)."""
    bts = _const_bytes(value, in_bytes)  # constant has <= in_bytes bytes here
    T = np.zeros((out_bytes, in_bytes), dtype=np.float32)
    for k in range(out_bytes):
        for i in range(in_bytes):
            j = k - i
            if 0 <= j < in_bytes:
                T[k, i] = bts[j]
    return T


class FieldSpec:
    """Static per-field constants (host numpy; embedded into jit traces)."""

    def __init__(self, name, mod, n_limbs, mont_r2, mont_inv):
        self.name = name
        self.mod = mod
        self.n_limbs = n_limbs
        self.mod_limbs = int_to_limbs(mod, n_limbs)
        self.r2_limbs = int_to_limbs(mont_r2, n_limbs)
        # full-width -p^-1 mod 2^(16L) for the SOS reduction low half-product
        self.ninv_limbs = int_to_limbs(mont_inv, n_limbs)
        self.one_limbs = int_to_limbs(1, n_limbs)
        # 2^(16L) - p: adding it == subtracting p, with the sweep's carry
        # bit flagging whether the subtraction stayed nonnegative
        self.negmod_limbs = int_to_limbs((1 << (LIMB_BITS * n_limbs)) - mod,
                                         n_limbs)
        # MXU operands for the two constant products of Montgomery SOS
        # (t_lo * ninv mod R needs only the low half; m * p needs the full
        # double-width product) — see mont_mul
        nb = 2 * n_limbs
        self.ninv_toeplitz = _toeplitz_bytes(mont_inv % (1 << (8 * nb)), nb, nb)
        self.mod_toeplitz = _toeplitz_bytes(mod, nb, 2 * nb)


FR = FieldSpec("Fr", R_MOD, FR_LIMBS, FR_MONT_R2, FR_MONT_INV)
FQ = FieldSpec("Fq", Q_MOD, FQ_LIMBS, FQ_MONT_R2, FQ_MONT_INV)


# --- checked carry/exactness contracts ---------------------------------------
# Every _carry_sweep caller that DROPS the carry lane relies on one of the
# side conditions below: they are modular-number-theory facts about the
# field constants that per-element interval analysis (analysis/bounds.py)
# cannot derive, because the limb-column representation is redundant (a
# column vector bounds the value only up to ~2^7 x slack). They used to
# live as prose in _carry_sweep's docstring; now they are machine-checked
# inequalities over the ACTUAL moduli/limb counts — `python -m
# distributed_plonk_tpu.analysis` (and tests/test_analysis.py) evaluates
# every contract for both FieldSpecs, so a field/limb-layout change that
# silently breaks a zero-carry assumption fails CI instead of corrupting
# proofs. `R(spec)` below is the Montgomery radix 2^(16*L).
#
# These inequalities are the BOUNDS half of the story (machine arithmetic
# == exact integer semantics). The ALGEBRAIC half — mont_mul really
# computes a*b*R^-1 mod p, add/sub/neg/to_mont/from_mont their mod-p
# claims, _carry_sweep the equation value(limbs) + carry*2^(16K) ==
# value(cols) — is no longer prose either: every registered entry point
# of this module carries a value obligation the exact-evaluation pass
# (analysis/values.py via analysis/registry.py) checks at seeded +
# corner sample points, on BOTH multiplier paths. A dropped carry lane
# in the f32 path that keeps every limb in range is invisible to the
# interval pass by construction and is caught there (the seeded-mutant
# harness analysis/mutants.py proves that stays true).

def _R(spec):
    return 1 << (LIMB_BITS * spec.n_limbs)


CARRY_CONTRACTS = (
    {"name": "cond_sub_fits",
     "claim": "v < 2p fits in L limbs (2p <= R), so _cond_sub_mod/add's "
              "lane-1 sweep and sub's lane-2 wrap both have carry <= 1 "
              "and the assumed-zero carry of the reduced lane is zero",
     "holds": lambda spec: 2 * spec.mod <= _R(spec)},
    {"name": "mont_hi_fits",
     "claim": "for reduced inputs a,b < p the Montgomery high half "
              "(a*b + m*p)/R is < 2p (p^2 + R*p <= 2*p*R, i.e. p <= R), "
              "so mont_mul's final _cond_sub_mod sees a value that fits",
     "holds": lambda spec: spec.mod ** 2 + _R(spec) * spec.mod
              <= 2 * spec.mod * _R(spec)},
    {"name": "u32_colsum",
     "claim": "u32-path product columns stay carry-free: <= 2L split "
              "halves per column, each < 2^16, lo+hi recombined "
              "(4L * (2^16-1) < 2^32)",
     "holds": lambda spec: 4 * spec.n_limbs * (LIMB_MASK + 1) < 1 << 32},
    {"name": "byte_colsum_f32_exact",
     "claim": "f32-path byte-column sums stay exactly representable: "
              "<= 4L byte products per column, each <= 255^2 "
              "(4L * 255^2 <= 2^24, the f32 integer round-trip bound)",
     "holds": lambda spec: 4 * spec.n_limbs * 255 ** 2 <= 1 << 24},
    {"name": "combined_cols_u32",
     "claim": "recombined 16-bit columns (even + 2^8 * odd byte columns) "
              "fit u32 before the sweep (4L * 255^2 * 257 < 2^32)",
     "holds": lambda spec: 4 * spec.n_limbs * 255 ** 2 * 257 < 1 << 32},
    {"name": "sweep_preadd_single_bit",
     "claim": "_carry_sweep's pre-add s_i = lo_i + hi_{i-1} < 2^17, so "
              "the residual inter-limb carry is a single bit and the "
              "Kogge-Stone (generate, propagate) recurrence is exact",
     "holds": lambda spec: 2 * LIMB_MASK < 1 << 17},
)


def _bcast_const(limbs, ndim):
    """(L,) host constant -> (L, 1, ..., 1) for broadcasting against batch."""
    return jnp.asarray(limbs).reshape(limbs.shape + (1,) * (ndim - 1))


def _carry_sweep(cols):
    """Exact carry propagation. cols: (K, *batch) uint32 (ANY u32 entries:
    the f32 path feeds combined even+odd byte columns up to ~2^30 here).

    Returns (limbs, carry_out): limbs (K, *batch) all < 2^16, carry_out the
    overflow past the top limb. CONTRACT: callers that drop the carry
    assert the value fits in K limbs (or intend the mod-2^(16K)
    truncation); each such assumption is a named, machine-checked
    inequality in CARRY_CONTRACTS, evaluated for every FieldSpec by the
    static verifier (analysis/bounds.py::check_contracts) — do not add a
    carry-dropping call site without extending that table. The sweep's
    own value equation — value(limbs) + carry*2^(16K) == value(cols),
    exactly, for ANY u32 columns — is machine-checked too (the
    field/carry_sweep value obligation in analysis/registry.py).

    Log-depth Kogge-Stone instead of a K-step ripple chain: pre-add each
    column's high bits into the next column (s_i = lo_i + hi_{i-1} < 2^17,
    so the residual inter-limb carry is a single bit), then resolve the
    bit-carry recurrence b_i = G_i | (P_i & b_{i-1}) with an associative
    scan over (generate, propagate) pairs. Traced ops: O(log K), and the
    work is whole-array passes (VPU-friendly) rather than per-limb rows.
    """
    lo = cols & LIMB_MASK
    hi = cols >> LIMB_BITS
    zero_row = jnp.zeros_like(hi[:1])
    s = lo + jnp.concatenate([zero_row, hi[:-1]], axis=0)  # s_i < 2^17

    def shift_down(x, k):  # x[i] -> x[i-k], zeros shifted in at the bottom
        return jnp.concatenate([jnp.zeros_like(x[:k]), x[:-k]], axis=0)

    gen = s > LIMB_MASK
    prop = s == LIMB_MASK
    k = 1
    while k < s.shape[0]:  # hand-rolled KS: cheaper lowering than
        gen = gen | (prop & shift_down(gen, k))  # associative_scan here
        prop = prop & shift_down(prop, k)
        k *= 2
    b_in = shift_down(gen, 1)
    limbs = (s + b_in) & LIMB_MASK
    carry = hi[-1] + gen[-1]
    return limbs, carry


def _skew_colsum(m, shift, dtype=jnp.uint32):
    """Anti-diagonal column sums: out[k] = Σ_i m[i, k - i - shift].

    m: (rows, w, *batch). Each row i is logically shifted right by i+shift,
    then columns are summed — computed with pure pad/reshape/slice/reduce
    (row i of the flattened (rows, W-1) view starts at i·(W-1) = i·W - i,
    i.e. sits i slots earlier, which IS the skew), so the traced program is
    O(1) ops instead of an O(rows) chain of dynamic-update-slices. Integer
    entries must be < 2^16 (sums of <= 96 terms stay far below 2^32);
    float entries must keep sums < 2^24 so f32 accumulation stays exact.
    """
    rows, w = m.shape[0], m.shape[1]
    batch = m.shape[2:]
    pad = [(0, 0)] * m.ndim
    pad[1] = (shift, rows)
    mp = jnp.pad(m, pad)  # (rows, W) with W = w + shift + rows
    W = w + shift + rows
    flat = mp.reshape((rows * W,) + batch)
    skewed = flat[: rows * (W - 1)].reshape((rows, W - 1) + batch)
    return jnp.sum(skewed, axis=0, dtype=dtype)  # (W-1, *batch)


# Multiplier path (DPT_FIELD_MUL):
#   auto (default): the Pallas fused kernel on TPU for wide shapes, the
#       XLA f32 byte-product path otherwise. Measured round 4 (v5e): the
#       XLA paths materialize their byte-column transients to HBM
#       (~18 KB/lane/mul — the MSM's measured traffic wall and a 24 GB
#       OOM at 2^18-lane calls); the Pallas kernel keeps them in VMEM and
#       runs 42 ns/mul Fr / 85 ns/mul Fq, ~10-40x the XLA paths.
#   f32: XLA byte-product path only (f32 VPU products + bf16 MXU Toeplitz
#       constant products).
#   u32: the round-2 integer path (u32 multiply is an emulation ~50x
#       below the f32 FMA rate; kept as a reference oracle).
#   pallas: force the Pallas kernel for any wide-enough shape (off-TPU
#       that needs DPT_PALLAS_INTERPRET=1 — slow, test-only).
MUL_CHOICES = ("pallas", "f32", "u32")
_MUL_MODE = os.environ.get("DPT_FIELD_MUL", "auto")


def _mul_path():
    """Resolved multiplier mode name: the DPT_FIELD_MUL knob (env, or a
    test-patched _MUL_MODE attr), "auto" (platform default) when unset.
    Read per call like msm_jax's dispatch knobs."""
    return _MUL_MODE


def _f32_active():
    """Whether the XLA byte-product/MXU path (vs the u32 reference
    oracle) backs non-Pallas mont_muls under the resolved mode."""
    return _mul_path() != "u32"

# below this many lanes the per-call overhead of a pallas kernel exceeds
# the XLA path's cost (scalar/narrow shapes: transcript scalars, finish
# tails) — those stay on the fused-XLA path
_PALLAS_MIN_LANES = int(os.environ.get("DPT_PALLAS_MIN_LANES", "2048"))


import contextlib
import threading

_pallas_off = threading.local()


@contextlib.contextmanager
def pallas_disabled():
    """Disable the Pallas dispatch for mont_muls traced inside this block.

    Used by MeshBackend around its GSPMD-auto-sharded round math: a
    pallas_call has no SPMD partitioning rule, so letting the partitioner
    meet one on a sharded operand outside shard_map would either fail or
    silently all-gather the shards. The explicit shard_map paths (mesh
    NTT/MSM) are per-device local and keep the kernel."""
    prev = getattr(_pallas_off, "v", False)
    _pallas_off.v = True
    try:
        yield
    finally:
        _pallas_off.v = prev


def pallas_interpret():
    """Whether Pallas kernels run in interpret mode: only when a test ASKS
    for it (DPT_PALLAS_INTERPRET=1, which tests/conftest.py sets for the
    CPU suite). The device path never falls into it — with the knob off a
    pallas_call goes through Mosaic or raises what the compiler said, so a
    kernel the chip refuses cannot pass as an emulated run."""
    return os.environ.get("DPT_PALLAS_INTERPRET", "0") != "0"


def _use_pallas(shape):
    if getattr(_pallas_off, "v", False):
        return False
    lanes = 1
    for d in shape[1:]:
        lanes *= d
    mode = _mul_path()
    if mode in ("u32", "f32") or lanes < _PALLAS_MIN_LANES:
        return False
    if mode == "pallas":
        return True
    return jax.default_backend() == "tpu"


def pallas_mul_possible():
    """Whether a mont_mul of ANY width could dispatch the Pallas kernel
    under the current guard, mode and platform — _use_pallas without the
    shape (parallel/ntt_mesh decides check_vma with it before it knows
    what widths the traced body multiplies at)."""
    if getattr(_pallas_off, "v", False):
        return False
    mode = _mul_path()
    return mode == "pallas" or (mode == "auto"
                                and jax.default_backend() == "tpu")


def pack_limb_pairs(v):
    """(2K, ...) u32 16-bit limbs -> (K, ...) u32 packed pairs (lo | hi<<16).

    Layout compression for RESIDENT arrays, not an arithmetic form: kernels
    unpack slices on the fly. Used by the MSM bucket-plane scan carries and
    round 3's coset-eval set (whose 25 polynomials at 8n were the measured
    single-chip 2^19 OOM, scale_2p19_r04.log)."""
    return v[0::2] | jnp.left_shift(v[1::2], 16)


def unpack_limb_pairs(p):
    """(K, ...) packed pairs -> (2K, ...) u32 16-bit limbs."""
    lo = p & 0xFFFF
    hi = jnp.right_shift(p, 16)
    return jnp.stack([lo, hi], axis=1).reshape((2 * p.shape[0],) + p.shape[1:])


def _bytes_f32(a):
    """(L, *b) u32 16-bit limbs -> (2L, *b) f32 radix-2^8 digits."""
    lo = (a & 0xFF).astype(jnp.float32)
    hi = ((a >> 8) & 0xFF).astype(jnp.float32)
    s = jnp.stack([lo, hi], axis=1)  # (L, 2, *b)
    return s.reshape((2 * a.shape[0],) + a.shape[1:])


def _combine_byte_cols(col8, out_limbs):
    """(K8, *b) f32 byte-column sums (each < 2^23, exact) -> (out_limbs, *b)
    u32 16-bit-column sums: out[k] = col8[2k] + 2^8 * col8[2k+1] (< 2^31)."""
    c = col8.astype(jnp.uint32)
    c = _pad_rows(c, 2 * out_limbs)[: 2 * out_limbs]
    ev = c[0::2]
    od = c[1::2]
    return ev + (od << 8)


def _mul_columns_f32(a, b, out_limbs):
    """Variable x variable product columns via exact f32 byte products."""
    a8 = _bytes_f32(a)
    b8 = _bytes_f32(b)
    p = a8[:, None] * b8[None, :]  # (2la, 2lb, *batch), exact (<= 255^2)
    col8 = _skew_colsum(p, 0, dtype=jnp.float32)
    return _combine_byte_cols(col8, out_limbs)


def _mul_columns_const(T, a, out_limbs):
    """Constant x variable product columns as ONE matmul: T is a banded
    byte-Toeplitz host matrix (_toeplitz_bytes), a is (L, *batch) 16-bit
    limbs. bf16 operands (integers <= 255: exact), f32 accumulation
    (column sums < 2^23: exact) — this is the MXU path."""
    a8 = _bytes_f32(a).astype(jnp.bfloat16)
    col8 = jax.lax.dot_general(
        jnp.asarray(T, dtype=jnp.bfloat16), a8,
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return _combine_byte_cols(col8, out_limbs)


def _mul_columns_u32(a, b, out_limbs):
    """Round-2 u32 fallback path (DPT_FIELD_MUL=u32)."""
    la, lb = a.shape[0], b.shape[0]
    p = a[:, None] * b[None, :]  # (la, lb, *batch), each product < 2^32
    lo = _skew_colsum(p & LIMB_MASK, 0)  # cols 0 .. la+lb-2
    hi = _skew_colsum(p >> LIMB_BITS, 1)  # cols 1 .. la+lb-1
    lo = _pad_rows(lo[:out_limbs], out_limbs)
    hi = _pad_rows(hi[:out_limbs], out_limbs)
    return lo + hi


def _mul_columns(a, b, out_limbs):
    """Carry-free column sums of the product, truncated to out_limbs limbs."""
    if _f32_active():
        return _mul_columns_f32(a, b, out_limbs)
    return _mul_columns_u32(a, b, out_limbs)


def _pad_rows(a, n):
    if a.shape[0] == n:
        return a
    return jnp.pad(a, [(0, n - a.shape[0])] + [(0, 0)] * (a.ndim - 1))


def _sweep_pair(cols_a, cols_b):
    """Carry-sweep two column vectors in ONE vectorized sweep.

    Stacks them on a lane axis so the log-depth carry machinery is traced
    once; returns ((limbs_a, limbs_b), (carry_a, carry_b)).
    """
    pair = jnp.stack([cols_a, cols_b], axis=1)  # (K, 2, *batch)
    limbs, carry = _carry_sweep(pair)
    return (limbs[:, 0], limbs[:, 1]), (carry[0], carry[1])


def _cond_sub_mod(spec, cols):
    """Value of `cols` reduced once: v - p if v >= p else v  (v < 2p).

    Takes UNCARRIED columns (any u32 entries — the sweep's pre-add bound
    is per-limb, not per-column; see _carry_sweep) and resolves both
    candidates with a single paired sweep: lane2 adds 2^(16L) - p, whose
    carry-out flags v >= p.
    """
    negp = _bcast_const(spec.negmod_limbs, cols.ndim)
    (t, d), (_, c2) = _sweep_pair(cols, cols + negp)
    return jnp.where((c2 != 0)[None], d, t)


def add(spec, a, b):
    """a + b mod p (inputs < p): one paired sweep."""
    return _cond_sub_mod(spec, a + b)


def sub(spec, a, b):
    """a - b mod p (inputs < p): one paired sweep.

    Lane1 = a + ~b + 1 (= a-b mod 2^(16L); carries iff a >= b);
    lane2 = lane1 + p (the wrapped-around candidate).
    """
    nb = (_pad_rows(b, a.shape[0]) ^ LIMB_MASK)
    base = (a + nb).at[0].add(1)
    p = _bcast_const(spec.mod_limbs, a.ndim)
    (d, dp), (c1, _) = _sweep_pair(base, base + p)
    return jnp.where((c1 != 0)[None], d, dp)


def neg(spec, a):
    zero = jnp.zeros_like(a)
    return sub(spec, zero, a)


def mont_mul(spec, a, b):
    """Montgomery product: a*b*R^-1 mod p, inputs/outputs reduced (< p).

    SOS with column-level accumulation: the three partial products stay as
    uncarried column sums (each < 2^22, so sums of two < 2^23 are still
    exact in u32) and only four short sweeps run: t mod R; m; the low-half
    carry-out of t + m*p (those limbs are identically 0 mod R); and the
    final reduce of the uncarried high half (t + m*p)/R, folded into
    _cond_sub_mod's paired sweep.

    Wide shapes on TPU dispatch to the Pallas fused kernel
    (field_pallas.py) — same algorithm, intermediates in VMEM.

    The claim in the first line IS the machine-checked contract: the
    field/*_mont_mul_{f32,u32} registry entries exactly evaluate this
    body and assert value(out) == a*b*R^-1 mod p with out < p, at
    corner and random points, for both fields and both column paths.
    """
    if _use_pallas(jnp.broadcast_shapes(a.shape, b.shape)):
        from . import field_pallas as FP
        return FP.mont_mul(spec, a, b)
    l = spec.n_limbs
    t_cols = _mul_columns(a, b, 2 * l)  # a*b < p^2, uncarried
    t_lo, c_t = _carry_sweep(t_cols[:l])  # exact t mod R + carry into col l
    if _f32_active():
        # constant products ride the MXU as banded-Toeplitz matmuls
        m_cols = _mul_columns_const(spec.ninv_toeplitz, t_lo, l)
        m, _ = _carry_sweep(m_cols)  # m = (t mod R)*(-p^-1) mod R
        mp_cols = _mul_columns_const(spec.mod_toeplitz, m, 2 * l)
    else:
        ninv = _bcast_const(spec.ninv_limbs, a.ndim)
        m, _ = _carry_sweep(_mul_columns(t_lo, ninv, l))
        p = _bcast_const(spec.mod_limbs, a.ndim)
        mp_cols = _mul_columns(m, p, 2 * l)  # m*p < R*p, uncarried
    # low half of t + m*p is == 0 mod R: only its carry-out matters
    _, c_lo = _carry_sweep(mp_cols[:l] + t_lo)
    hi = (mp_cols[l:] + t_cols[l:]).at[0].add(c_t + c_lo)
    return _cond_sub_mod(spec, hi)  # (t + m*p) / R < 2p


def to_mont(spec, a):
    return mont_mul(spec, a, _bcast_const(spec.r2_limbs, a.ndim) * jnp.ones_like(a[:1]))


def from_mont(spec, a):
    one = _bcast_const(spec.one_limbs, a.ndim) * jnp.ones_like(a[:1])
    return mont_mul(spec, a, one)


def mont_sq(spec, a):
    return mont_mul(spec, a, a)


def cumprod_mont(spec, v, reverse=False):
    """Inclusive prefix (or suffix) Montgomery products along axis 1 of a
    (L, n) array, as a Hillis-Steele shift-multiply ladder.

    NOT lax.associative_scan: the Blelchoch-style lowering runs ~2*log n
    levels of DIFFERENT widths, which (a) instantiates one fused Pallas
    multiplier per width — the resulting multi-Mosaic program wedged the
    remote TPU compile twice at 2^18 scale (round 4) — and (b) even on
    the XLA mul path produces an HLO whose compile never returned for
    jit(perm_product). Here every level is ONE full-width mont_mul of
    the SAME shape (identity-padded shift), so the whole ladder reuses a
    single kernel instantiation: log n levels, n*log n muls instead of
    ~2n — at 2^18 that is 4.7M extra lane-muls, milliseconds at the
    measured mul rate, for a compile that returns in seconds.
    """
    L, n = v.shape
    mont_one = (1 << (LIMB_BITS * spec.n_limbs)) % spec.mod
    one_col = jnp.asarray(
        int_to_limbs(mont_one, spec.n_limbs)).reshape(L, 1)
    k = 1
    while k < n:
        ones = jnp.broadcast_to(one_col, (L, k))
        if reverse:
            shifted = jnp.concatenate([v[:, k:], ones], axis=1)
        else:
            shifted = jnp.concatenate([ones, v[:, :-k]], axis=1)
        v = mont_mul(spec, v, shifted)
        k *= 2
    return v


def cumsum_mont(spec, v, reverse=False):
    """Inclusive prefix (or suffix) modular sums along axis 1 of (L, n):
    the zero-padded Hillis-Steele ladder — same single-width rationale as
    cumprod_mont (every level one full-width add of the same shape; no
    multi-width associative_scan lowering near the remote compiler)."""
    L, n = v.shape
    k = 1
    while k < n:
        zeros = jnp.zeros((L, k), v.dtype)
        if reverse:
            shifted = jnp.concatenate([v[:, k:], zeros], axis=1)
        else:
            shifted = jnp.concatenate([zeros, v[:, :-k]], axis=1)
        v = add(spec, v, shifted)
        k *= 2
    return v


def is_zero(spec, a):
    return jnp.all(a == 0, axis=0)


def eq(spec, a, b):
    return jnp.all(a == b, axis=0)


def select(cond, a, b):
    """cond: (*batch,) bool; a, b: (L, *batch) -> where(cond, a, b)."""
    return jnp.where(cond[None], a, b)


def double(spec, a):
    return add(spec, a, a)
