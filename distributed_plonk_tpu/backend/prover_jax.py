"""Device kernels for the prover's formerly-host-side round math.

These move the two serial hot loops the reference keeps on the dispatcher —
the round-2 permutation running product (/root/reference/src/dispatcher2.rs:
330-345) and the round-3 quotient evaluation loop (dispatcher2.rs:434-504) —
plus polynomial evaluation, linear combination, blinding, and the round-5
synthetic divisions (dispatcher2.rs:651-688) onto the device, so that wire/
selector/sigma/z polynomials stay device-resident in Montgomery form across
all 5 rounds and only transcript scalars cross the host boundary mid-prove
(SURVEY.md §7 stage 4; the capability the reference's 12 declared-but-never-
implemented round3*/round5* RPCs were sketching, src/hello_world.capnp:26-44).

Everything here is O(1)-size traced: sequential recurrences become
log-depth ladders — prefix PRODUCTS as the single-width Hillis-Steele
shift-multiply ladder (field_jax.cumprod_mont; NOT associative_scan,
whose multi-width lowering wedged the remote TPU compile at 2^18 —
see that docstring before reintroducing one), suffix SUMS as the
zero-padded add ladder (field_jax.cumsum_mont), and fixed-exponent
power ladders as bit-table scans.
"""

from functools import partial

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from ..constants import R_MOD, FR_LIMBS, FR_MONT_R
from . import field_jax as FJ
from .field_jax import FR
from .limbs import ints_to_limbs, limbs_to_ints, int_to_limbs

_MONT_ONE = int_to_limbs(FR_MONT_R % R_MOD, FR_LIMBS)


def lift(values):
    """Host canonical ints -> (16, n) Montgomery limb array (host numpy;
    becomes device-resident at first jit use)."""
    return ints_to_limbs([v * FR_MONT_R % R_MOD for v in values], FR_LIMBS)


def lift_scalar(x, ndim=2):
    """One int -> (16, 1, ..) Montgomery broadcastable constant."""
    arr = int_to_limbs(x % R_MOD * FR_MONT_R % R_MOD, FR_LIMBS)
    return arr.reshape((FR_LIMBS,) + (1,) * (ndim - 1))


def lower(v):
    """(16, n) Montgomery device array -> host canonical int list."""
    out = _from_mont_jit(v)
    return limbs_to_ints(np.asarray(out))


def _one_like(v):
    return jnp.broadcast_to(
        jnp.asarray(_MONT_ONE).reshape((FR_LIMBS,) + (1,) * (v.ndim - 1)),
        v.shape)


def _mm(a, b):
    return FJ.mont_mul(FR, a, b)


def cumprod(v, reverse=False):
    """Inclusive prefix (or suffix) products along axis 1 of (16, n):
    the single-width Hillis-Steele ladder (see field_jax.cumprod_mont for
    why not associative_scan — the 2^18 remote-compile wedge)."""
    return FJ.cumprod_mont(FR, v, reverse=reverse)


def fr_pow(base, exp):
    """base^exp for a fixed public int exponent; (16, *b) -> (16, *b).

    Square-and-multiply as a scan over the exponent's bits (MSB first):
    O(1) traced ops, ~255 tiny sequential steps."""
    nbits = max(exp.bit_length(), 1)
    bits = np.array([(exp >> (nbits - 1 - i)) & 1 for i in range(nbits)],
                    dtype=np.uint32)

    def step(acc, bit):
        sq = _mm(acc, acc)
        mul = _mm(sq, base)
        return jnp.where(bit != 0, mul, sq), None

    acc, _ = lax.scan(step, _one_like(base), bits)
    return acc


def batch_inverse(v):
    """Elementwise inverse of (16, n) nonzero Montgomery values.

    Montgomery's trick, log-depth: one prefix-product scan, one suffix-
    product scan, ONE field inversion (fixed-exponent ladder), two
    elementwise products:  v_j^-1 = P_{j-1} * S_{j+1} * (P_n)^-1."""
    pre = cumprod(v)
    suf = cumprod(v, reverse=True)
    total_inv = fr_pow(pre[:, -1:], R_MOD - 2)
    one = _one_like(v[:, :1])
    p_shift = jnp.concatenate([one, pre[:, :-1]], axis=1)
    s_shift = jnp.concatenate([suf[:, 1:], one], axis=1)
    return _mm(_mm(p_shift, s_shift), total_inv)


# --- round 2: permutation running product -----------------------------------

def perm_product(wires, id_tab, sig_tab, beta, gamma):
    """z(w^j) running-product evaluations on device.

    wires/id_tab/sig_tab: (16, w, n) Montgomery (witness values, identity
    permutation values k_i*w^j, and sigma-mapped identity values);
    beta/gamma: (16, 1, 1) Montgomery scalars. Returns (16, n) evals:
    [1, prod_{t<j} num_t/den_t ...] — the reference's O(n*w) host loop
    (src/dispatcher2.rs:330-345) as two reduces + a prefix scan."""
    n = wires.shape[2]
    t = FJ.add(FR, wires, jnp.broadcast_to(gamma, wires.shape))
    num_f = FJ.add(FR, t, _mm(jnp.broadcast_to(beta, id_tab.shape), id_tab))
    den_f = FJ.add(FR, t, _mm(jnp.broadcast_to(beta, sig_tab.shape), sig_tab))

    def wire_reduce(f):  # product over the wire axis (w small, unrolled)
        acc = f[:, 0]
        for i in range(1, f.shape[1]):
            acc = _mm(acc, f[:, i])
        return acc

    nums = wire_reduce(num_f)
    dens = wire_reduce(den_f)
    ratio = _mm(nums, batch_inverse(dens))  # (16, n)
    run = cumprod(ratio[:, :n - 1])
    return jnp.concatenate([_one_like(ratio[:, :1]), run], axis=1)


# --- round 3: quotient evaluations ------------------------------------------

def domain_tables(m, n, gen, group_gen):
    """Witness-independent per-(quot-domain) tables, computed on device.

    Returns dict of (16, m) Montgomery arrays: coset eval points
    ep_i = g*w^i, 1/Z_H(ep) tiled, and 1/(ep - 1)."""
    # ep = g * w^i via prefix products of a constant vector
    w_rep = jnp.broadcast_to(lift_scalar(group_gen),
                             (FR_LIMBS, m)).astype(jnp.uint32)
    pw = cumprod(w_rep)  # w^(i+1)
    g_c = lift_scalar(gen)
    ep = jnp.concatenate(
        [jnp.broadcast_to(g_c, (FR_LIMBS, 1)), _mm(pw[:, :m - 1], g_c)], axis=1)
    ratio = m // n
    one = _one_like(ep)
    zh = FJ.sub(FR, fr_pow(ep[:, :ratio], n), one[:, :ratio])
    # host loop indexes z_h_inv[i % ratio]: the (16, ratio) block repeats
    # m/ratio times
    zh_inv = jnp.tile(batch_inverse(zh), (1, m // ratio))
    shifted_inv = batch_inverse(FJ.sub(FR, ep, one))
    return {"ep": ep, "zh_inv": zh_inv, "shifted_inv": shifted_inv}


def _pow5(x):
    x2 = _mm(x, x)
    return _mm(_mm(x2, x2), x)


def quotient_evals_core(selectors, sigmas, wires, z, z_next, pi, ep, zh_inv,
                        shifted_inv, k, beta, gamma, alpha, alpha_sq_div_n):
    """Coset evaluations of the quotient polynomial, fully elementwise on m
    lanes (the reference's serial O(m) loop, src/dispatcher2.rs:434-504).

    selectors: (16, 13, m); sigmas/wires: (16, 5, m); z/z_next/pi: (16, m);
    ep/zh_inv/shifted_inv: (16, m) domain tables; k: (16, 5, 1); challenge
    scalars (16, 1). z_next is z rolled by -m/n (precomputed by the caller
    so m can be SLICED: every other input is pointwise in the lane index).
    Selector order matches circuit.py (Q_LC x4, Q_MUL x2, Q_HASH x4, Q_O,
    Q_C, Q_ECC)."""
    m = z.shape[1]
    a, b, c, d, e = (wires[:, i] for i in range(5))
    ab = _mm(a, b)
    cd = _mm(c, d)
    gate = FJ.add(FR, selectors[:, 11], pi)  # q_c + pi
    for i, operand in ((0, a), (1, b), (2, c), (3, d)):
        gate = FJ.add(FR, gate, _mm(selectors[:, i], operand))
    gate = FJ.add(FR, gate, _mm(selectors[:, 4], ab))
    gate = FJ.add(FR, gate, _mm(selectors[:, 5], cd))
    for i, operand in ((6, a), (7, b), (8, c), (9, d)):
        gate = FJ.add(FR, gate, _mm(selectors[:, i], _pow5(operand)))
    gate = FJ.add(FR, gate, _mm(selectors[:, 12], _mm(_mm(ab, cd), e)))
    gate = FJ.sub(FR, gate, _mm(selectors[:, 10], e))

    acc1 = z
    acc2 = z_next
    beta_b = jnp.broadcast_to(beta, (FR_LIMBS, m))
    for j in range(5):
        t = FJ.add(FR, wires[:, j], jnp.broadcast_to(gamma, (FR_LIMBS, m)))
        acc1 = _mm(acc1, FJ.add(FR, t, _mm(_mm(jnp.broadcast_to(k[:, j], (FR_LIMBS, m)), ep), beta_b)))
        acc2 = _mm(acc2, FJ.add(FR, t, _mm(sigmas[:, j], beta_b)))
    perm = _mm(jnp.broadcast_to(alpha, (FR_LIMBS, m)), FJ.sub(FR, acc1, acc2))

    one = _one_like(z)
    l1 = _mm(_mm(jnp.broadcast_to(alpha_sq_div_n, (FR_LIMBS, m)),
                 FJ.sub(FR, z, one)), shifted_inv)
    out = FJ.add(FR, _mm(zh_inv, FJ.add(FR, gate, perm)), l1)
    return out


def quotient_evals(selectors, sigmas, wires, z, pi, tabs, k, beta, gamma,
                   alpha, alpha_sq_div_n, ratio):
    """One-shot quotient evaluation over the full domain (the unpacked
    path: host-oracle-shaped backends and the mesh backend, whose GSPMD
    sharding replaces slicing as the memory strategy)."""
    z_next = jnp.roll(z, -ratio, axis=1)
    return quotient_evals_core(
        selectors, sigmas, wires, z, z_next, pi, tabs["ep"], tabs["zh_inv"],
        tabs["shifted_inv"], k, beta, gamma, alpha, alpha_sq_div_n)


# --- streaming round 3: consume each selector/sigma plane as it is made ------
# The residency floor of the packed path is still all 25 coset planes at
# once (6.4 GB packed at m=2^23 — past the measured single-chip budget).
# But the quotient formula reads each SELECTOR plane exactly once (one
# gate term) and each SIGMA plane exactly once (one acc2 factor), so both
# can be folded into running accumulators right after their coset FFT and
# dropped. Only 10 planes ever stay resident: 5 wires, z, z_next, pi→gate,
# acc2 — ~2.5 GB packed at m=2^23, unlocking the n=2^20 prove.
# (Reference formula: /root/reference/src/dispatcher2.rs:434-507.)

# Gate accumulation steps, one jitted program per operand STRUCTURE (the
# wire plane(s) a selector multiplies are passed as arguments, so the 13
# selectors reuse 6 compiled programs instead of 13 — each compile is at
# full quotient-domain width, so the program count is cold-prove
# wall-clock). gate_p is the packed (8, m)
# accumulator (initialized to the pi plane); plane is the UNPACKED
# (16, m) selector coset evals straight from the FFT launch. Selector
# order: circuit.py (Q_LC x4, Q_MUL x2, Q_HASH x4, Q_O, Q_C, Q_ECC).

def _gate_add(gate_p, term):
    return FJ.pack_limb_pairs(
        FJ.add(FR, FJ.unpack_limb_pairs(gate_p), term))


def gate_linear_step(gate_p, plane, w_p):
    """gate += sel * w (the four Q_LC selectors)."""
    return _gate_add(gate_p, _mm(plane, FJ.unpack_limb_pairs(w_p)))


def gate_mul2_step(gate_p, plane, wa_p, wb_p):
    """gate += sel * (wa * wb) (the two Q_MUL selectors)."""
    unp = FJ.unpack_limb_pairs
    return _gate_add(gate_p, _mm(plane, _mm(unp(wa_p), unp(wb_p))))


def gate_pow5_step(gate_p, plane, w_p):
    """gate += sel * w^5 (the four Q_HASH selectors)."""
    return _gate_add(gate_p, _mm(plane, _pow5(FJ.unpack_limb_pairs(w_p))))


def gate_out_step(gate_p, plane, w_p):
    """gate -= sel * e (Q_O)."""
    return FJ.pack_limb_pairs(
        FJ.sub(FR, FJ.unpack_limb_pairs(gate_p),
               _mm(plane, FJ.unpack_limb_pairs(w_p))))


def gate_const_step(gate_p, plane):
    """gate += sel (Q_C)."""
    return _gate_add(gate_p, plane)


def gate_ecc_step(gate_p, plane, w0_p, w1_p, w2_p, w3_p, w4_p):
    """gate += sel * a*b*c*d*e (Q_ECC)."""
    unp = FJ.unpack_limb_pairs
    abcd = _mm(_mm(unp(w0_p), unp(w1_p)), _mm(unp(w2_p), unp(w3_p)))
    return _gate_add(gate_p, _mm(plane, _mm(abcd, unp(w4_p))))


def sigma_step(acc2_p, plane, w_p, beta, gamma):
    """acc2 *= (w + gamma + beta * sigma) — ONE program for all 5 sigmas.

    acc2 is INITIALIZED to the rolled z plane (z_next), so after the 5
    sigma steps it equals quotient_evals_core's full acc2 product."""
    unp = FJ.unpack_limb_pairs
    acc2 = unp(acc2_p)
    wj = unp(w_p)
    t = FJ.add(FR, wj, jnp.broadcast_to(gamma, wj.shape))
    f = FJ.add(FR, t, _mm(plane, jnp.broadcast_to(beta, plane.shape)))
    return FJ.pack_limb_pairs(_mm(acc2, f))


def quotient_combine_slice(wires_p, z_p, gate_p, acc2_p, ep_p,
                           zh_inv_p, shifted_inv_p, k, beta, gamma, alpha,
                           alpha_sq_div_n, j0, *, chunk):
    """Final combine on one lane slice: acc1 from the resident wires + ep
    table, then out = zh_inv*(gate + alpha*(acc1 - acc2)) + l1. Inputs
    packed (acc2 already includes the z_next factor); j0 traced so all
    slices share one program."""
    def cut(a):
        return lax.dynamic_slice_in_dim(a, j0, chunk, axis=a.ndim - 1)

    unp = FJ.unpack_limb_pairs
    z = unp(cut(z_p))
    gate = unp(cut(gate_p))
    acc2 = unp(cut(acc2_p))
    ep = unp(cut(ep_p))
    sh = unp(cut(shifted_inv_p))
    zh = unp(cut(zh_inv_p))
    shape = z.shape
    beta_b = jnp.broadcast_to(beta, shape)
    acc1 = z
    for j in range(5):
        wj = unp(cut(wires_p[j]))
        t = FJ.add(FR, wj, jnp.broadcast_to(gamma, shape))
        kj = jnp.broadcast_to(k[:, j], shape)
        acc1 = _mm(acc1, FJ.add(FR, t, _mm(_mm(kj, ep), beta_b)))
    perm = _mm(jnp.broadcast_to(alpha, shape), FJ.sub(FR, acc1, acc2))
    l1 = _mm(_mm(jnp.broadcast_to(alpha_sq_div_n, shape),
                 FJ.sub(FR, z, _one_like(z))), sh)
    return FJ.add(FR, _mm(zh, FJ.add(FR, gate, perm)), l1)


# --- polynomial utility kernels ---------------------------------------------

def poly_eval(poly, zc, chunk=256):
    """p(z) for (16, L) Montgomery coeffs and a (16, 1) Montgomery point.

    Block Horner: `chunk` sequential steps of (L/chunk)-lane fused
    multiply-adds, then a log-depth combine with powers of z^chunk."""
    L = poly.shape[1]
    lanes = -(-L // chunk)
    pad = lanes * chunk - L
    v = jnp.pad(poly, ((0, 0), (0, pad)))
    v = v.reshape(FR_LIMBS, lanes, chunk).transpose(2, 0, 1)  # (chunk,16,lanes)

    def horner(acc, coeff):
        return FJ.add(FR, _mm(acc, jnp.broadcast_to(zc, acc.shape)), coeff), None

    acc, _ = lax.scan(horner, jnp.zeros((FR_LIMBS, lanes), jnp.uint32),
                      v[::-1])
    # combine chunk evals: sum_j acc_j * (z^chunk)^j
    zk = fr_pow(zc, chunk)
    zk_rep = jnp.broadcast_to(zk, (FR_LIMBS, lanes))
    pw = jnp.concatenate([_one_like(acc[:, :1]), cumprod(zk_rep)[:, :lanes - 1]],
                         axis=1)
    terms = _mm(acc, pw)
    # log-tree sum over lanes
    k = lanes
    while k > 1:
        half = (k + 1) // 2
        hi = terms[:, half:k]
        lo = terms[:, :hi.shape[1]]
        summed = FJ.add(FR, lo, hi)
        terms = jnp.concatenate([summed, terms[:, hi.shape[1]:half]], axis=1)
        k = half
    return terms[:, :1]


def poly_eval_many(polys, zs):
    """Batched evaluation: (B, 16, L) polys at (B, 16, 1) points -> (16, B)
    CANONICAL-form limbs. One device program (and one host round-trip) for
    the prover's whole round 4 — per-call dispatch latency dominates
    scalar-result kernels."""
    evals = jax.vmap(poly_eval)(polys, zs)  # (B, 16, 1)
    return FJ.from_mont(FR, evals[:, :, 0].transpose(1, 0))


def synthetic_divide(poly, zc):
    """Quotient of p(X)/(X - z) (remainder discarded) for a (16, 1)
    Montgomery point, device analog of poly.synthetic_divide:
    q_j = S_{j+1} * z^-(j+1) with S the suffix sums of c_t * z^t — two
    log-depth scans instead of an O(n) recurrence."""
    L = poly.shape[1]
    if L <= 1:
        return poly[:, :0]
    zinv = fr_pow(zc, R_MOD - 2)
    z_rep = jnp.broadcast_to(zc, (FR_LIMBS, L))
    pw = jnp.concatenate([_one_like(poly[:, :1]), cumprod(z_rep)[:, :L - 1]],
                         axis=1)  # z^t
    g = _mm(poly, pw)
    # suffix sums via the single-width add ladder (same remote-compile
    # rationale as cumprod: no multi-width associative_scan lowerings)
    s = FJ.cumsum_mont(FR, g, reverse=True)
    s_next = s[:, 1:]  # S_{j+1}, j = 0..L-2
    ipw = cumprod(jnp.broadcast_to(zinv, (FR_LIMBS, L - 1)))  # z^-(j+1)
    return _mm(s_next, ipw)


def lin_comb(stacked, coeffs):
    """sum_i coeff_i * p_i for (16, k, L) stacked Montgomery polys and
    (16, k, 1) Montgomery coefficients: one scanned multiply-add body."""
    def step(acc, x):
        p, cf = x
        return FJ.add(FR, acc, _mm(p, jnp.broadcast_to(cf, p.shape))), None

    xs = (stacked.transpose(1, 0, 2), coeffs.transpose(1, 0, 2))
    acc, _ = lax.scan(step, jnp.zeros_like(stacked[:, 0]), xs)
    return acc


def add_vanishing_blind(coeffs, b, n):
    """coeffs + blind(X)*(X^n - 1) for a small (16, d1) Montgomery blind:
    out has length n + d1; out[n+i] += b_i, out[i] -= b_i."""
    d1 = b.shape[1]
    ext = jnp.pad(coeffs, ((0, 0), (0, n + d1 - coeffs.shape[1])))
    head = FJ.sub(FR, ext[:, :d1], b)
    tail = FJ.add(FR, ext[:, n:n + d1], b)
    return jnp.concatenate([head, ext[:, d1:n], tail], axis=1)


def _all_zero(t):
    return jnp.all(t == 0)


_all_zero_jit = jax.jit(_all_zero)


def tail_is_zero(poly, degree):
    """True iff all coefficients above `degree` are zero (device reduce)."""
    return bool(_all_zero_jit(poly[:, degree + 1:]))


# --- module-level jitted entry points (stable wrappers => no retracing) ------

_from_mont_jit = FJ.named_jit("fr_from_mont", partial(FJ.from_mont, FR))
_to_mont_jit = FJ.named_jit("fr_to_mont", partial(FJ.to_mont, FR))
poly_eval_jit = jax.jit(poly_eval)
poly_eval_many_jit = jax.jit(poly_eval_many)
synthetic_divide_jit = jax.jit(synthetic_divide)
lin_comb_jit = jax.jit(lin_comb)
blind_jit = jax.jit(add_vanishing_blind, static_argnums=2)
quotient_evals_jit = jax.jit(quotient_evals, static_argnums=11)
gate_linear_step_jit = jax.jit(gate_linear_step)
gate_mul2_step_jit = jax.jit(gate_mul2_step)
gate_pow5_step_jit = jax.jit(gate_pow5_step)
gate_out_step_jit = jax.jit(gate_out_step)
gate_const_step_jit = jax.jit(gate_const_step)
gate_ecc_step_jit = jax.jit(gate_ecc_step)
sigma_step_jit = jax.jit(sigma_step)
quotient_combine_slice_jit = jax.jit(quotient_combine_slice,
                                     static_argnames=("chunk",))
domain_tables_jit = jax.jit(domain_tables, static_argnums=(0, 1, 2, 3))
pack_jit = jax.jit(FJ.pack_limb_pairs)
roll_jit = FJ.named_jit("roll", lambda v, r: jnp.roll(v, -r, axis=1),
                        static_argnums=1)
perm_product_jit = jax.jit(perm_product)
