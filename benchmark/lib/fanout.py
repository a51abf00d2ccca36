"""The frozen host prover fanned out over the host's cores, with the same bytes.

`FanoutBackend` is the frozen `PythonBackend` (`benchmark/reference/backend`)
with the entry points whose work is independent pieces handed to a pool of
processes. Every piece is one call of a frozen function, unchanged:
- a commitment's MSM is cut by point range, each range one call of the
  frozen `curve.g1_msm`, and the partial sums are added with the frozen
  `curve.g1_add_affine`: a point has one affine form, so the sum is the
  point the serial call returns;
- the batch NTTs (`ifft_many`, `coset_fft_many`) are one task a polynomial,
  each a call of the frozen `poly` function the serial backend calls;
- the quotient's coset evaluations are cut into contiguous index ranges,
  each running the frozen loop's body (`quotient_range`) on its own slices.
Everything else, the transcript and every blinding draw among it, stays
the frozen prover's, on the calling process, so a proof is the serial
prove's byte for byte. This module is not among the frozen files, whose
digests stay as they were taken; it imports nothing of jax, numpy or the
program, and its workers are spawned.
"""

import multiprocessing
from concurrent.futures import ProcessPoolExecutor

from ..reference import curve as C
from ..reference import poly as P
from ..reference.backend.python_backend import PythonBackend, _pad
from ..reference.circuit import (GATE_WIDTH, NUM_WIRE_TYPES, Q_C, Q_ECC,
                                 Q_HASH, Q_LC, Q_MUL, Q_O)
from ..reference.constants import FR_GENERATOR, R_MOD
from ..reference.fields import batch_inverse


def pool(workers):
    """`workers` spawned processes for a `FanoutBackend`."""
    return ProcessPoolExecutor(max_workers=workers,
                               mp_context=multiprocessing.get_context("spawn"))


def ranges(total, parts):
    """0..total cut into at most `parts` contiguous (lo, hi) ranges, none
    empty unless `total` is 0, whose lengths differ by one at most."""
    parts = max(1, min(parts, total))
    size, extra = divmod(total, parts)
    out, lo = [], 0
    for i in range(parts):
        hi = lo + size + (i < extra)
        out.append((lo, hi))
        lo = hi
    return out


def msm_range(points, scalar_lists):
    """One point range of several MSMs: a frozen `g1_msm` call each."""
    return [C.g1_msm(points, s) for s in scalar_lists]


def quotient_range(lo, hi, n, m, wq, k, beta, gamma, alpha, alpha_sq_div_n,
                   selectors_coset, sigmas_coset, wires_coset, z_coset,
                   z_next, pi_coset):
    """The frozen `PythonBackend.quotient` loop over the indices lo..hi-1.
    Every list holds those indices only; `z_next` holds z at (i + m/n) % m,
    which wraps past the end of the domain near its top. The evaluation
    points and the batch inverses are worked out for this range, and a
    field element has one residue, so each value is the serial loop's."""
    g = FR_GENERATOR
    ratio = m // n
    eval_points = []
    cur = g * pow(wq, lo, R_MOD) % R_MOD
    for _ in range(lo, hi):
        eval_points.append(cur)
        cur = cur * wq % R_MOD
    z_h_vals = [(pow(g * pow(wq, i, R_MOD) % R_MOD, n, R_MOD) - 1) % R_MOD
                for i in range(ratio)]
    z_h_inv = batch_inverse(z_h_vals, R_MOD)
    shifted = [(e - 1) % R_MOD for e in eval_points]
    shifted_inv = batch_inverse(shifted, R_MOD)

    q_lc = selectors_coset[Q_LC:Q_LC + GATE_WIDTH]
    q_mul = selectors_coset[Q_MUL:Q_MUL + 2]
    q_hash = selectors_coset[Q_HASH:Q_HASH + GATE_WIDTH]
    q_o = selectors_coset[Q_O]
    q_c = selectors_coset[Q_C]
    q_ecc = selectors_coset[Q_ECC]

    out = []
    for i in range(hi - lo):
        a, b, c, d, e = (w[i] for w in wires_coset)
        ab = a * b % R_MOD
        cd = c * d % R_MOD
        gate = (
            q_c[i] + pi_coset[i]
            + q_lc[0][i] * a + q_lc[1][i] * b + q_lc[2][i] * c + q_lc[3][i] * d
            + q_mul[0][i] * ab + q_mul[1][i] * cd
            + q_ecc[i] * ab % R_MOD * cd % R_MOD * e
            + q_hash[0][i] * pow(a, 5, R_MOD)
            + q_hash[1][i] * pow(b, 5, R_MOD)
            + q_hash[2][i] * pow(c, 5, R_MOD)
            + q_hash[3][i] * pow(d, 5, R_MOD)
            - q_o[i] * e
        ) % R_MOD
        acc1 = z_coset[i]
        acc2 = z_next[i]
        ep = eval_points[i]
        for j in range(NUM_WIRE_TYPES):
            t = (wires_coset[j][i] + gamma) % R_MOD
            acc1 = acc1 * ((t + k[j] * ep % R_MOD * beta) % R_MOD) % R_MOD
            acc2 = acc2 * ((t + sigmas_coset[j][i] * beta) % R_MOD) % R_MOD
        perm = alpha * (acc1 - acc2) % R_MOD
        l1_term = alpha_sq_div_n * ((z_coset[i] - 1) % R_MOD) % R_MOD * shifted_inv[i] % R_MOD
        out.append((z_h_inv[(lo + i) % ratio] * ((gate + perm) % R_MOD)
                    + l1_term) % R_MOD)
    return out


class FanoutBackend(PythonBackend):
    """`PythonBackend` whose independent pieces run on `executor`, cut
    `width` ways. The executor is the caller's, and so is its lifetime."""

    def __init__(self, executor, width):
        self.executor, self.width = executor, width

    def ifft_many(self, domain, handles):
        return list(self.executor.map(P.ifft, [domain] * len(handles),
                                      handles))

    def coset_fft_many(self, domain, handles):
        return list(self.executor.map(P.coset_fft, [domain] * len(handles),
                                      handles))

    def commit_many(self, ck, coeff_lists):
        # the serial `msm` takes bases[:len(scalars)]; padding every list to
        # the longest with zero scalars adds nothing to any sum
        size = max((len(s) for s in coeff_lists), default=0)
        lists = [_pad(s, size) for s in coeff_lists]
        futs = [self.executor.submit(msm_range, ck[lo:hi],
                                     [s[lo:hi] for s in lists])
                for lo, hi in ranges(size, self.width)]
        sums = [C.INF] * len(lists)
        for fut in futs:
            sums = [C.g1_add_affine(a, b) for a, b in zip(sums, fut.result())]
        return sums

    def commit_many_h(self, ck, hs):
        return self.commit_many(ck, [_pad(h, len(ck)) for h in hs])

    def quotient(self, n, m, quot_domain, k, beta, gamma, alpha,
                 alpha_sq_div_n, selectors_coset, sigmas_coset, wires_coset,
                 z_coset, pi_coset):
        ratio = m // n
        futs = [self.executor.submit(
            quotient_range, lo, hi, n, m, quot_domain.group_gen, k, beta,
            gamma, alpha, alpha_sq_div_n,
            [s[lo:hi] for s in selectors_coset],
            [s[lo:hi] for s in sigmas_coset],
            [w[lo:hi] for w in wires_coset], z_coset[lo:hi],
            [z_coset[(i + ratio) % m] for i in range(lo, hi)], pi_coset[lo:hi])
            for lo, hi in ranges(m, self.width)]
        return [v for fut in futs for v in fut.result()]
