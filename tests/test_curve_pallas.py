"""Fused Pallas complete projective add vs the XLA path and the host
curve oracle (interpret mode on CPU; the same kernels run compiled on
TPU behind curve_jax.proj_add/_mixed's wide-shape gate)."""

import random

import numpy as np
import jax.numpy as jnp
import pytest

from distributed_plonk_tpu import curve as C
from distributed_plonk_tpu.constants import FQ_LIMBS, FQ_MONT_R, Q_MOD, R_MOD
from distributed_plonk_tpu.backend import curve_jax as CJ
from distributed_plonk_tpu.backend import curve_pallas as CP
from distributed_plonk_tpu.backend.limbs import ints_to_limbs, limbs_to_ints

RNG = random.Random(0xADD)
_R_INV = pow(FQ_MONT_R, Q_MOD - 2, Q_MOD)


def _proj_device(points):
    """list of (affine point | None) -> homogeneous projective Montgomery
    coords (identity = (0 : 1 : 0))."""
    xs = [p[0] * FQ_MONT_R % Q_MOD if p else 0 for p in points]
    ys = [p[1] * FQ_MONT_R % Q_MOD if p else FQ_MONT_R for p in points]
    zs = [FQ_MONT_R if p else 0 for p in points]
    return tuple(jnp.asarray(ints_to_limbs(v, FQ_LIMBS)) for v in (xs, ys, zs))


def _proj_to_affine(coords):
    """(X, Y, Z) limb arrays -> list of (affine point | None)."""
    X, Y, Z = (limbs_to_ints(np.asarray(c)) for c in coords)
    out = []
    for x, y, z in zip(X, Y, Z):
        x, y, z = (v * _R_INV % Q_MOD for v in (x, y, z))
        if z == 0:
            out.append(None)
            continue
        zi = pow(z, Q_MOD - 2, Q_MOD)
        out.append((x * zi % Q_MOD, y * zi % Q_MOD))
    return out


def _rand_pts(n):
    return [C.g1_mul(C.G1_GEN, RNG.randrange(1, R_MOD)) for _ in range(n)]


def _edge_pairs():
    """P==Q, P==-Q, P=identity, Q=identity, both identity — the cases the
    complete formula must flow through with no masking."""
    p = C.g1_mul(C.G1_GEN, 7)
    q = C.g1_mul(C.G1_GEN, 11)
    pneg = (p[0], Q_MOD - p[1])
    return [(p, p), (p, pneg), (None, q), (p, None), (None, None)]


@pytest.mark.slow
def test_proj_add_matches_oracle_and_xla():
    pairs = _edge_pairs() + list(zip(_rand_pts(11), _rand_pts(11)))
    ps = _proj_device([a for a, _ in pairs])
    qs = _proj_device([b for _, b in pairs])
    got = CP.proj_add(ps, qs)
    # bit-identical to the XLA staged-lane path, not merely equal mod p
    ref = CJ.proj_add(ps, qs)
    for g, r in zip(got, ref):
        assert np.array_equal(np.asarray(g), np.asarray(r))
    exp = [C.g1_add_affine(a, b) for a, b in pairs]
    assert _proj_to_affine(got) == exp


@pytest.mark.slow
def test_proj_add_mixed_matches_oracle_and_xla():
    # accumulator with arbitrary Z (built by a prior add), affine addend
    base = _rand_pts(13)
    addend = _rand_pts(13)
    acc = CJ.proj_add(_proj_device(base), _proj_device(base))  # 2*base, Z != R
    pairs = list(zip([C.g1_add_affine(b, b) for b in base], addend))
    # edge rows: acc identity; P == Q; P == -Q
    acc = tuple(jnp.concatenate([a, b], axis=1) for a, b in zip(
        acc, _proj_device([None, addend[0], C.g1_neg(addend[1])])))
    pairs += [(None, addend[0]), (addend[0], addend[0]),
              (C.g1_neg(addend[1]), addend[1])]
    q = _proj_device([b for _, b in pairs])
    got = CP.proj_add_mixed(acc, (q[0], q[1]))
    exp = [C.g1_add_affine(a, b) for a, b in pairs]
    assert _proj_to_affine(got) == exp


@pytest.mark.tier2
def test_dispatch_gate_respects_mask_and_bitmatch():
    """curve_jax.proj_add_mixed with the fused path forced must equal the
    XLA path limb-for-limb, including the q_inf select."""
    n = 9
    pts = _rand_pts(n)
    acc = _proj_device(pts)
    q = _proj_device(_rand_pts(n))
    q_inf = jnp.asarray([i % 3 == 0 for i in range(n)])
    ref = CJ.proj_add_mixed(acc, (q[0], q[1]), q_inf)
    res = CP.proj_add_mixed(acc, (q[0], q[1]))
    got = CJ.pt_select(q_inf, acc, res)
    for g, r in zip(got, ref):
        assert np.array_equal(np.asarray(g), np.asarray(r))
