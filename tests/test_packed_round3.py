"""Packed round 3: limb-packed coset planes + sliced quotient evaluation.

The single-device memory strategy for the reference's quotient pipeline
(/root/reference/src/dispatcher2.rs:382-507): coset evals live packed
(two 16-bit limbs per u32) and the quotient evaluation runs in lane
slices. These tests pin the invariant that the packed+sliced path is
VALUE-IDENTICAL to the one-shot unpacked path (which the host oracle and
mesh backend keep using).
"""

import random

import numpy as np
import pytest
import jax.numpy as jnp

from distributed_plonk_tpu.constants import R_MOD
from distributed_plonk_tpu.poly import Domain
from distributed_plonk_tpu.backend import field_jax as FJ
from distributed_plonk_tpu.backend import prover_jax as PJ
from distributed_plonk_tpu.backend.jax_backend import JaxBackend

RNG = random.Random(0x9A4D)


def _rand_h(length):
    return jnp.asarray(PJ.lift([RNG.randrange(R_MOD) for _ in range(length)]))


def test_pack_unpack_roundtrip():
    v = _rand_h(320)
    p = PJ.pack_jit(v)
    assert p.shape == (8, 320)
    assert np.array_equal(np.asarray(FJ.unpack_limb_pairs(p)), np.asarray(v))


@pytest.mark.tier2
def test_quotient_streamed_matches_unpacked_multislice():
    """The streaming round 3 (accumulating gate/acc2 plane by plane,
    sliced final combine) must be VALUE-IDENTICAL to the one-shot
    unpacked path from the same coefficient handles."""
    n, m = 64, 512
    qd = Domain(m)
    be = JaxBackend()
    be._QUOT_SLICE = 128  # force 4 combine slices through one program

    sel = [_rand_h(n) for _ in range(13)]
    sig = [_rand_h(n) for _ in range(5)]
    wir = [_rand_h(n + 2) for _ in range(5)]  # blinded wire lengths
    zpoly = _rand_h(n + 3)
    pi = _rand_h(n)
    k = [RNG.randrange(R_MOD) for _ in range(5)]
    beta, gamma, alpha, asdn = (RNG.randrange(R_MOD) for _ in range(4))

    batch = be.coset_fft_many(qd, sel + sig + wir + [zpoly, pi])
    ref = be.quotient(n, m, qd, k, beta, gamma, alpha, asdn,
                      batch[:13], batch[13:18], batch[18:23],
                      batch[23], batch[24])
    got = be.quotient_streamed(n, m, qd, k, beta, gamma, alpha, asdn,
                               sel, sig, wir, zpoly, pi)
    assert np.array_equal(np.asarray(ref), np.asarray(got))


def test_coset_fft_many_packed_matches():
    m = 256
    qd = Domain(m)
    be = JaxBackend()
    hs = [_rand_h(m), _rand_h(m // 2), _rand_h(m)]  # short handle pads
    plain = be.coset_fft_many(qd, hs)
    packed = be.coset_fft_many_packed(qd, hs)
    for a, b in zip(plain, packed):
        assert b.shape == (8, m)
        assert np.array_equal(np.asarray(a),
                              np.asarray(FJ.unpack_limb_pairs(b)))
