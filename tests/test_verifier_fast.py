"""The verifier's pairing equation on the ate pairing and the sized folds.

What the pool's verify-before-serve rests on (service/pool.py::_self_verify
-> verifier.verify -> curve.pairing_check), at toy sizes: a KZG opening
under a test tau, the fold of the two opening proofs against the bucket
method it replaced, and the verifier's verdict on each of the points that
fold touches. CPU, pure Python, seconds.
"""

import copy
import random

import pytest

from distributed_plonk_tpu import curve as C
from distributed_plonk_tpu import kzg
from distributed_plonk_tpu import poly as P
from distributed_plonk_tpu import verifier as V
from distributed_plonk_tpu.constants import R_MOD

TAU = 0x7E57_7A0
DEGREE = 9


@pytest.fixture(scope="module")
def opening():
    """(srs, commitment, z, p(z), opening proof) of a random polynomial."""
    r = random.Random(0x0BE1)
    srs = kzg.universal_setup(DEGREE, tau=TAU)
    coeffs = [r.randrange(R_MOD) for _ in range(DEGREE + 1)]
    z = r.randrange(R_MOD)
    y = sum(c * pow(z, i, R_MOD) for i, c in enumerate(coeffs)) % R_MOD
    comm = kzg.commit_host(srs.powers_of_g1, coeffs)
    w = kzg.commit_host(srs.powers_of_g1, P.synthetic_divide(coeffs, z))
    return srs, comm, z, y, w


def _opening_holds(srs, comm, z, y, w):
    # e(C - [y] + z W, g2) == e(W, tau g2)
    lhs = C.g1_msm([comm, C.G1_GEN, w], [1, (-y) % R_MOD, z])
    return C.pairing_check([(lhs, srs.g2), (C.g1_neg(w), srs.tau_g2)])


@pytest.mark.parametrize("altered", ["nothing", "evaluation", "proof_point",
                                     "commitment", "point"])
def test_kzg_opening_under_the_test_tau(opening, altered):
    srs, comm, z, y, w = opening
    if altered == "evaluation":
        y = (y + 1) % R_MOD
    elif altered == "proof_point":
        w = C.g1_add_affine(w, C.G1_GEN)
    elif altered == "commitment":
        comm = C.g1_add_affine(comm, C.G1_GEN)
    elif altered == "point":
        z = (z + 1) % R_MOD
    assert _opening_holds(srs, comm, z, y, w) == (altered == "nothing")


@pytest.mark.parametrize("seed", range(3))
def test_fold_of_the_two_openings_is_the_msm_it_replaced(seed):
    r = random.Random(0xF01D + seed)
    w1 = C.g1_mul(C.G1_GEN, r.randrange(1, R_MOD))
    w2 = C.g1_mul(C.G1_GEN, r.randrange(1, R_MOD))
    u = r.randrange(1, R_MOD)
    for points in ([w1, w2], [None, w2], [w1, None], [w1, C.g1_neg(w1)]):
        assert V._fold_openings(points, [1, u]) == C.g1_msm(points, [1, u])
    assert V._fold_openings([w1, C.g1_neg(w1)], [1, 1]) is None


def test_verify_holds_the_proof_to_both_opening_points(proven):
    """The two points the fold takes, and the commitment the shifted
    opening is of: each altered alone is refused, under any fold
    challenge."""
    ckt, _, vk, proof = proven
    pub = ckt.public_input()
    for seed in (1, 2):
        assert V.verify(vk, pub, proof, rng=random.Random(seed))
    for name in ("opening_proof", "shifted_opening_proof",
                 "prod_perm_poly_comm"):
        bad = copy.deepcopy(proof)
        setattr(bad, name, C.g1_add_affine(getattr(bad, name), C.G1_GEN))
        assert not V.verify(vk, pub, bad, rng=random.Random(3)), name
    bad = copy.deepcopy(proof)
    bad.split_quot_poly_comms[-1] = C.g1_neg(bad.split_quot_poly_comms[-1])
    assert not V.verify(vk, pub, bad, rng=random.Random(4))


def test_verify_aggregate_folds_members_and_refuses_one_bad(proven):
    ckt, _, vk, proof = proven
    pub = ckt.public_input()
    r = random.Random(0xA66)
    chal = [(r.randrange(1, R_MOD), r.randrange(1, R_MOD)) for _ in range(3)]
    C.reset_pairing_counters()
    assert V.verify_aggregate([(vk, pub, proof, u, rr) for u, rr in chal])
    assert C.PAIRING_COUNTERS == {"checks": 1, "pairs": 2}
    bad = copy.deepcopy(proof)
    bad.shifted_opening_proof = C.g1_add_affine(bad.shifted_opening_proof,
                                                C.G1_GEN)
    members = [(vk, pub, proof, *chal[0]), (vk, pub, bad, *chal[1]),
               (vk, pub, proof, *chal[2])]
    assert not V.verify_aggregate(members)
