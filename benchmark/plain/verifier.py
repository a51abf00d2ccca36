"""A TurboPlonk verifier for the deployment's proofs, from the protocol.

The constraint system has five wires a, b, c, d, e and thirteen selectors;
on every row

    q_c + PI + q_lc0 a + q_lc1 b + q_lc2 c + q_lc3 d + q_mul0 ab + q_mul1 cd
        + q_hash0 a^5 + q_hash1 b^5 + q_hash2 c^5 + q_hash3 d^5
        + q_ecc abcde - q_o e = 0

and the wires are tied by a permutation argument over the cosets k_i H. The
prover commits (KZG) to the wires, the product polynomial z, the quotient in
five parts of degree n + 1, opens everything at zeta and z at omega zeta,
and derives its challenges from a merlin transcript in jf-plonk's schedule.

Keys. The deployment's SRS is a test one whose tau is public (the
configuration states it), so the commitment to a polynomial is its value at
tau times the generator: `derive_key` evaluates each selector and sigma
column at tau from its values over the domain (barycentric formula) and
needs no SRS, no inverse FFT and no multi-scalar multiplication.

Openings. e(A, g2) = e(B, tau g2) holds exactly when A = tau B in G1, so
with tau in hand each opening is checked as an equality of G1 points, each
on its own, and no pairing and no folding challenge is needed.
"""

from collections import namedtuple

from . import bls
from .bls import R
from .merlin import Transcript

WIRES, SELECTORS = 5, 13
PROOF_BYTES = 13 * 48 + 10 * 32

Key = namedtuple("Key", "n inputs k selectors sigmas")
Proof = namedtuple("Proof", "wires z quotient open_zeta open_shifted "
                            "wire_evals sigma_evals z_shifted_eval")


def separators():
    """k_i = 7^i: the cosets k_i H are disjoint for the five wires."""
    return [pow(7, i, R) for i in range(WIRES)]


def sigma_columns(wire_rows, n):
    """The permutation as values: cell (i, j) of wire i at row j holds the
    identity value k_i omega^j of the NEXT cell of the same variable, cells
    taken wire by wire and row by row, the last wrapping to the first."""
    omega, k = bls.root_of_unity(n), separators()
    powers = [1] * n
    for j in range(1, n):
        powers[j] = powers[j - 1] * omega % R
    cells = {}
    for i in range(WIRES):
        for j in range(n):
            cells.setdefault(wire_rows[i][j], []).append((i, j))
    out = [[0] * n for _ in range(WIRES)]
    for group in cells.values():
        for (i, j), (ni, nj) in zip(group, group[1:] + group[:1]):
            out[i][j] = k[ni] * powers[nj] % R
    return out


def values_at(columns, n, point):
    """Each column holds a polynomial's values over the size-n domain;
    returns each polynomial's value at `point` (outside the domain):
    p(x) = (x^n - 1)/n * sum_j p_j omega^j / (x - omega^j)."""
    omega = bls.root_of_unity(n)
    weights, w = [], 1
    for _ in range(n):
        weights.append(w * pow(point - w, -1, R) % R)
        w = w * omega % R
    scale = (pow(point, n, R) - 1) * pow(n, -1, R) % R
    return [scale * sum(v * wt for v, wt in zip(col, weights)) % R
            for col in columns]


def derive_key(selector_columns, wire_rows, inputs, tau):
    """The verifying key of a constraint system given as tables: thirteen
    selector columns and five columns of variable ids, n rows each."""
    n = len(wire_rows[0])
    assert n & (n - 1) == 0 and len(selector_columns) == SELECTORS
    assert all(len(c) == n for c in list(selector_columns) + list(wire_rows))
    at_tau = values_at(list(selector_columns) + sigma_columns(wire_rows, n),
                       n, tau)
    comms = [bls.mul(bls.G1, v) for v in at_tau]
    return Key(n, inputs, separators(), comms[:SELECTORS], comms[SELECTORS:])


def decode_proof(raw):
    """944 bytes: thirteen compressed G1 points (five wires, z, five
    quotient parts, the two opening proofs), then ten scalars, 32 bytes
    little-endian and reduced (five wire values, four sigma values, z at
    omega zeta). ValueError on anything else."""
    raw = bytes(raw)
    if len(raw) != PROOF_BYTES:
        raise ValueError(f"{len(raw)} bytes, not {PROOF_BYTES}")
    pts = [bls.decode_g1(raw[48 * i:48 * i + 48]) for i in range(13)]
    nums = [int.from_bytes(raw[624 + 32 * i:656 + 32 * i], "little")
            for i in range(10)]
    if any(x >= R for x in nums):
        raise ValueError("a scalar is not reduced")
    return Proof(pts[:5], pts[5], pts[6:11], pts[11], pts[12],
                 nums[:5], nums[5:9], nums[9])


def challenges(key, public, proof):
    """beta, gamma, alpha, zeta, v as jf-plonk's StandardTranscript gives
    them: 64 squeezed bytes, little-endian, reduced, and absorbed again."""
    t = Transcript(b"PlonkProof")

    def squeeze(label):
        c = int.from_bytes(t.challenge(label, 64), "little") % R
        t.append(label, bls.fr_bytes(c))
        return c

    t.append(b"field size in bits", (255).to_bytes(8, "little"))
    t.append(b"domain size", key.n.to_bytes(8, "little"))
    t.append(b"input size", key.inputs.to_bytes(8, "little"))
    for k in key.k:
        t.append(b"wire subsets separators", bls.fr_bytes(k))
    for c in key.selectors:
        t.append(b"selector commitments", bls.ark_g1(c))
    for c in key.sigmas:
        t.append(b"sigma commitments", bls.ark_g1(c))
    for x in public:
        t.append(b"public input", bls.fr_bytes(x))
    for c in proof.wires:
        t.append(b"witness_poly_comms", bls.ark_g1(c))
    beta, gamma = squeeze(b"beta"), squeeze(b"gamma")
    t.append(b"perm_poly_comms", bls.ark_g1(proof.z))
    alpha = squeeze(b"alpha")
    for c in proof.quotient:
        t.append(b"quot_poly_comms", bls.ark_g1(c))
    zeta = squeeze(b"zeta")
    for x in proof.wire_evals:
        t.append(b"wire_evals", bls.fr_bytes(x))
    for x in proof.sigma_evals:
        t.append(b"wire_sigma_evals", bls.fr_bytes(x))
    t.append(b"perm_next_eval", bls.fr_bytes(proof.z_shifted_eval))
    return beta, gamma, alpha, zeta, squeeze(b"v")


def verify(key, public, raw, tau):
    """(accepted, why). `raw` are the served bytes, `public` the statement's
    public input, `tau` the SRS's public trapdoor."""
    try:
        proof = decode_proof(raw)
    except ValueError as e:
        return False, f"undecodable: {e}"
    if len(public) != key.inputs or any(not 0 <= x < R for x in public):
        return False, "public input of the wrong shape"
    n = key.n
    omega = bls.root_of_unity(n)
    beta, gamma, alpha, zeta, v = challenges(key, public, proof)
    vanish = (pow(zeta, n, R) - 1) % R
    if vanish == 0:
        return False, "zeta fell into the domain"
    n_inv = pow(n, -1, R)
    lagrange_1 = vanish * n_inv * pow(zeta - 1, -1, R) % R
    pi, w = 0, 1
    for x in public:                      # public inputs sit on rows 0, 1, ..
        pi += x * w * vanish * n_inv * pow(zeta - w, -1, R)
        w = w * omega % R
    a, b, c, d, e = proof.wire_evals
    z_next = proof.z_shifted_eval

    with_sigma = 1                        # prod_{i<4} (w_i + beta s_i + gamma)
    for wv, sv in zip(proof.wire_evals, proof.sigma_evals):
        with_sigma = with_sigma * (wv + beta * sv + gamma) % R
    with_id = 1                           # prod_{i<5} (w_i + beta k_i zeta + gamma)
    for wv, k in zip(proof.wire_evals, key.k):
        with_id = with_id * (wv + beta * k * zeta + gamma) % R

    # r(X), the linearisation: the identity with every opened value put in,
    # left as a combination of commitments; r(zeta) is what the identity
    # then demands of it
    points = list(key.selectors)
    scalars = [a, b, c, d, a * b, c * d, pow(a, 5, R), pow(b, 5, R),
               pow(c, 5, R), pow(d, 5, R), -e, 1, a * b * c * d * e]
    points += [proof.z, key.sigmas[4]]
    scalars += [alpha * with_id + alpha * alpha * lagrange_1,
                -alpha * beta * z_next * with_sigma]
    part = zeta ** 2 * (vanish + 1) % R   # zeta^(n+2): parts have n+2 terms
    for i, t_i in enumerate(proof.quotient):
        points.append(t_i)
        scalars.append(-vanish * pow(part, i, R))
    r_at_zeta = (alpha * alpha * lagrange_1 - pi
                 + alpha * z_next * (e + gamma) * with_sigma) % R

    # everything opened at zeta, batched by powers of v
    value, vp = r_at_zeta, v
    for comm, ev in zip(list(proof.wires) + list(key.sigmas[:4]),
                        list(proof.wire_evals) + list(proof.sigma_evals)):
        points.append(comm)
        scalars.append(vp)
        value = (value + vp * ev) % R
        vp = vp * v % R
    batched = bls.combine(points, scalars)

    # C - [value] = (tau - x) W, for each opening
    lhs = bls.add(batched, bls.neg(bls.mul(bls.G1, value)))
    if lhs != bls.mul(proof.open_zeta, (tau - zeta) % R):
        return False, "the opening at zeta does not hold"
    lhs = bls.add(proof.z, bls.neg(bls.mul(bls.G1, z_next % R)))
    if lhs != bls.mul(proof.open_shifted, (tau - omega * zeta) % R):
        return False, "the opening of z at omega zeta does not hold"
    return True, ""
