"""What a job's spec states, worked out natively: the public input of the
circuit that the service proves for it. For a Merkle job that is the root of
a 3-ary Rescue-Prime tree over payloads drawn from the job's seed; no
circuit is built here.

The hash is the deployment's Rescue-Prime instance (Szepieniec, Ashur,
Dhooghe 2020): state of 4 over Fr, rate 3, alpha 5, 12 rounds, a Cauchy MDS
matrix and round keys squeezed from SHAKE-256 under the tags below."""

import hashlib
import random

from .bls import R

ROUNDS, WIDTH, ALPHA = 12, 4, 5
ALPHA_INV = pow(ALPHA, -1, R - 1)


def _squeeze(tag, count):
    """`count` field elements from SHAKE-256(tag), 64 bytes each."""
    stream = hashlib.shake_256(tag.encode()).digest(64 * count)
    return [int.from_bytes(stream[64 * i:64 * i + 64], "little") % R
            for i in range(count)]


def _cauchy():
    attempt = 0
    while True:
        draw = _squeeze(f"dpt-rescue-mds-v1-{attempt}", 2 * WIDTH)
        xs, ys = draw[:WIDTH], draw[WIDTH:]
        if (len(set(xs)) == WIDTH == len(set(ys))
                and all((x + y) % R for x in xs for y in ys)):
            return [[pow(x + y, -1, R) for y in ys] for x in xs]
        attempt += 1


MDS = _cauchy()
KEYS = [_squeeze(f"dpt-rescue-rk-v1-{k}", WIDTH) for k in range(2 * ROUNDS + 1)]


def _mix(state, key):
    return [(sum(m * s for m, s in zip(row, state)) + k) % R
            for row, k in zip(MDS, key)]


def rescue(state):
    state = [(s + k) % R for s, k in zip(state, KEYS[0])]
    for r in range(ROUNDS):
        state = _mix([pow(s, ALPHA, R) for s in state], KEYS[2 * r + 1])
        state = _mix([pow(s, ALPHA_INV, R) for s in state], KEYS[2 * r + 2])
    return state


def hash3(a, b, c):
    return rescue([a % R, b % R, c % R, 0])[0]


def merkle_root(payloads, height):
    """Leaves hash (index, payload, 1); a node hashes its three children,
    an absent child standing as 0; `height` levels above the leaves."""
    level = [hash3(i, p, 1) for i, p in enumerate(payloads)]
    for _ in range(height):
        level += [0] * (-len(level) % 3)
        level = [hash3(*level[i:i + 3]) for i in range(0, len(level), 3)]
    if len(level) != 1:
        raise ValueError("height too small for the leaves")
    return level[0]


def public_input(spec):
    """The public input the spec's circuit exposes, as a list of ints."""
    rng = random.Random(spec.get("seed", 0))
    if spec["kind"] == "merkle":
        proofs = spec.get("num_proofs", 1)
        leaves = spec.get("num_leaves") or max(proofs, 3)
        return [merkle_root([rng.randrange(R) for _ in range(leaves)],
                            spec["height"])]
    if spec["kind"] == "toy":                   # the CPU tests' circuit
        return [rng.randrange(1, R), rng.randrange(1, R)]
    raise ValueError(f"no statement for jobs of kind {spec['kind']!r}")
