"""A `merkle` job's circuit from its witness alone (ISSUE 27).

Everything `workload.generate_circuit` builds except the witness vector and
the public input is a function of the job's SHAPE (`height`, `num_proofs`,
`num_leaves`): gates, wiring, selectors, permutation tables, domain. And the
witness of a path node is the trace of the Rescue permutation the tree ran
to get that node's digest. The plain builder hashes every path node three
times over (tree, `proof.verify`, gadget) and rebuilds the structure per
job; this one

  - keeps the structure per shape in a `_Template`, taken from ONE plain
    build of the shape and shared, read-only, by every later circuit of it;
  - runs each Rescue permutation of a job once, keeping its trace (the
    values `rescue.permutation_gadget` would create, in its order) keyed by
    its input state: the tree's digests and the gadget's wire values read
    the same trace, and the gadget finds a node's trace by the child values
    it arranged, not by where the node sits. The trace is computed in
    native code (`runtime.native.RescueTrace`, over `rescue.py`'s own
    constants), which holds no GIL; `permutation_trace` below is its
    plain-Python oracle;
  - fills the witness in the order the plain builder creates variables, and
    runs the plain builder's guard, `check_satisfiability()`, on every
    circuit it returns. The guard recomputes each gate from the raw witness
    with its own arithmetic, so a trace that does not fit the template, or a
    computed root that is not the tree's (`enforce_equal(computed_root,
    root_var)`), fails the job here.

`workload.py`, `merkle.py`, `rescue.py` and `circuit.py` stay the plain
builder this one is held to (tests/test_merkle_witness.py; and the first
build of every shape compares its witness with the plain builder's).
"""

import random
import threading

from ..circuit import PlonkCircuit
from ..constants import R_MOD
from ..merkle import BRANCH, LEAF_TAG
from ..rescue import (ALPHA, ALPHA_INV, MDS, NUM_ROUNDS, ROUND_KEYS,
                      STATE_WIDTH, _affine)
from ..runtime.native import RescueTrace
from ..workload import generate_circuit

# a deployment serves a handful of shapes; past this many the oldest
# template goes (its shape's next job pays one plain build again)
MAX_TEMPLATES = 8

_lock = threading.Lock()
_templates = {}     # (height, num_proofs, num_leaves) -> _Template

# `permutation_trace` in native code, over rescue.py's constants
_native_trace = RescueTrace(R_MOD, ROUND_KEYS, MDS, ALPHA, ALPHA_INV)


class _Template:
    """What every circuit of one shape shares: the finalized structure of
    the plain build, and how many variables it created. Read-only once
    made (the backend's `_circuit_tabs` contract already forbids mutating
    a built circuit)."""

    SHARED = ("wire_variables", "selectors", "wire_permutation",
              "extended_id_permutation", "k", "n", "eval_domain",
              "pub_input_gate_ids", "zero_var", "one_var")

    def __init__(self, plain):
        for name in self.SHARED:
            setattr(self, name, getattr(plain, name))
        self.num_vars = plain.num_vars

    def circuit(self, witness, pub_inputs):
        """A NEW finalized PlonkCircuit over the shared structure (device
        tables are cached by `id(circuit)`)."""
        if len(witness) != self.num_vars:
            raise AssertionError(f"witness of {len(witness)} values for "
                                 f"{self.num_vars} variables")
        ckt = PlonkCircuit.__new__(PlonkCircuit)
        for name in self.SHARED:
            setattr(ckt, name, getattr(self, name))
        ckt.witness = witness
        ckt.pub_inputs = pub_inputs
        ckt._finalized = True
        return ckt


def permutation_trace(state):
    """The Rescue permutation of `state`, as the values
    `rescue.permutation_gadget` creates, in its order: the key-0
    injection, then per round the forward half-round's outputs, the
    inverse S-box's roots, and the affine layer's outputs (4 + 12 x 12
    values). The last four are `rescue.permutation(state)`."""
    state = [(k + x) % R_MOD for k, x in zip(ROUND_KEYS[0], state)]
    trace = list(state)
    for r in range(NUM_ROUNDS):
        state = _affine([pow(x, ALPHA, R_MOD) for x in state],
                        ROUND_KEYS[2 * r + 1])
        roots = [pow(x, ALPHA_INV, R_MOD) for x in state]
        trace += state
        trace += roots
        state = _affine(roots, ROUND_KEYS[2 * r + 2])
        trace += state
    return trace


class _Hasher:
    """hash3 with a memory: one permutation per distinct input state, its
    trace computed natively (`native` counts those)."""

    def __init__(self):
        self.traces = {}
        self.native = 0

    def trace(self, a, b, c):
        key = (a, b, c)
        found = self.traces.get(key)
        if found is None:
            found = self.traces[key] = _native_trace([a, b, c, 0])
            self.native += 1
        return found

    def digest(self, a, b, c):
        return self.trace(a, b, c)[-STATE_WIDTH]


def _tree_levels(hasher, payloads, height):
    """`merkle.MerkleTree(payloads, height).levels`: leaf digests, then
    each level's node digests up to the root, absent children 0. (That the
    height holds the leaves is the shape's: the plain build behind the
    template has checked it.)"""
    level = [hasher.digest(i, p, LEAF_TAG) for i, p in enumerate(payloads)]
    levels = [level]
    for _ in range(height):
        level = level + [0] * ((-len(level)) % BRANCH)
        level = [hasher.digest(*level[i:i + BRANCH])
                 for i in range(0, len(level), BRANCH)]
        levels.append(level)
    return levels


def _witness(height, num_proofs, payloads):
    """(witness, root, permutations computed, of those computed natively)
    of the job, the witness in the order `generate_circuit` creates
    variables: 0, 1, the root, then per proof the payload, the index, the
    leaf hash's trace, and per level the position bits, their sum, the two
    siblings, `_select3`'s six values and the node hash's trace."""
    hasher = _Hasher()
    levels = _tree_levels(hasher, payloads, height)
    root = levels[-1][0]
    w = [0, 1, root]
    for k in range(num_proofs):
        idx = k % len(payloads)
        w.append(payloads[idx])
        w.append(idx)
        # the gadget hashes (idx_var, payload_var, one_var): the leaf's
        # digest, because LEAF_TAG is 1
        trace = hasher.trace(idx, payloads[idx], 1)
        w += trace
        cur = trace[-STATE_WIDTH]
        for row in levels[:height]:
            pos = idx % BRANCH
            base = idx - pos
            s0, s1 = (row[base + j] if base + j < len(row) else 0
                      for j in range(BRANCH) if j != pos)
            b0, b1, b2 = (int(pos == j) for j in range(BRANCH))
            d0 = (cur - s0) % R_MOD
            d1 = (cur - s1) % R_MOD
            t = (b1 * cur + b0 * s0) % R_MOD
            slots = ((b0 * d0 + s0) % R_MOD, (b2 * s1 + t) % R_MOD,
                     (b2 * d1 + s1) % R_MOD)
            w += (b0, b1, b2, 1, s0, s1, d0, slots[0], t, slots[1], d1,
                  slots[2])
            trace = hasher.trace(*slots)
            w += trace
            cur = trace[-STATE_WIDTH]
            idx //= BRANCH
    return w, root, len(hasher.traces), hasher.native


def _template(shape, seed):
    """(the shape's template, the plain circuit it was just made from or
    None where it was there). A miss builds the plain circuit of THIS job
    once, outside the lock: two workers that miss a new shape together
    both build, and the first to finish is kept (the structure is the
    shape's, so theirs are equal)."""
    with _lock:
        found = _templates.get(shape)
    if found is not None:
        return found, None
    height, num_proofs, num_leaves = shape
    plain, _tree = generate_circuit(rng=random.Random(seed), height=height,
                                    num_proofs=num_proofs,
                                    num_leaves=num_leaves)
    with _lock:
        kept = _templates.setdefault(shape, _Template(plain))
        while len(_templates) > MAX_TEMPLATES:
            del _templates[next(iter(_templates))]
    return kept, plain


def build(params, seed, metrics=None):
    """Finalized, satisfied circuit of a `merkle` spec; what the prover
    and the backends see is `workload.generate_circuit`'s of the same
    spec, value for value."""
    height, num_proofs = params["height"], params["num_proofs"]
    num_leaves = params["num_leaves"]
    rng = random.Random(seed)
    payloads = [rng.randrange(R_MOD) for _ in range(num_leaves)]
    template, plain = _template((height, num_proofs, num_leaves), seed)
    witness, root, permutations, native = _witness(height, num_proofs,
                                                   payloads)
    ckt = template.circuit(witness, [root])
    # raised, not asserted: the guard is part of what the service promises
    # and must not go with `python -O`
    ok, bad = ckt.check_satisfiability()
    if not ok:
        raise AssertionError(f"workload circuit unsatisfied at gate {bad}")
    # once per shape, the witness program against the plain builder's
    if plain is not None and (witness != plain.witness
                              or ckt.pub_inputs != plain.pub_inputs):
        raise AssertionError("witness differs from the plain builder's")
    if metrics is not None:
        metrics.inc("circuit_builds")
        metrics.inc("circuit_template_hits", int(plain is None))
        metrics.inc("circuit_build_permutations", permutations)
        metrics.inc("circuit_build_permutations_native", native)
    return ckt
