"""The host oracle's prove of a job spec: the bytes a served proof must equal.

This directory is a frozen copy of the program's pure-Python host engine
(circuit builder, KZG set-up and preprocess, the prover on `PythonBackend`),
so it is the program's own second engine and not an independent one: what
the byte comparison holds the served path to is the device arithmetic and
every later change of the program, not a fault the two engines shared on
the day of the copy. The independent side is `benchmark/plain`. All of it
runs in jax-free worker processes (`benchmark/lib/refpool.py`), and a prove
may fan its independent pieces out over more (`benchmark/lib/fanout.py`),
with the same bytes. A spec is
the wire dict the client SUBMITs: `{"kind": "merkle", "height": h,
"num_proofs": p, "seed": s}` (or the toy kind the CPU tests use).
"""

import fcntl
import os
import pickle
import random
import time

from ..lib import fanout
from . import kzg
from .backend.python_backend import PythonBackend
from .circuit import PlonkCircuit
from .constants import R_MOD
from .proof_io import serialize_proof
from .prover import prove
from .workload import generate_circuit

# the service's deterministic toxic waste (service/jobs.py TEST_TAU): server
# and verifying client derive identical keys from a spec alone
TEST_TAU = 0xDEADBEEF
REUSED_BLINDING_SEED = 0


def _toy_circuit(gates, seed):
    rng = random.Random(seed)
    ckt = PlonkCircuit()
    x = ckt.create_public_variable(rng.randrange(1, R_MOD))
    y = ckt.create_public_variable(rng.randrange(1, R_MOD))
    acc = ckt.add(x, y)
    for i in range(gates):
        if i % 3 == 0:
            acc = ckt.mul(acc, x)
        elif i % 3 == 1:
            acc = ckt.add(acc, y)
        else:
            acc = ckt.lc([acc, x, y, acc], [1, 2, 3, 4])
    return ckt


def build_circuit(spec):
    """Spec -> finalized, satisfied circuit, deterministic in the spec."""
    seed = spec.get("seed", 0)
    if spec["kind"] == "toy":
        ckt = _toy_circuit(spec["gates"], seed)
        ok, bad = ckt.check_satisfiability()
        if not ok:
            raise ValueError(f"toy circuit unsatisfied at gate {bad}")
        return ckt.finalize()
    if spec["kind"] != "merkle":
        raise ValueError(f"the reference has no circuit of kind {spec['kind']!r}")
    num_proofs = spec.get("num_proofs", 1)
    ckt, _tree = generate_circuit(
        rng=random.Random(seed), height=spec["height"], num_proofs=num_proofs,
        num_leaves=spec.get("num_leaves") or max(num_proofs, 3))
    return ckt


def build_keys(spec):
    """(pk, vk) for the spec's SHAPE, from the seed-0 circuit as the
    structure donor, on the host oracle."""
    ckt = build_circuit(dict(spec, seed=0))
    srs = kzg.universal_setup(ckt.n + 3, tau=TEST_TAU)
    return kzg.preprocess(srs, ckt)


def shape_name(spec):
    return "-".join(f"{k}{spec[k]}" for k in sorted(spec)
                    if k not in ("seed", "kind")) + "-" + spec["kind"]


def load_or_build_keys(spec, cache_dir):
    """The reference's own keys, built once per checkout (67 s at 2^13) and
    kept under `cache_dir`; only bytes this function wrote are unpickled.
    A lock file makes concurrent workers build once."""
    if cache_dir is None:
        return build_keys(spec)
    os.makedirs(cache_dir, exist_ok=True)
    path = os.path.join(cache_dir, f"ref-keys-{shape_name(spec)}.pkl")
    with open(path + ".lock", "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        try:
            if os.path.exists(path):
                with open(path, "rb") as f:
                    return pickle.load(f)
            keys = build_keys(spec)
            tmp = f"{path}.{os.getpid()}.tmp"
            with open(tmp, "wb") as f:
                pickle.dump(keys, f, protocol=pickle.HIGHEST_PROTOCOL)
            os.replace(tmp, path)
            return keys
        finally:
            fcntl.flock(lock, fcntl.LOCK_UN)


_KEYS = {}


def _keys(spec, cache_dir):
    name = (shape_name(spec), cache_dir)
    if name not in _KEYS:
        _KEYS[name] = load_or_build_keys(spec, cache_dir)
    return _KEYS[name]


def oracle_proof(spec, cache_dir=None, precision="full", workers=0):
    """The bytes the host oracle serves for this spec: the circuit and the
    blinding both drawn from `spec["seed"]`, as the service's pool worker
    draws them. Returns {"proof": bytes, "seconds": float, "ended": the
    `time.monotonic()` at which the prove finished}.

    workers=0 proves on the serial `PythonBackend`, the yardstick of the
    tests; with `workers` the prove's independent pieces run on a pool of
    that many spawned processes (`fanout.FanoutBackend`), and the bytes
    are the same.

    precision="reused_blinding" is the control: the same prove blinded from
    one fixed seed and not from the job's, a proof that verifies and is not
    the one the guarantee names. (Zero blinders would be the plainer breach,
    but the prover asserts the quotient's degree and will not run so.)"""
    t0 = time.monotonic()
    pk, _vk = _keys(spec, cache_dir)
    rng = random.Random(spec.get("seed", 0))
    if precision == "reused_blinding":
        rng = random.Random(REUSED_BLINDING_SEED)
    elif precision != "full":
        raise ValueError(f"unknown precision {precision!r}")
    ckt = build_circuit(spec)
    if workers:
        with fanout.pool(workers) as executor:
            proof = prove(rng, ckt, pk,
                          fanout.FanoutBackend(executor, workers))
    else:
        proof = prove(rng, ckt, pk, PythonBackend())
    ended = time.monotonic()
    return {"proof": serialize_proof(proof), "seconds": ended - t0,
            "ended": ended}
