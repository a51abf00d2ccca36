"""The plain reference. Its verifying side (`benchmark/plain`) is written
from the specifications and is held here to outside vectors (hashlib's SHA3,
merlin's published challenge, the curve's generator) and to the program,
which it has to agree with without sharing a line; its proving side
(`benchmark/reference`) is a frozen copy of the program's host engine. And
the control of the comparison that decides `correct`, at a size a test can
hold."""

import functools
import hashlib
import os
import random
import subprocess
import sys
import time
from concurrent.futures import Executor, Future

import pytest

from bench_toy import REPO, TOY_JOB
from benchmark.lib import check, manifest, refpool, served
from benchmark.lib.fanout import FanoutBackend, ranges
from benchmark.plain import bls, merlin, statement, verifier
from benchmark.reference import curve as C, oracle
from benchmark.reference.backend.python_backend import PythonBackend
from benchmark.reference.constants import R_MOD
from benchmark.reference.poly import Domain

REF = os.path.join(REPO, "benchmark", "reference")
TAU = 0xDEADBEEF
# trimmed to what the frozen prover imports, so no longer copies
TRIMMED = ("trace.py", "checkpoint.py")
COPIES = sorted(
    os.path.relpath(os.path.join(d, f), REF)
    for d, _dirs, files in os.walk(REF) for f in files
    if f.endswith(".py") and f not in ("__init__.py", "oracle.py") + TRIMMED)


def test_the_proving_side_is_a_copy_of_the_host_oracle():
    """Each copy is held to the sha256 taken when it was frozen
    (DIGESTS.json beside them), not to the live file of its name: the
    program is free to move on, and what ties the two is their bytes on the
    same job (test_frozen_oracle_agrees_with_the_programs_host_oracle)."""
    digests = manifest.load_json(os.path.join(REF, "DIGESTS.json"))
    assert len(COPIES) == 14 and sorted(digests) == COPIES
    for rel in COPIES:
        with open(os.path.join(REF, rel), "rb") as f:
            assert hashlib.sha256(f.read()).hexdigest() == digests[rel], rel


@pytest.mark.parametrize("module, banned", [
    ("benchmark.reference.oracle", ("jax", "distributed_plonk_tpu")),
    ("benchmark.lib.fanout", ("jax", "distributed_plonk_tpu", "numpy")),
    ("benchmark.plain.verifier", ("jax", "distributed_plonk_tpu",
                                  "benchmark.reference", "numpy")),
    ("benchmark.plain.statement", ("jax", "distributed_plonk_tpu",
                                   "benchmark.reference", "numpy")),
])
def test_the_reference_imports_nothing_of_the_program(module, banned):
    code = ("import sys; sys.path.insert(0, %r); import %s; "
            "bad = [m for m in sys.modules for b in %r "
            "if m == b or m.startswith(b + '.')]; "
            "print(bad); sys.exit(1 if bad else 0)" % (REPO, module, banned))
    done = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=120)
    assert done.returncode == 0, done.stdout + done.stderr


def _sha3_256(msg):
    """SHA3-256 as FIPS 202 builds it on the permutation under test."""
    rate, state = 136, bytearray(200)
    padded = bytearray(msg) + b"\x06"
    padded += bytes(-len(padded) % rate)
    padded[-1] |= 0x80
    for off in range(0, len(padded), rate):
        for i in range(rate):
            state[i] ^= padded[off + i]
        state = merlin.keccak_f(state)
    return bytes(state[:32])


def test_plain_primitives_against_outside_vectors():
    for msg in (b"", b"abc", b"q" * 135, b"q" * 136, b"x" * 300):
        assert _sha3_256(msg) == hashlib.sha3_256(msg).digest()
    # merlin's own test vector (merlin 3.0, tests of `Transcript`)
    t = merlin.Transcript(b"test protocol")
    t.append(b"some label", b"some data")
    assert t.challenge(b"challenge", 32).hex() == (
        "d5a21972d0d5fe320c0d263fac7fffb8145aa640af6e9bca177c03c7efcf0615")
    # the curve: the generator's published compressed encoding, its order
    gen = bytes.fromhex(
        "97f1d3a73197d7942695638c4fa9ac0fc3688c4f9774b905a14e3a3f171bac58"
        "6c55e83ff97a1aeffb3af00adb22c6bb")
    assert bls.decode_g1(gen) == bls.G1 and bls.in_g1(bls.G1)
    assert bls.decode_g1(bytes([0xC0]) + bytes(47)) is None
    assert bls.mul(bls.G1, bls.R) is None
    assert bls.add(bls.mul(bls.G1, 5), bls.neg(bls.mul(bls.G1, 3))) == \
        bls.mul(bls.G1, 2)
    assert pow(bls.root_of_unity(8192), 8192, bls.R) == 1
    assert pow(bls.root_of_unity(8192), 4096, bls.R) == bls.R - 1
    for bad in (bytes(48), gen[:-1], bytes([0xE0]) + bytes(47),
                bytes([0x9A]) + bytes([0xFF]) * 47):
        with pytest.raises(ValueError):
            bls.decode_g1(bad)
    # a point of the curve outside the order-r subgroup is refused
    x = 0
    while pow((x ** 3 + 4) % bls.P, (bls.P - 1) // 2, bls.P) != 1 or \
            bls.mul((x, pow(x ** 3 + 4, (bls.P + 1) // 4, bls.P)), bls.R) is None:
        x += 1
    raw = bytearray(x.to_bytes(48, "big"))
    raw[0] |= 0x80
    with pytest.raises(ValueError, match="subgroup"):
        bls.decode_g1(bytes(raw))


MERKLE = {"kind": "merkle", "height": 2, "num_proofs": 1}


def test_plain_statement_and_key_agree_with_the_program():
    """Two derivations that share no code: the program builds a circuit,
    an SRS and runs preprocess (inverse FFTs and MSMs); the plain side
    hashes natively and evaluates the gate tables at tau."""
    from distributed_plonk_tpu.service.jobs import (JobSpec, build_bucket_keys,
                                                    build_circuit)
    for spec in (dict(MERKLE, seed=9), dict(MERKLE, seed=2 ** 31 + 5),
                 dict(TOY_JOB, seed=77)):
        theirs = build_circuit(JobSpec.from_wire(spec)).public_input()
        assert statement.public_input(spec) == theirs
    for spec in (dict(MERKLE, seed=9), dict(TOY_JOB, seed=77)):
        _srs, _pk, vk = build_bucket_keys(JobSpec.from_wire(spec))
        key = served.key_for(spec, TAU)
        assert (key.n, key.inputs, key.k) == (vk.domain_size, vk.num_inputs,
                                              vk.k)
        assert key.selectors == vk.selector_comms
        assert key.sigmas == vk.sigma_comms
    with pytest.raises(ValueError):
        statement.public_input({"kind": "rollup"})


def test_frozen_oracle_agrees_with_the_programs_host_oracle():
    from distributed_plonk_tpu.backend.python_backend import PythonBackend
    from distributed_plonk_tpu.proof_io import serialize_proof
    from distributed_plonk_tpu.prover import prove
    from distributed_plonk_tpu.service.jobs import (JobSpec, build_bucket_keys,
                                                    build_circuit)
    spec = dict(TOY_JOB, seed=77)
    js = JobSpec.from_wire(spec)
    _srs, pk, _vk = build_bucket_keys(js)
    theirs = serialize_proof(prove(random.Random(77), build_circuit(js), pk,
                                   PythonBackend()))
    assert oracle.oracle_proof(spec)["proof"] == theirs
    # and the plain verifier accepts what the program's engine proves
    pub = [hex(x) for x in statement.public_input(spec)]
    assert served.check_served(spec, theirs, pub, TAU) == {
        "pub_equal": True, "verified": True, "why": ""}


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 4, 55])
def test_control_reused_blinding_verifies_and_is_not_correct(seed):
    """The control: the oracle in the program's place with every proof
    blinded from one fixed seed. Its answer verifies, so only the byte
    comparison can fail it, and does; the sound answer passes the same
    comparison."""
    spec = dict(TOY_JOB, seed=seed)
    full = oracle.oracle_proof(spec)["proof"]
    ctl = oracle.oracle_proof(spec, precision="reused_blinding")["proof"]
    pub = [hex(x) for x in statement.public_input(spec)]
    assert served.check_served(spec, ctl, pub, TAU) == {
        "pub_equal": True, "verified": True, "why": ""}
    assert check.byte_diffs(ctl, full) > 100
    assert check.byte_diffs(full, oracle.oracle_proof(spec)["proof"]) == 0


def test_wrong_answers_are_told_from_right_ones(tmp_path):
    spec = dict(MERKLE, seed=21)
    cache = str(tmp_path / "ref")
    good = oracle.oracle_proof(spec, cache_dir=cache)["proof"]
    assert os.listdir(cache)          # the keys are kept for the next run
    pub = [hex(x) for x in statement.public_input(spec)]
    ok = served.check_served(spec, good, pub, TAU)
    assert ok["verified"] and ok["pub_equal"]
    # every part of the answer is held: one bit anywhere and it is refused
    for at in (3, 250, 300, 540, 590, 630, 790, 920):
        flipped = good[:at] + bytes([good[at] ^ 1]) + good[at + 1:]
        assert not served.check_served(spec, flipped, pub, TAU)["verified"], at
    assert not served.check_served(spec, good[:-3], pub, TAU)["verified"]
    # under another tau the same sound proof does not open
    assert not verifier.verify(served.key_for(spec, TAU),
                               statement.public_input(spec), good, TAU + 1)[0]
    # a sound proof of ANOTHER seed's statement: wrong public input, and
    # not a proof of this one
    other = oracle.oracle_proof(dict(spec, seed=22))["proof"]
    other_pub = [hex(x) for x in
                 statement.public_input(dict(spec, seed=22))]
    verdict = served.check_served(spec, other, other_pub, TAU)
    assert not verdict["pub_equal"] and not verdict["verified"]
    assert not served.check_served(spec, good, None, TAU)["pub_equal"]
    assert check.byte_diffs(b"abc", b"abd") == 1
    assert check.byte_diffs(b"abc", b"ab") == 1
    assert check.sample_clients(5, 4, 1) == check.sample_clients(5, 4, 1)
    assert check.sample_clients(5, 4, 4) == [0, 1, 2, 3]
    assert len(check.sample_clients(5, 4, 9)) == 4


class _Inline(Executor):
    """An executor that runs each task as it is submitted."""

    def submit(self, fn, /, *args, **kwargs):
        fut = Future()
        fut.set_result(fn(*args, **kwargs))
        return fut


@functools.cache
def _serial_proof(spec_items, precision="full"):
    return oracle.oracle_proof(dict(spec_items), precision=precision)["proof"]


@pytest.mark.parametrize("workers", [2, 3])
@pytest.mark.parametrize("seed", [8, 2 ** 31 + 9])
@pytest.mark.parametrize("job", [TOY_JOB, MERKLE], ids=["toy", "merkle"])
def test_the_fanout_prove_has_the_serial_bytes(job, seed, workers):
    spec = dict(job, seed=seed)
    got = oracle.oracle_proof(spec, workers=workers)
    assert got["proof"] == _serial_proof(tuple(sorted(spec.items())))
    assert got["ended"] <= time.monotonic()


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 4])
def test_the_control_through_the_fanout_is_still_not_correct(seed):
    spec = dict(TOY_JOB, seed=seed)
    ctl = oracle.oracle_proof(spec, precision="reused_blinding", workers=2)
    assert ctl["proof"] == _serial_proof(tuple(sorted(spec.items())),
                                         "reused_blinding")
    full = _serial_proof(tuple(sorted(spec.items())))
    assert check.byte_diffs(ctl["proof"], full) > 100


def _points(count):
    pts = [C.g1_mul(C.G1_GEN, 3 * i + 1) for i in range(count)]
    pts[count // 2] = C.INF              # the SRS's zero padding
    return pts


@pytest.mark.parametrize("points, width, lengths", [
    (11, 3, [11, 11, 11]),               # uneven ranges: 4, 4, 3
    (11, 4, [11, 7, 0]),                 # lists shorter than the key
    (3, 5, [3, 2]),                      # fewer points than workers
    (1, 2, [1]),
], ids=["uneven", "short-lists", "fewer-points", "one-point"])
def test_a_chunked_commit_is_the_frozen_msm(points, width, lengths):
    ck = _points(points)
    rng = random.Random(points * 100 + width)
    lists = [[rng.choice((0, 0, 1, R_MOD - 1, rng.randrange(R_MOD)))
              for _ in range(n)] for n in lengths]
    lists.append([0] * lengths[0])       # every scalar zero: infinity
    want = [C.g1_msm(ck[:len(s)], s) for s in lists]
    assert FanoutBackend(_Inline(), width).commit_many(ck, lists) == want
    assert want[-1] is None
    # handles a coefficient short of the key, as the prover's are
    hs = [s[:max(1, len(s) - 1)] for s in lists if s]
    assert FanoutBackend(_Inline(), width).commit_many_h(ck, hs) == \
        PythonBackend().commit_many_h(ck, hs)


@pytest.mark.parametrize("width", [3, 17, 32])
def test_the_chunked_quotient_is_the_frozen_loop(width):
    """n = 16 on a quotient domain of 128, so z is read 8 places on and
    wraps for the top 8 indices: widths 17 and 32 put a range's boundary
    inside that wrap (121 and 124), 3 does not."""
    n, m = 16, 128
    dom = Domain(m)
    rng = random.Random(width)

    def plane():
        return [rng.randrange(R_MOD) for _ in range(m)]

    args = (n, m, dom, [rng.randrange(R_MOD) for _ in range(5)],
            rng.randrange(R_MOD), rng.randrange(R_MOD), rng.randrange(R_MOD),
            rng.randrange(R_MOD), [plane() for _ in range(13)],
            [plane() for _ in range(5)], [plane() for _ in range(5)], plane(),
            plane())
    bounds = [hi for _lo, hi in ranges(m, width)][:-1]
    assert any(m - m // n < b < m for b in bounds) == (width != 3)
    assert FanoutBackend(_Inline(), width).quotient(*args) == \
        PythonBackend().quotient(*args)


def test_a_killed_pool_leaves_no_fanout_process_behind():
    """`RefPool.close(kill=True)` in the middle of a fanned-out prove ends
    the worker and every process of the pool below it."""
    ref = refpool.RefPool(1, None, fanout=2)
    try:
        fut = ref.oracle_proof(dict(MERKLE, seed=31))
        worker = list(ref._pool._processes.values())[0].pid
        below = []
        deadline = time.monotonic() + 240
        while len(below) < 2 and not fut.done() and \
                time.monotonic() < deadline:
            time.sleep(0.05)
            below = refpool.descendants([worker])
        assert len(below) >= 2, "the prove never fanned out"
    finally:
        ref.close(kill=True)
    deadline = time.monotonic() + 30
    left = [pid for pid in below + [worker] if _running(pid)]
    while left and time.monotonic() < deadline:
        time.sleep(0.1)
        left = [pid for pid in left if _running(pid)]
    assert left == []


def _running(pid):
    """Alive and not a zombie waiting to be reaped."""
    try:
        with open(f"/proc/{pid}/stat") as f:
            return f.read().rpartition(")")[2].split()[0] != "Z"
    except OSError:
        return False
