"""The witness-only Merkle builder (circuits/merkle_witness.py, ISSUE 27)
held to the plain one (workload.generate_circuit): same circuit value for
value over shapes that meet, part and wrap; the structure shared per shape,
a new object per job, the guard run on every job; the three counters; two
threads on one new shape; and two served jobs on the host oracle."""

import os
import random
import sys
import threading

import pytest

from distributed_plonk_tpu import rescue
from distributed_plonk_tpu.circuits import merkle_witness as MW
from distributed_plonk_tpu.proof_io import deserialize_proof
from distributed_plonk_tpu.service import ProofService, ServiceClient
from distributed_plonk_tpu.service.jobs import (JobSpec, build_bucket_keys,
                                                build_circuit)
from distributed_plonk_tpu.service.metrics import Metrics
from distributed_plonk_tpu.verifier import verify
from distributed_plonk_tpu.workload import generate_circuit

SAME = ("witness", "wire_variables", "selectors", "wire_permutation",
        "extended_id_permutation", "pub_inputs", "pub_input_gate_ids", "n",
        "k", "zero_var", "one_var")

# (height, num_proofs, num_leaves, seed)
CASES = [
    (32, 1, 3, 5),              # the served 2^13 shape
    (32, 3, 3, 2 ** 31 + 7),    # the served 2^14 shape; its paths meet at level 1
    (1, 1, 3, 1),               # height 1: the leaf's parent is the root
    (1, 5, 2, 2),               # more proofs than leaves: the index wraps
    (3, 4, 9, 3),               # paths part at the root's children
    (4, 7, 20, 4),              # a ragged last triple on two levels
    (2, 2, 1, 9),               # a single leaf, proved twice
    (5, 3, 27, 11),             # leaves nobody proves still decide the root
    (3, 12, 5, 2 ** 31 + 5),    # wraps twice and more
]


@pytest.fixture(autouse=True)
def no_templates():
    MW._templates.clear()
    yield
    MW._templates.clear()


def _spec(height, num_proofs, num_leaves, seed):
    return JobSpec.from_wire({"kind": "merkle", "height": height,
                              "num_proofs": num_proofs,
                              "num_leaves": num_leaves, "seed": seed})


def test_a_trace_ends_in_the_permutation():
    rng = random.Random(3)
    state = [rng.randrange(rescue.R_MOD) for _ in range(3)] + [0]
    trace = MW.permutation_trace(state)
    assert len(trace) == 4 + 12 * rescue.NUM_ROUNDS
    assert trace[-4:] == rescue.permutation(state)


@pytest.mark.parametrize("height,num_proofs,num_leaves,seed", CASES)
def test_equals_the_plain_builder(height, num_proofs, num_leaves, seed):
    plain, tree = generate_circuit(random.Random(seed), height, num_proofs,
                                   num_leaves)
    # a job of another seed fills the template, so the circuit compared is
    # one that took its structure from the template and not from its own
    # plain build
    build_circuit(_spec(height, num_proofs, num_leaves, seed + 1))
    ours = build_circuit(_spec(height, num_proofs, num_leaves, seed))
    for name in SAME:
        assert getattr(ours, name) == getattr(plain, name), name
    assert ours.public_input() == [tree.root]
    assert ours.eval_domain.size == plain.eval_domain.size
    assert [ours.wire_values(i) for i in range(5)] == \
        [plain.wire_values(i) for i in range(5)]
    assert ours.check_satisfiability() == (True, -1)


def test_a_second_job_shares_the_structure_and_is_a_new_object(monkeypatch):
    first = build_circuit(_spec(2, 2, 4, 1))
    second = build_circuit(_spec(2, 2, 4, 2))
    again = build_circuit(_spec(2, 2, 4, 2))
    assert second is not first and again is not second
    for name in MW._Template.SHARED:
        assert getattr(second, name) is getattr(first, name), name
    assert second.witness != first.witness
    assert second.pub_inputs != first.pub_inputs
    assert again.witness == second.witness and again.witness is not second.witness
    assert len(MW._templates) == 1

    # the guard runs on every job: a witness with one value altered in a
    # template-hit build is refused at its gate
    real = MW._witness

    def one_value_off(height, num_proofs, payloads):
        w, root, perms = real(height, num_proofs, payloads)
        w[len(w) // 2] = (w[len(w) // 2] + 1) % rescue.R_MOD
        return w, root, perms
    monkeypatch.setattr(MW, "_witness", one_value_off)
    with pytest.raises(AssertionError, match="unsatisfied at gate"):
        build_circuit(_spec(2, 2, 4, 3))
    # and a root that is not the tree's fails enforce_equal / the IO gate
    monkeypatch.setattr(MW, "_witness", lambda h, p, pl: (
        real(h, p, pl)[0], 12345, 0))
    with pytest.raises(AssertionError, match="unsatisfied at gate 0"):
        build_circuit(_spec(2, 2, 4, 3))


def test_templates_are_bounded():
    shapes = [(1, p, 3) for p in range(1, MW.MAX_TEMPLATES + 3)]
    for shape in shapes:
        build_circuit(_spec(*shape, 0))
    assert len(MW._templates) == MW.MAX_TEMPLATES
    assert list(MW._templates) == shapes[-MW.MAX_TEMPLATES:]


def test_counters_after_two_jobs_of_one_shape():
    m = Metrics()
    build_circuit(_spec(32, 3, 3, 1), m)
    build_circuit(_spec(32, 3, 3, 2), m)
    c = m.snapshot()["counters"]
    assert c["circuit_builds"] == 2
    assert c["circuit_template_hits"] == 1
    # 3 leaves + 32 nodes: the three paths meet at level 1, and the gadget
    # needs no permutation the tree has not run
    assert c["circuit_build_permutations"] == 2 * 35
    # paths that part below the root: still the tree's count and no more
    m = Metrics()
    build_circuit(_spec(3, 4, 9, 1), m)
    assert m.snapshot()["counters"] == {
        "circuit_builds": 1, "circuit_template_hits": 0,
        "circuit_build_permutations": 9 + 3 + 1 + 1}
    # no registry, no count, same circuit
    assert build_circuit(_spec(3, 4, 9, 1)).witness == \
        build_circuit(_spec(3, 4, 9, 1), Metrics()).witness


def test_threads_on_a_new_shape_leave_one_template(monkeypatch):
    """More threads than cores meet a new shape at once, every one of them
    inside the plain build before any finishes: one template is kept, every
    circuit shares it, no count is lost; a second wave only hits."""
    workers = (os.cpu_count() or 4) + 2
    inside = threading.Barrier(workers, timeout=120)
    plain = MW.generate_circuit

    def slow(**kw):
        inside.wait()
        return plain(**kw)
    monkeypatch.setattr(MW, "generate_circuit", slow)
    m = Metrics()
    out = [None] * (2 * workers)

    def run(i):
        out[i] = build_circuit(_spec(1, 2, 3, 7 + i % 2), m)

    def wave(ids):
        threads = [threading.Thread(target=run, args=(i,)) for i in ids]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
        assert not any(t.is_alive() for t in threads)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        wave(range(workers))
        c = m.snapshot()["counters"]
        assert c["circuit_builds"] == workers
        assert c["circuit_template_hits"] == 0
        wave(range(workers, 2 * workers))
    finally:
        sys.setswitchinterval(interval)
    assert all(ckt is not None for ckt in out)
    assert len({id(ckt) for ckt in out}) == len(out)
    assert list(MW._templates) == [(1, 2, 3)]
    kept = MW._templates[(1, 2, 3)]
    for ckt in out:
        for name in MW._Template.SHARED:
            assert getattr(ckt, name) is getattr(kept, name), name
    for i, ckt in enumerate(out):
        assert ckt.witness == out[i % 2].witness
        assert ckt.pub_inputs == out[i % 2].pub_inputs
    c = m.snapshot()["counters"]
    assert c["circuit_builds"] == 2 * workers
    assert c["circuit_template_hits"] == workers
    assert c["circuit_build_permutations"] == 2 * workers * (3 + 1)


def test_served_jobs_count_their_builds():
    """Two jobs of one shape through the pool on the host oracle: the key
    build's seed-0 circuit fills the template, both jobs find it, and the
    proofs verify for the tree's own root."""
    shape = {"kind": "merkle", "height": 1, "num_proofs": 1}
    svc = ProofService(port=0, prover_workers=1).start()
    try:
        with ServiceClient("127.0.0.1", svc.port) as c:
            ids = [c.submit(dict(shape, seed=s))["job_id"] for s in (4, 5)]
            results = []
            for jid in ids:
                assert c.wait(jid, timeout_s=300)["state"] == "done"
                results.append(c.result(jid))
            counters = c.metrics()["counters"]
    finally:
        svc.shutdown()
    assert counters["circuit_builds"] == 2
    assert counters["circuit_template_hits"] == 2
    assert counters["circuit_build_permutations"] == 2 * (3 + 1)
    _srs, _pk, vk = build_bucket_keys(JobSpec.from_wire(shape))
    for seed, (header, blob) in zip((4, 5), results):
        rng = random.Random(seed)
        _ckt, tree = generate_circuit(rng, height=1, num_proofs=1)
        assert [int(x, 16) for x in header["public_input"]] == [tree.root]
        assert verify(vk, [tree.root], deserialize_proof(blob),
                      rng=random.Random(1))
