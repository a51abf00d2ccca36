"""The witness-only Merkle builder (circuits/merkle_witness.py, ISSUE 27)
held to the plain one (workload.generate_circuit): same circuit value for
value over shapes that meet, part and wrap; the native Rescue trace equal to
the plain-Python one; the structure shared per shape, a new object per job,
the guard run on every job and catching a wrong native value; the counters;
two threads on one new shape; and two served jobs on the host oracle, whose
window the benchmark reads as every permutation native."""

import json
import os
import random
import sys
import threading

import pytest

from distributed_plonk_tpu import rescue
from distributed_plonk_tpu.circuits import merkle_witness as MW
from distributed_plonk_tpu.proof_io import deserialize_proof
from distributed_plonk_tpu.runtime.native import RescueTrace
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


def _edge_states():
    R = rescue.R_MOD
    return {
        "zero": [0, 0, 0, 0],
        "all_r_less_1": [R - 1] * 4,
        "one": [1, 0, 0, 0],
        # the key-0 injection leaves every first S-box input 0
        "first_sbox_input_zero": [(R - k) % R for k in rescue.ROUND_KEYS[0]],
        # not reduced: the trace is of the residues
        "unreduced": [R, R + 5, 2 * R - 1, 3 * R],
    }


def _random_state(seed):
    rng = random.Random(seed)
    return [rng.randrange(rescue.R_MOD) for _ in range(4)]


@pytest.mark.parametrize("state", [_random_state(s) for s in range(64)]
                         + list(_edge_states().values()),
                         ids=[f"seed{s}" for s in range(64)]
                         + list(_edge_states()))
def test_the_native_trace_equals_the_python_trace(state):
    native = MW._native_trace(state)
    assert native == MW.permutation_trace(state)
    assert len(native) == 4 + 12 * rescue.NUM_ROUNDS
    assert all(0 <= x < rescue.R_MOD for x in native)


def test_the_native_trace_refuses_constants_not_below_the_modulus():
    keys = [list(k) for k in rescue.ROUND_KEYS]
    keys[7][2] = rescue.R_MOD
    bad = RescueTrace(rescue.R_MOD, keys, rescue.MDS, rescue.ALPHA,
                      rescue.ALPHA_INV)
    with pytest.raises(ValueError, match="not below the modulus"):
        bad([1, 2, 3, 0])
    with pytest.raises(ValueError, match="not 4"):
        MW._native_trace([1, 2, 3])


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
    m = Metrics()
    ours = build_circuit(_spec(height, num_proofs, num_leaves, seed), m)
    # every trace of the compared circuit came from the native code
    c = m.snapshot()["counters"]
    assert c["circuit_build_permutations_native"] == \
        c["circuit_build_permutations"] > 0
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
        w, root, perms, native = real(height, num_proofs, payloads)
        w[len(w) // 2] = (w[len(w) // 2] + 1) % rescue.R_MOD
        return w, root, perms, native
    monkeypatch.setattr(MW, "_witness", one_value_off)
    with pytest.raises(AssertionError, match="unsatisfied at gate"):
        build_circuit(_spec(2, 2, 4, 3))
    # and a root that is not the tree's fails enforce_equal / the IO gate
    monkeypatch.setattr(MW, "_witness", lambda h, p, pl: (
        real(h, p, pl)[0], 12345, 0, 0))
    with pytest.raises(AssertionError, match="unsatisfied at gate 0"):
        build_circuit(_spec(2, 2, 4, 3))


@pytest.mark.parametrize("position", [0, 4, 9, 60, 147])
def test_a_wrong_native_value_fails_the_guard(monkeypatch, position):
    """One value of one native trace off by one, at a position of each
    kind (key-0 injection, forward half-round, root, affine output, the
    digest): the guard recomputes the gates and the build raises, so the
    value never reaches a proof."""
    build_circuit(_spec(32, 1, 3, 1))       # the shape's template, plainly
    real = MW._native_trace
    calls = []

    def one_off(state):
        trace = real(state)
        calls.append(state)
        if len(calls) == 5:                 # a node of the path
            trace[position] = (trace[position] + 1) % rescue.R_MOD
        return trace
    monkeypatch.setattr(MW, "_native_trace", one_off)
    with pytest.raises(AssertionError, match="unsatisfied at gate"):
        build_circuit(_spec(32, 1, 3, 2))
    assert len(calls) >= 5
    monkeypatch.setattr(MW, "_native_trace", real)
    assert build_circuit(_spec(32, 1, 3, 2)).check_satisfiability() == \
        (True, -1)


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
    # every one of them computed by the native trace
    assert c["circuit_build_permutations_native"] == 2 * 35
    # paths that part below the root: still the tree's count and no more
    m = Metrics()
    build_circuit(_spec(3, 4, 9, 1), m)
    assert m.snapshot()["counters"] == {
        "circuit_builds": 1, "circuit_template_hits": 0,
        "circuit_build_permutations": 9 + 3 + 1 + 1,
        "circuit_build_permutations_native": 9 + 3 + 1 + 1}
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
    assert c["circuit_build_permutations_native"] == 2 * workers * (3 + 1)


def _rescue_native_pct(before, after):
    """The benchmark's reading of `rescue_native_pct`: its data file
    through the `service_metric` reader over two METRICS snapshots."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo not in sys.path:
        sys.path.insert(0, repo)
    from benchmark.lib import readers
    with open(os.path.join(repo, "benchmark", "layer_metrics",
                           "rescue_native_pct.json")) as f:
        spec = json.load(f)
    assert spec == {"kind": "service_metric",
                    "counter": "circuit_build_permutations_native",
                    "percent_of": "circuit_build_permutations",
                    "layer": "pool worker", "moves": "proofs_per_s"}
    return readers.read_service_metric(
        spec, readers.Evidence(metrics_open=before, metrics_close=after))


def test_served_jobs_count_their_builds():
    """Two jobs of one shape through the pool on the host oracle: the key
    build's seed-0 circuit fills the template, both jobs find it, every
    permutation is native (`rescue_native_pct` reads 100 over the window),
    and the proofs verify for the tree's own root."""
    shape = {"kind": "merkle", "height": 1, "num_proofs": 1}
    svc = ProofService(port=0, prover_workers=1).start()
    try:
        with ServiceClient("127.0.0.1", svc.port) as c:
            opened = c.metrics()
            ids = [c.submit(dict(shape, seed=s))["job_id"] for s in (4, 5)]
            results = []
            for jid in ids:
                assert c.wait(jid, timeout_s=300)["state"] == "done"
                results.append(c.result(jid))
            closed = c.metrics()
    finally:
        svc.shutdown()
    counters = closed["counters"]
    assert counters["circuit_builds"] == 2
    assert counters["circuit_template_hits"] == 2
    assert counters["circuit_build_permutations"] == 2 * (3 + 1)
    assert counters["circuit_build_permutations_native"] == 2 * (3 + 1)
    assert _rescue_native_pct(opened, closed) == 100.0
    # nothing built, nothing to read; a program without the counter reads 0
    assert _rescue_native_pct(closed, closed) is None
    assert _rescue_native_pct({"counters": {}}, {"counters": {
        "circuit_build_permutations": 8}}) == 0.0
    _srs, _pk, vk = build_bucket_keys(JobSpec.from_wire(shape))
    for seed, (header, blob) in zip((4, 5), results):
        rng = random.Random(seed)
        _ckt, tree = generate_circuit(rng, height=1, num_proofs=1)
        assert [int(x, 16) for x in header["public_input"]] == [tree.root]
        assert verify(vk, [tree.root], deserialize_proof(blob),
                      rng=random.Random(1))
