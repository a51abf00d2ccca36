"""Round-pipelined multi-job proving tests (prover.prove_pipelined +
the pool's coalesced routing).

The hard contract pinned here: jobs advancing through the five round
stages STAGGERED — one member's device launches overlapping the others'
host transcript/checkpoint work — produce proof bytes BYTE-IDENTICAL to
sequential proves, at every depth, with mixed per-job blinding RNGs and
MIXED CIRCUIT KINDS (per-member proving keys). Plus the failure-domain
semantics at the stage latches: DPT_PIPELINE=0 is a bit-parity escape
hatch; a member killed mid-pipeline resumes ALONE from its round
snapshot (no round-1 re-prove) while the others complete in-flight; a
drain parks EVERY member at its own next latch, each resumable to the
same bytes.

Everything runs the host oracle backend at tiny domains (jax-free), so
the module lives in the fast/chaos tier.
"""

import random

import pytest

from distributed_plonk_tpu import prover
from distributed_plonk_tpu.backend.python_backend import PythonBackend
from distributed_plonk_tpu.checkpoint import ProverCheckpoint
from distributed_plonk_tpu.proof_io import serialize_proof
from distributed_plonk_tpu.prover import prove, prove_pipelined
from distributed_plonk_tpu.service import ProofService
from distributed_plonk_tpu.service import placement as PL
from distributed_plonk_tpu.service.jobs import (JobSpec, build_bucket_keys,
                                                build_circuit)

# mixed kinds: different domain sizes AND different proving keys, so the
# pipeline is exercised with per-member pks (not one shared key)
MIXED = [{"kind": "toy", "gates": 16, "seed": 4100},
         {"kind": "range", "bits": 8, "count": 2, "seed": 4101},
         {"kind": "toy", "gates": 16, "seed": 4102},
         {"kind": "range", "bits": 8, "count": 2, "seed": 4103}]


def _keys(spec_obj, _cache={}):
    s = JobSpec.from_wire(spec_obj)
    key = (s.kind, tuple(sorted(s.params.items())))
    if key not in _cache:
        _cache[key] = build_bucket_keys(s)[1]
    return s, _cache[key]


def _sequential_proof(spec_obj):
    """Uninterrupted single prove of a spec — the byte oracle."""
    s, pk = _keys(spec_obj)
    return serialize_proof(prove(random.Random(s.seed), build_circuit(s),
                                 pk, PythonBackend()))


def _members(specs):
    rngs, ckts, pks = [], [], []
    for spec in specs:
        s, pk = _keys(spec)
        rngs.append(random.Random(s.seed))
        ckts.append(build_circuit(s))
        pks.append(pk)
    return rngs, ckts, pks


# --- byte-identity across depths, mixed kinds --------------------------------

@pytest.mark.parametrize("depth", [
    pytest.param(1, marks=pytest.mark.tier2),
    pytest.param(2, marks=pytest.mark.tier2), 4])
def test_pipeline_byte_identity(depth):
    """Depth-D pipelined prove of 4 mixed-kind jobs == 4 sequential
    proves, byte for byte. The depth-4 run also checks the stage
    observer saw the pipeline actually fill past one member."""
    oracle = [_sequential_proof(s) for s in MIXED]
    events = []
    rngs, ckts, pks = _members(MIXED)
    proofs, errors = prove_pipelined(rngs, ckts, pks, PythonBackend(),
                                     depth=depth, observer=events.append)
    assert errors == [None] * len(MIXED)
    assert [serialize_proof(p) for p in proofs] == oracle
    assert len(events) == 5 * len(MIXED)  # one per member stage finalize
    for ev in events:
        assert {"round", "depth", "stage_wait_s", "force_wait_s",
                "finalize_s"} <= set(ev)
        # what the device waited for is the ledger's account now
        # (device_unfed_s/<phase>), not a per-round guess of the driver
        assert "device_idle_s" not in ev
    if depth >= 2:
        assert max(ev["depth"] for ev in events) >= 2


def test_pipeline_knob_off_parity(monkeypatch):
    """DPT_PIPELINE=0: prove_pipelined degrades to the sequential
    per-job path — same signature, identical bytes."""
    monkeypatch.setattr(prover, "PIPELINE", False)
    oracle = [_sequential_proof(s) for s in MIXED[:2]]
    rngs, ckts, pks = _members(MIXED[:2])
    proofs, errors = prove_pipelined(rngs, ckts, pks, PythonBackend(),
                                     depth=4)
    assert errors == [None, None]
    assert [serialize_proof(p) for p in proofs] == oracle


# --- stage-latch failure domains ---------------------------------------------

class _Killed(Exception):
    pass


class _Drained(Exception):
    pass


class _LatchCheckpoint(ProverCheckpoint):
    """Checkpoint guard that raises `exc` right after the `at_round`
    snapshot is durable — the same crash point the pool's kill/drain
    guards model. Records every save's round number."""

    def __init__(self, path, at_round=None, exc=None):
        super().__init__(path)
        self.at_round = at_round
        self.exc = exc
        self.saved_rounds = []

    def save(self, round_no, *args, **kwargs):
        super().save(round_no, *args, **kwargs)
        self.saved_rounds.append(round_no)
        if self.exc is not None and round_no == self.at_round:
            raise self.exc(f"latch fired after round {round_no}")


@pytest.mark.tier2
def test_pipeline_member_kill_resumes_alone(tmp_path):
    """A member-local failure at its round-2 latch takes down ONLY that
    member: the others complete in-flight (same call, correct bytes),
    and the victim's solo retry RESUMES from its snapshot — saving only
    rounds 3-4, never re-proving 1-2 — to byte-identical bytes."""
    specs = MIXED[:3]
    oracle = [_sequential_proof(s) for s in specs]
    cks = [_LatchCheckpoint(str(tmp_path / f"m{i}.npz"),
                            at_round=2 if i == 1 else None,
                            exc=_Killed if i == 1 else None)
           for i in range(len(specs))]
    rngs, ckts, pks = _members(specs)
    proofs, errors = prove_pipelined(rngs, ckts, pks, PythonBackend(),
                                     checkpoints=cks, depth=4)
    assert proofs[0] is not None and proofs[2] is not None
    assert proofs[1] is None and isinstance(errors[1], _Killed)
    assert [serialize_proof(p) for p in (proofs[0], proofs[2])] == \
        [oracle[0], oracle[2]]
    # the victim's snapshot is durable at its latch; the solo retry
    # resumes at round 3 (the pool's single-job retry path)
    assert cks[1].saved_rounds == [1, 2]
    s, pk = _keys(specs[1])
    resume_ck = _LatchCheckpoint(cks[1].path)
    proof = prove(random.Random(s.seed), build_circuit(s), pk,
                  PythonBackend(), checkpoint=resume_ck)
    assert serialize_proof(proof) == oracle[1]
    assert resume_ck.saved_rounds == [3, 4]  # resumed, never re-proved 1-2
    assert not resume_ck.has_snapshot()  # cleared on success


@pytest.mark.tier2
def test_pipeline_drain_parks_every_member(tmp_path):
    """An abort_on exception (the pool's drain signal) at one member's
    latch aborts the whole pipeline: every member parks at its OWN next
    stage latch — snapshot durable at its last completed round — and
    each resumes independently to byte-identical bytes."""
    specs = MIXED[:3]
    oracle = [_sequential_proof(s) for s in specs]
    cks = [_LatchCheckpoint(str(tmp_path / f"d{i}.npz"),
                            at_round=2 if i == 0 else None,
                            exc=_Drained if i == 0 else None)
           for i in range(len(specs))]
    rngs, ckts, pks = _members(specs)
    with pytest.raises(_Drained):
        prove_pipelined(rngs, ckts, pks, PythonBackend(),
                        checkpoints=cks, abort_on=(_Drained,), depth=4)
    # every member parked at its own latch: whatever rounds it finished
    # are snapshot, in order, nothing past round 2 (the drain point)
    for ck in cks:
        assert ck.saved_rounds == list(range(1, len(ck.saved_rounds) + 1))
    assert cks[0].saved_rounds == [1, 2]
    for spec, ck, want in zip(specs, cks, oracle):
        s, pk = _keys(spec)
        proof = prove(random.Random(s.seed), build_circuit(s), pk,
                      PythonBackend(), checkpoint=ProverCheckpoint(ck.path))
        assert serialize_proof(proof) == want


# --- service routing: queue coalescing fills the pipeline --------------------

@pytest.mark.tier2
def test_service_coalesces_queue_into_pipeline(monkeypatch):
    """With shape-batching OFF (jobs arrive as single dispatch units),
    a worker that pops one unit coalesces its queue neighbors into a
    pipelined attempt — small-shape traffic fills the pipeline without
    the placement layer forming a batch — and every proof still matches
    the sequential oracle."""
    monkeypatch.setattr(PL, "BATCH_PROVE", False)
    specs = [dict(MIXED[i % 2], seed=4200 + i) for i in range(4)]
    svc = ProofService(port=0, prover_workers=1)
    jobs = [svc.submit_local(s) for s in specs]  # queued before start
    svc.start()
    try:
        for j in jobs:
            assert j.done_event.wait(timeout=180), j.status()
            assert j.state == "done"
        ctr = svc.metrics.snapshot()["counters"]
        # coalesced singles are NOT shape batches
        assert "batch_proves" not in ctr
        assert ctr.get("pipelined_proves", 0) >= 1
        assert ctr.get("pipelined_jobs", 0) >= 2
        for spec, job in zip(specs, jobs):
            assert job.proof_bytes == _sequential_proof(spec)
    finally:
        svc.shutdown()


def test_service_pipeline_off_routes_sequential(monkeypatch):
    """DPT_PIPELINE=0 at the service layer: no coalescing, no pipelined
    attempts — the historical per-job path, identical bytes."""
    monkeypatch.setattr(prover, "PIPELINE", False)
    monkeypatch.setattr(PL, "BATCH_PROVE", False)
    specs = [dict(MIXED[0], seed=4300 + i) for i in range(2)]
    svc = ProofService(port=0, prover_workers=1)
    jobs = [svc.submit_local(s) for s in specs]
    svc.start()
    try:
        for j in jobs:
            assert j.done_event.wait(timeout=180), j.status()
            assert j.state == "done"
        ctr = svc.metrics.snapshot()["counters"]
        assert "pipelined_proves" not in ctr
        for spec, job in zip(specs, jobs):
            assert job.proof_bytes == _sequential_proof(spec)
    finally:
        svc.shutdown()
