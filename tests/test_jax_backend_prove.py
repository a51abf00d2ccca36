"""End-to-end prove on the JAX device backend.

The device analog of the reference's `test2` (fully-distributed prove,
/root/reference/src/dispatcher2.rs:1273-1295): every FFT and MSM of the
5-round prover runs through the device kernels, the proof must be
bit-identical to the host-oracle proof (same rng) and verify.
"""

import random

import pytest

from distributed_plonk_tpu.prover import prove
from distributed_plonk_tpu.verifier import verify
from distributed_plonk_tpu.backend.jax_backend import JaxBackend


def test_jax_prove_verifies_and_matches_oracle(proven):
    ckt, pk, vk, proof_host = proven
    be = JaxBackend()
    proof_dev = prove(random.Random(1), ckt, pk, be)
    assert verify(vk, ckt.public_input(), proof_dev, rng=random.Random(2))

    # device residency: O(n) host->device uploads are the proving key, the
    # circuit witness/permutation tables (once each, cached) and the
    # public-input vector; the only lower is the single batched round-4
    # evaluation transfer (everything else stays on device between rounds)
    assert be.lifts == 3, be.lifts
    assert be.lowers == 1, be.lowers

    # bit-identical across backends (the reference's core invariant:
    # distributed == single-node, SURVEY.md §4)
    assert proof_dev.wires_poly_comms == proof_host.wires_poly_comms
    assert proof_dev.prod_perm_poly_comm == proof_host.prod_perm_poly_comm
    assert proof_dev.split_quot_poly_comms == proof_host.split_quot_poly_comms
    assert proof_dev.opening_proof == proof_host.opening_proof
    assert proof_dev.shifted_opening_proof == proof_host.shifted_opening_proof
    assert proof_dev.wires_evals == proof_host.wires_evals
    assert proof_dev.wire_sigma_evals == proof_host.wire_sigma_evals
    assert proof_dev.perm_next_eval == proof_host.perm_next_eval


@pytest.mark.slow
def test_jax_prove_msm_pallas_byte_identical(proven, monkeypatch):
    """DPT_MSM_KERNEL=pallas (the fused VMEM-resident bucket kernel)
    produces the SAME proof bytes as the host oracle — and therefore as
    the default-kernel prove above. Slow tier: every commitment batch
    recompiles through the interpret-mode Mosaic emulation."""
    from distributed_plonk_tpu import proof_io
    from distributed_plonk_tpu.backend import msm_jax

    ckt, pk, vk, proof_host = proven
    monkeypatch.setattr(msm_jax, "_MSM_KERNEL", "pallas")
    proof_pl = prove(random.Random(1), ckt, pk, JaxBackend())
    assert (proof_io.serialize_proof(proof_pl)
            == proof_io.serialize_proof(proof_host))


@pytest.mark.slow
def test_jax_prove_r3_unfused_byte_identical(proven, monkeypatch):
    """DPT_R3_FUSE=0 (the standalone gate/sigma/combine step programs)
    produces the SAME proof bytes as the default fused round 3 — the
    tier-1 oracle test above runs the FUSED path, so together they pin
    both sides of the round-3 fusion seam."""
    from distributed_plonk_tpu import proof_io
    from distributed_plonk_tpu.backend import jax_backend

    ckt, pk, vk, proof_host = proven
    monkeypatch.setattr(jax_backend, "_R3_FUSE", False)
    proof_uf = prove(random.Random(1), ckt, pk, JaxBackend())
    assert (proof_io.serialize_proof(proof_uf)
            == proof_io.serialize_proof(proof_host))


@pytest.mark.slow
def test_jax_prove_radix2_byte_identical(proven, monkeypatch):
    """DPT_NTT_RADIX=2 (the parity/debug core) produces the SAME proof
    bytes as the host oracle — and therefore as the default radix-4
    prove above. Slow tier: a second full set of prover-kernel compiles."""
    from distributed_plonk_tpu import proof_io

    ckt, pk, vk, proof_host = proven
    monkeypatch.setenv("DPT_NTT_RADIX", "2")
    proof_r2 = prove(random.Random(1), ckt, pk, JaxBackend())
    assert (proof_io.serialize_proof(proof_r2)
            == proof_io.serialize_proof(proof_host))
