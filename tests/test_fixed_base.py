"""Device fixed-base batch scalar mul + device SRS/preprocess path.

Oracle: the host double-and-add walk the reference's jf-plonk setup does
(/root/reference/src/dispatcher2.rs:1279). Invariant: DeviceSrs powers and
DeviceCommitKey commitments are bit-identical to the host oracle's."""

import random

import pytest

from distributed_plonk_tpu import curve as C
from distributed_plonk_tpu import kzg
from distributed_plonk_tpu.constants import R_MOD
from distributed_plonk_tpu.backend import curve_jax as CJ
from distributed_plonk_tpu.backend.fixed_base import FixedBaseContext


def test_batch_mul_matches_host_oracle():
    rng = random.Random(3)
    # edge scalars: 0 -> infinity, 1 -> G, r-1 -> -G, plus randoms
    scalars = [0, 1, R_MOD - 1, 2] + [rng.randrange(R_MOD) for _ in range(12)]
    ctx = FixedBaseContext(C.G1_GEN)
    got = CJ.device_to_affine(ctx.batch_mul(scalars))
    want = [C.g1_mul(C.G1_GEN, s) for s in scalars]
    assert got == want


def test_device_srs_matches_host_setup():
    srs_h = kzg.universal_setup(33, tau=987654321)
    srs_d = kzg.universal_setup_device(33, tau=987654321)
    assert srs_d.count == 34
    assert srs_d.powers_affine() == srs_h.powers_of_g1
    assert srs_d.tau_g2 == srs_h.tau_g2


def test_device_srs_over_several_chunks_matches_host_setup(monkeypatch):
    """A walk longer than one device call (2^16 keys take three of 2^15
    lanes) pads its last chunk with zero scalars and slices them off; the
    powers, normalized at the width of their commit key, are the host's."""
    from distributed_plonk_tpu.backend.fixed_base import FixedBaseContext
    monkeypatch.setattr(FixedBaseContext, "_CHUNK", 16)
    srs_h = kzg.universal_setup(40, tau=987654321)
    srs_d = kzg.universal_setup_device(40, tau=987654321)
    assert srs_d.count == 41 and srs_d.jac_powers[0].shape == (24, 41)
    host = srs_d.to_host()
    assert host.powers_of_g1 == srs_h.powers_of_g1
    assert host.tau_g2 == srs_h.tau_g2 and host.g2 == srs_h.g2


@pytest.mark.tier2
def test_device_preprocess_matches_host(proven_inputs):
    """Device SRS + backend preprocess produce the identical pk/vk (and so
    the identical transcript/proof downstream) as the host-oracle path."""
    from distributed_plonk_tpu.backend.jax_backend import JaxBackend

    ckt, srs_h, pk_h, vk_h = proven_inputs
    srs_d = kzg.universal_setup_device(ckt.n + 2, tau=424242)
    be = JaxBackend()
    pk_d, vk_d = kzg.preprocess(srs_d, ckt, backend=be)
    assert vk_d.selector_comms == vk_h.selector_comms
    assert vk_d.sigma_comms == vk_h.sigma_comms
    assert pk_d.selectors == pk_h.selectors
    assert pk_d.sigmas == pk_h.sigmas


@pytest.fixture(scope="module")
def proven_inputs():
    from distributed_plonk_tpu.workload import generate_circuit

    ckt, _ = generate_circuit(rng=random.Random(5), height=2, num_proofs=1)
    srs = kzg.universal_setup(ckt.n + 2, tau=424242)
    pk, vk = kzg.preprocess(srs, ckt)
    return ckt, srs, pk, vk
