"""Device NTT kernels vs the poly.py oracle — all 8 flag combos, both radices.

Mirrors the reference's FFT integration matrix ({main,quot} x {fwd,inv} x
{coset,plain}, /root/reference/src/dispatcher.rs:273-345) with the oracle
being the pure-Python radix-2 NTT, on an even-log2 domain (64: pure radix-4
stages, peeled-last path) and an odd-log2 domain (128: radix-2 fixup-stage
path). The radix-4 fused-twiddle core must be BIT-identical to both the
oracle and the radix-2 parity core (`DPT_NTT_RADIX`), at single, batch,
and shared-stage-core granularity — that kernel-level identity is what
makes proofs byte-identical across radices.
"""

import random

import numpy as np
import pytest

from distributed_plonk_tpu import poly as P
from distributed_plonk_tpu.constants import R_MOD
from distributed_plonk_tpu.backend import ntt_jax
from distributed_plonk_tpu.backend.ntt_jax import get_plan

RNG = random.Random(0x7717)


def _oracle(domain, values, inverse, coset):
    if inverse and coset:
        return P.coset_ifft(domain, values)
    if inverse:
        return P.ifft(domain, values)
    if coset:
        return P.coset_fft(domain, values)
    return P.fft(domain, values)


@pytest.mark.parametrize("n", [64, 128])  # even and odd log2(n)
@pytest.mark.parametrize("inverse", [False, True])
@pytest.mark.parametrize("coset", [False, True])
def test_ntt_matches_oracle(n, inverse, coset):
    domain = P.Domain(n)
    plan = get_plan(n)
    values = [RNG.randrange(R_MOD) for _ in range(n)]
    got = plan.run_ints(values, inverse=inverse, coset=coset)
    assert got == _oracle(domain, values, inverse, coset)


@pytest.mark.parametrize("inverse", [False, True])
@pytest.mark.parametrize("coset", [False, True])
def test_radix2_matches_radix4(inverse, coset):
    """The radix-2 parity core and the radix-4 fused-twiddle core are
    bit-identical in every mode (n=64 reuses the radix-4 kernels compiled
    above; only the radix-2 variants compile here)."""
    n = 64
    plan = get_plan(n)
    values = [RNG.randrange(R_MOD) for _ in range(n)]
    r4 = plan.run_ints(values, inverse=inverse, coset=coset, radix=4)
    r2 = plan.run_ints(values, inverse=inverse, coset=coset, radix=2)
    assert r4 == r2


def test_radix_env_knob(monkeypatch):
    """DPT_NTT_RADIX routes kernel construction (the msm_jax
    DPT_BUCKET_UPDATE pattern): resolved per call, no plan rebuild.
    Memo keys carry the resolved radix."""
    plan = get_plan(64)
    monkeypatch.setenv("DPT_NTT_RADIX", "2")
    plan.kernel(boundary="plain")
    assert (False, False, "plain", 2) in plan._fns
    monkeypatch.setenv("DPT_NTT_RADIX", "4")
    plan.kernel(boundary="plain")
    assert (False, False, "plain", 4) in plan._fns
    monkeypatch.setenv("DPT_NTT_RADIX", "3")
    with pytest.raises(ValueError):
        plan.kernel(boundary="plain")
    # tiny domains have no radix-4 stage: radix 4 falls back to the
    # radix-2 body and still matches the oracle
    monkeypatch.delenv("DPT_NTT_RADIX")
    tiny = get_plan(2)
    vals = [RNG.randrange(R_MOD) for _ in range(2)]
    assert tiny._effective_radix() == 2
    assert tiny.run_ints(vals, radix=4) == P.fft(P.Domain(2), vals)


def test_batch_kernel_matches_single():
    """(16, B, n) Montgomery batch kernel == B single launches, radix-4
    coset modes (the round-1/round-3 prover batches)."""
    import jax.numpy as jnp

    n, b = 64, 3
    plan = get_plan(n)
    v = np.random.default_rng(5).integers(
        0, 1 << 16, size=(16, b, n), dtype=np.uint32)
    for inverse, coset in ((False, True), (True, True)):
        got = np.asarray(plan.kernel_batch(inverse, coset, radix=4)(
            jnp.asarray(v)))
        want = np.stack(
            [np.asarray(plan.kernel(inverse, coset, radix=4)(
                jnp.asarray(v[:, j]))) for j in range(b)], axis=1)
        assert (got == want).all(), (inverse, coset)


@pytest.mark.tier2
def test_shared_stage_core_radix_parity():
    """run_stages (the core the mesh NTT and fleet panels call) is
    bit-identical across the radix-2 and radix-4 table sets, forward and
    inverse (eager dispatch: no XLA compile). Inputs must be CANONICAL
    limb vectors (< p): that is the contract every real pipeline meets,
    and the trivial-twiddle first-stage peel (which skips multiplies by
    the Montgomery ONE) is only a bitwise no-op on that domain."""
    import jax.numpy as jnp
    from distributed_plonk_tpu.backend.limbs import ints_to_limbs

    n, b = 64, 2
    plan = get_plan(n)
    vals = [RNG.randrange(R_MOD) for _ in range(b * n)]
    v = jnp.asarray(ints_to_limbs(vals, 16).reshape(16, b, n))
    for inverse in (False, True):
        c2 = {k: jnp.asarray(a)
              for k, a in plan.core_consts(inverse, radix=2).items()}
        c4 = {k: jnp.asarray(a)
              for k, a in plan.core_consts(inverse, radix=4).items()}
        assert "exps4" in c4 and "exps" in c2
        r2 = np.asarray(ntt_jax.run_stages(v, c2))
        r4 = np.asarray(ntt_jax.run_stages(v, c4))
        assert (r2 == r4).all(), inverse


def test_ntt_short_input_padding():
    n = 64
    domain = P.Domain(n)
    plan = get_plan(n)
    values = [RNG.randrange(R_MOD) for _ in range(20)]
    assert plan.run_ints(values) == P.fft(domain, values)


def test_fft_ifft_roundtrip_device():
    n = 64
    plan = get_plan(n)
    values = [RNG.randrange(R_MOD) for _ in range(n)]
    assert plan.run_ints(plan.run_ints(values), inverse=True) == values
    assert plan.run_ints(plan.run_ints(values, coset=True),
                         inverse=True, coset=True) == values
