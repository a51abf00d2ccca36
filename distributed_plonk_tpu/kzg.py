"""KZG polynomial commitments: setup, preprocess, proving/verifying keys.

Re-provides the jf-plonk surface consumed by the reference:
`universal_setup` / `preprocess` (/root/reference/src/dispatcher2.rs:1279-1280)
and the commit-key layout the dispatcher pads to a multiple of 32
(/root/reference/src/dispatcher2.rs:207-208).
"""

import random

from .constants import R_MOD
from . import curve as C
from . import poly as P
from .circuit import NUM_WIRE_TYPES, NUM_SELECTORS


class UniversalSrs:
    def __init__(self, powers_of_g1, g2, tau_g2):
        self.powers_of_g1 = powers_of_g1  # [G1, tau G1, tau^2 G1, ...]
        self.g2 = g2
        self.tau_g2 = tau_g2


class VerifyingKey:
    def __init__(self, domain_size, num_inputs, selector_comms, sigma_comms,
                 k, g1, g2, tau_g2):
        self.domain_size = domain_size
        self.num_inputs = num_inputs
        self.selector_comms = selector_comms
        self.sigma_comms = sigma_comms
        self.k = k
        self.g1 = g1
        self.g2 = g2
        self.tau_g2 = tau_g2


class ProvingKey:
    """ck: commit key (G1 powers, padded); selectors: 13 coefficient
    vectors; sigmas: 5 coefficient vectors.

    When built by a device backend the host coefficient lists are LAZY:
    the device handles are what the prover consumes (registered via
    backend.register_pk_polys), and materializing 18 host int lists
    (~150 MB of device-to-host traffic at the 2^18 workload) only happens
    if an oracle/fleet consumer actually asks for them."""

    def __init__(self, ck, selectors, sigmas, vk, domain, lazy=None):
        self.ck = ck
        self._selectors = selectors
        self._sigmas = sigmas
        self._lazy = lazy  # () -> (selector_lists, sigma_lists)
        self.vk = vk
        self.domain = domain

    def _materialize(self):
        if self._selectors is None:
            self._selectors, self._sigmas = self._lazy()
            self._lazy = None  # release the captured backend/device handles

    @property
    def selectors(self):
        self._materialize()
        return self._selectors

    @property
    def sigmas(self):
        self._materialize()
        return self._sigmas

    @property
    def domain_size(self):
        return self.domain.size


def _tau_powers(max_degree, rng=None, tau=None):
    if tau is None:
        rng = rng or random.Random()
        tau = rng.randrange(1, R_MOD)
    powers = []
    acc = 1
    for _ in range(max_degree + 1):
        powers.append(acc)
        acc = acc * tau % R_MOD
    return tau, powers


def universal_setup(max_degree, rng=None, tau=None):
    """Simulated trusted setup (test SRS; tau is toxic waste).

    Mirrors PlonkKzgSnark::universal_setup (reference src/dispatcher2.rs:1279).
    """
    tau, powers = _tau_powers(max_degree, rng, tau)
    # batch the scalar muls through one Pippenger-style pass per power is
    # overkill here; direct double-and-add per power (host oracle only).
    powers_of_g1 = [C.g1_mul(C.G1_GEN, p) for p in powers]
    tau_g2 = C.g2_mul(C.G2_GEN, tau)
    return UniversalSrs(powers_of_g1, C.G2_GEN, tau_g2)


class DeviceSrs:
    """SRS whose G1 powers live on device as Jacobian Montgomery limb
    arrays ((24, N),)*3 — produced by the fixed-base batch kernel, consumed
    by DeviceCommitKey/MsmContext without ever visiting the host."""

    def __init__(self, jac_powers, count, g2, tau_g2):
        self.jac_powers = jac_powers
        self.count = count
        self.g2 = g2
        self.tau_g2 = tau_g2

    def powers_affine(self):
        """Host affine list, normalized on the device with one batched
        inversion, at the width of the commit key `pad_commit_key` makes of
        these powers: the width whose programs the key's window table runs
        (backend/msm_jax.window_table)."""
        from .backend import curve_jax as CJ
        return CJ.batch_to_host_affine(
            self.jac_powers, self.count + (-self.count) % 32)

    def to_host(self):
        """The UniversalSrs the host walk gives for the same tau: the same
        affine powers, so the same commit key layout and store blob."""
        return UniversalSrs(self.powers_affine(), self.g2, self.tau_g2)


def universal_setup_device(max_degree, rng=None, tau=None):
    """Trusted setup with the [tau^i]G1 walk run as one device batch
    (backend/fixed_base.py) instead of max_degree serial host scalar muls —
    the setup-scale blocker for reference-size domains (2^18 powers,
    reference workload src/dispatcher2.rs:1219-1221)."""
    from .backend.fixed_base import g1_batch_mul

    tau, powers = _tau_powers(max_degree, rng, tau)
    jac = g1_batch_mul(powers)
    tau_g2 = C.g2_mul(C.G2_GEN, tau)
    return DeviceSrs(jac, max_degree + 1, C.G2_GEN, tau_g2)


def commit_host(ck, coeffs):
    """Host-side commitment (oracle); device path uses backend MSM."""
    assert len(coeffs) <= len(ck)
    return C.g1_msm(ck[:len(coeffs)], coeffs)


def pad_commit_key(powers, srs_size):
    """Host G1 powers -> commit key: slice to srs_size, pad to a multiple
    of 32 with the identity, as the dispatcher does (reference
    src/dispatcher2.rs:207-208) so MSM shard sizes divide evenly.

    Shared by `preprocess` and the artifact store's key deserializer
    (store/keycache.py) — both must produce the IDENTICAL layout or a
    disk-loaded proving key would commit differently than a fresh one."""
    assert len(powers) >= srs_size, "SRS too small for this circuit"
    ck = list(powers[:srs_size])
    while len(ck) % 32 != 0:
        ck.append(None)
    return ck


def preprocess(srs, circuit, backend=None):
    """Build (pk, vk) for a finalized circuit.

    Mirrors PlonkKzgSnark::preprocess (reference src/dispatcher2.rs:1280):
    selector/sigma polynomials are iFFTs of their domain evaluations;
    their commitments go into the vk (and the Fiat-Shamir transcript).

    With a backend, the 18 iFFTs and 18 commitments run on its kernels (the
    commit key of a DeviceSrs stays device-resident, never normalized to
    host affine); without one, everything runs on the host oracle.
    """
    n = circuit.n
    domain = circuit.eval_domain
    srs_size = n + 3  # degree n+2 polys (blinded z) must be committable
    if isinstance(srs, DeviceSrs):
        assert backend is not None, "DeviceSrs requires a device backend"
        assert srs.count >= srs_size, "SRS too small for this circuit"
        from .backend.msm_jax import DeviceCommitKey
        import jax.numpy as jnp
        # pad further than the reference's x32 (dispatcher2.rs:207-208):
        # x1024 keeps the MSM bucket-scan group width at its 512 maximum
        # (msm_jax._group_size needs group | n), e.g. at the 2^18+3 SRS of
        # the 50-proof workload; identity padding never changes commitments
        padded = srs_size + (-srs_size) % 1024
        px, py, pz = (p[:, :srs_size] for p in srs.jac_powers)
        if padded > srs_size:
            ext = padded - srs_size
            px, py, pz = (jnp.pad(p, ((0, 0), (0, ext))) for p in (px, py, pz))
        ck = DeviceCommitKey(px, py, pz)
    else:
        ck = pad_commit_key(srs.powers_of_g1, srs_size)

    lazy = None
    if backend is not None:
        # the 18 iFFTs run as batched launches and the 18 commitments as
        # batched MSMs over poly HANDLES (device-resident end to end) —
        # round-2's per-poly int-list path made preprocess 14x the prove
        # (266 s at 2^13, scale_2p13.json) because every selector round-
        # tripped the host; this is the reference's join_all fan-out
        # (src/dispatcher2.rs:294-321) applied to setup
        cols = list(circuit.selectors) + list(circuit.sigma_values())
        assert len(circuit.selectors) == NUM_SELECTORS
        assert len(cols) == NUM_SELECTORS + NUM_WIRE_TYPES
        if hasattr(backend, "lift_many"):
            hs = backend.lift_many(cols)
        else:
            hs = [backend.lift(col) for col in cols]
        chs = backend.ifft_many(domain, hs)
        comms = backend.commit_many_h(ck, chs)
        selector_comms = comms[:NUM_SELECTORS]
        sigma_comms = comms[NUM_SELECTORS:]
        sel_h, sig_h = chs[:NUM_SELECTORS], chs[NUM_SELECTORS:]
        selectors = sigmas = None
        lazy = lambda: ([backend.lower(h) for h in sel_h],
                        [backend.lower(h) for h in sig_h])
    else:
        selectors = [P.ifft(domain, col) for col in circuit.selectors]
        sigmas = [P.ifft(domain, col) for col in circuit.sigma_values()]
        selector_comms = [commit_host(ck, s) for s in selectors]
        sigma_comms = [commit_host(ck, s) for s in sigmas]
        assert len(selectors) == NUM_SELECTORS and len(sigmas) == NUM_WIRE_TYPES

    vk = VerifyingKey(
        domain_size=n,
        num_inputs=circuit.num_inputs,
        selector_comms=selector_comms,
        sigma_comms=sigma_comms,
        k=list(circuit.k),
        g1=C.G1_GEN,
        g2=srs.g2,
        tau_g2=srs.tau_g2,
    )
    pk = ProvingKey(ck, selectors, sigmas, vk, domain, lazy=lazy)
    if backend is not None and hasattr(backend, "register_pk_polys"):
        # seed the backend's device cache so the prover's pk_polys() does
        # not re-lift host coefficient lists it just computed on device
        backend.register_pk_polys(pk, sel_h, sig_h)
    return pk, vk
