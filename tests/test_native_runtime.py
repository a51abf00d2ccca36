"""Native data plane (C++ limb codec/transpose) + framed transport +
distributed worker/dispatcher runtime.

The runtime analog of the reference's distributed tests (test_msm
/root/reference/src/dispatcher.rs:177-244, test_fft :246-350, test2
dispatcher2.rs:1273-1295) — but against an in-process localhost fleet
(SURVEY.md §4's "missing piece"), not a hand-provisioned LAN.
"""

import os
import random
import subprocess
import sys
import time

import numpy as np
import pytest

from distributed_plonk_tpu import curve as C
from distributed_plonk_tpu import poly as P
from distributed_plonk_tpu.constants import R_MOD
from distributed_plonk_tpu.backend.limbs import ints_to_limbs
from distributed_plonk_tpu.runtime import native, protocol
from distributed_plonk_tpu.runtime.netconfig import NetworkConfig
from distributed_plonk_tpu.runtime.dispatcher import Dispatcher, RemoteBackend

RNG = random.Random(0xD15)
REPO = os.path.normpath(os.path.join(os.path.dirname(__file__), ".."))


# --- data plane --------------------------------------------------------------

def test_native_limb_codec_matches_python():
    vals = [RNG.randrange(R_MOD) for _ in range(100)]
    raw = b"".join(v.to_bytes(32, "little") for v in vals)
    got = native.bytes_to_limbs(raw, 100, 32)
    assert np.array_equal(got, ints_to_limbs(vals, 16))
    assert native.limbs_to_bytes(got) == raw


def test_native_limb_codec_rejects_unreduced():
    bad = np.full((16, 4), 0x10000, dtype=np.uint32)
    with pytest.raises(ValueError):
        native.limbs_to_bytes(bad)


def test_native_transpose():
    a = np.arange(96 * 130, dtype=np.uint32).reshape(96, 130)
    assert np.array_equal(native.transpose(a), a.T)


# --- transport + fleet -------------------------------------------------------

def _spawn_fleet(tmp_path_factory, backend, port_base, startup_s):
    """Start a 2-worker fleet; yields a connected Dispatcher and always
    reaps the worker processes (including when startup fails)."""
    cfg_path = str(tmp_path_factory.mktemp(f"rt-{backend}") / "network.json")
    base = port_base + (os.getpid() % 500) * 2
    cfg = NetworkConfig([f"127.0.0.1:{base}", f"127.0.0.1:{base + 1}"])
    cfg.save(cfg_path)
    procs = [
        subprocess.Popen(
            [sys.executable, "-m", "distributed_plonk_tpu.runtime.worker",
             str(i), cfg_path, "--backend", backend],
            cwd=REPO)
        for i in range(2)
    ]
    try:
        d = None
        deadline = time.time() + startup_s
        while time.time() < deadline:
            try:
                d = Dispatcher(cfg)
                d.ping()
                break
            except (ConnectionError, OSError):
                time.sleep(0.3)
                d = None
        assert d is not None, f"{backend} workers did not come up"
        d.worker_procs = procs  # exposed for failure-injection tests
        yield d
        d.shutdown()
        for p in procs:
            p.wait(timeout=10)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()


@pytest.fixture(scope="module")
def fleet(tmp_path_factory):
    yield from _spawn_fleet(tmp_path_factory, "python", 19000, 30)


def test_distributed_msm(fleet):
    n = 64
    bases = [C.g1_mul(C.G1_GEN, RNG.randrange(1, R_MOD)) for _ in range(n - 1)]
    bases.append(None)
    scalars = [RNG.randrange(R_MOD) for _ in range(n - 1)] + [0]
    fleet.init_bases(bases)
    assert fleet.msm(scalars) == C.g1_msm(bases, scalars)


def test_distributed_ntt_all_modes(fleet):
    n = 64
    domain = P.Domain(n)
    values = [RNG.randrange(R_MOD) for _ in range(n)]
    assert fleet.ntt(values) == P.fft(domain, values)
    assert fleet.ntt(values, inverse=True) == P.ifft(domain, values)
    assert fleet.ntt(values, coset=True) == P.coset_fft(domain, values)
    assert fleet.ntt(values, inverse=True, coset=True) == P.coset_ifft(domain, values)
    jobs = [(values, False, False), (values, True, False), (values, False, True)]
    got = fleet.ntt_many(jobs)
    assert got == [P.fft(domain, values), P.ifft(domain, values),
                   P.coset_fft(domain, values)]


@pytest.mark.parametrize("coset", [False, True])
@pytest.mark.parametrize("inverse", [False, True])
def test_distributed_sharded_fft(fleet, inverse, coset):
    """Cross-worker 4-step FFT == oracle for all mode combos, both square
    (r == c) and uneven (r != c) splits — the fleet analog of the
    reference's test_fft 8-combo sweep (src/dispatcher.rs:246-350)."""
    for n in (64, 128):
        domain = P.Domain(n)
        values = [RNG.randrange(R_MOD) for _ in range(n)]
        if inverse and coset:
            want = P.coset_ifft(domain, values)
        elif inverse:
            want = P.ifft(domain, values)
        elif coset:
            want = P.coset_fft(domain, values)
        else:
            want = P.fft(domain, values)
        assert fleet.fft_dist(values, inverse=inverse, coset=coset) == want


def test_remote_prove_matches_oracle(fleet, proven):
    """Fully-distributed prove through the worker fleet == host proof
    (the reference's test2 invariant), with the per-poly NTT batches
    actually spread across >1 worker (join_all, dispatcher2.rs:294-321)."""
    from distributed_plonk_tpu.prover import prove
    from distributed_plonk_tpu.verifier import verify

    ckt, pk, vk, proof_host = proven
    before = fleet.stats()
    proof = prove(random.Random(1), ckt, pk, RemoteBackend(fleet))
    assert verify(vk, ckt.public_input(), proof, rng=random.Random(2))
    assert proof.opening_proof == proof_host.opening_proof
    assert proof.wires_poly_comms == proof_host.wires_poly_comms
    assert proof.split_quot_poly_comms == proof_host.split_quot_poly_comms

    # every worker served both NTTs and MSM shards during the prove
    after = fleet.stats()
    for b, a in zip(before, after):
        assert a.get(str(protocol.NTT), 0) > b.get(str(protocol.NTT), 0)
        assert a.get(str(protocol.MSM), 0) > b.get(str(protocol.MSM), 0)


def test_remote_prove_with_sharded_fft(fleet, proven):
    """Prove with every main-domain+ NTT run as the cross-worker sharded
    4-step FFT (the reference's v2 hot path, dispatcher2.rs:731-787):
    proof still byte-identical."""
    from distributed_plonk_tpu.prover import prove

    ckt, pk, vk, proof_host = proven
    before = fleet.stats()
    proof = prove(random.Random(1), ckt, pk,
                  RemoteBackend(fleet, dist_fft_min=ckt.n))
    assert proof.opening_proof == proof_host.opening_proof
    assert proof.split_quot_poly_comms == proof_host.split_quot_poly_comms
    after = fleet.stats()
    for b, a in zip(before, after):
        assert a.get(str(protocol.FFT2), 0) > b.get(str(protocol.FFT2), 0)
        assert a.get(str(protocol.FFT_EXCHANGE), 0) > b.get(str(protocol.FFT_EXCHANGE), 0)


@pytest.mark.slow
def test_sharded_fft_2p16_within_budget(fleet):
    """The fleet 4-step FFT at 2^16 under a wall-clock budget — the data
    plane is bulk limb codecs + numpy restrides end to end (VERDICT round-2
    weakness #8: the per-int Python plane was the 2^18 bottleneck); oracle
    checked via round-trip (forward then inverse) plus a spot-check against
    the host FFT on a random subset is too weak — full ifft oracle compare
    stays exact and is itself fast."""
    n = 1 << 16
    values = [RNG.randrange(R_MOD) for _ in range(n)]
    t0 = time.time()
    out = fleet.fft_dist(values, inverse=True)
    elapsed = time.time() - t0
    domain = P.Domain(n)
    assert out == P.ifft(domain, values)
    # generous for a 1-core CI host driving 2 python-backend workers; the
    # round-2 per-int plane was far beyond this at 2^16
    assert elapsed < 420, f"fleet 2^16 iFFT took {elapsed:.0f}s"


@pytest.fixture(scope="module")
def jax_fleet(tmp_path_factory):
    """Two workers on the JAX backend: FFT1/FFT2 run as single batched
    device launches over limb panels (runtime/jax_stages.py)."""
    yield from _spawn_fleet(tmp_path_factory, "jax", 21000, 60)


@pytest.mark.parametrize("inverse,coset", [
    (False, False), (True, True),
    pytest.param(False, True, marks=pytest.mark.tier2),
    pytest.param(True, False, marks=pytest.mark.tier2)])
def test_jax_fleet_sharded_fft(jax_fleet, inverse, coset):
    """Cross-worker 4-step FFT on jax workers (batched stage kernels) ==
    oracle, all mode combos, square and uneven splits."""
    for n in (64, 128):
        domain = P.Domain(n)
        values = [RNG.randrange(R_MOD) for _ in range(n)]
        if inverse and coset:
            want = P.coset_ifft(domain, values)
        elif inverse:
            want = P.ifft(domain, values)
        elif coset:
            want = P.coset_fft(domain, values)
        else:
            want = P.fft(domain, values)
        got = jax_fleet.fft_dist(values, inverse=inverse, coset=coset)
        assert got == want, (n, inverse, coset)


def test_msm_elastic_recovery(tmp_path_factory):
    """Kill one worker mid-prove: its MSM range is re-provisioned onto a
    healthy worker and the result is unchanged — the failure the reference
    cannot survive (every RPC is .unwrap(), SURVEY.md §5: 'a worker crash
    hangs or panics the prove')."""
    gen = _spawn_fleet(tmp_path_factory, "python", 23000, 30)
    d = next(gen)
    try:
        n = 64
        bases = [C.g1_mul(C.G1_GEN, RNG.randrange(1, R_MOD))
                 for _ in range(n)]
        scalars = [RNG.randrange(R_MOD) for _ in range(n)]
        want = C.g1_msm(bases, scalars)
        d.init_bases(bases)
        assert d.msm(scalars) == want

        d.worker_procs[1].kill()
        d.worker_procs[1].wait(timeout=10)
        assert d.msm(scalars) == want  # range 1 adopted by worker 0
    finally:
        gen.close()


def test_msm_recovery_memoized_and_repeated(tmp_path_factory):
    """After a death, later MSMs route straight to the adopting worker
    (no re-dial / re-upload), and a fresh init_bases resets adoptions."""
    gen = _spawn_fleet(tmp_path_factory, "python", 25000, 30)
    d = gen.__next__()
    try:
        n = 32
        bases = [C.g1_mul(C.G1_GEN, RNG.randrange(1, R_MOD))
                 for _ in range(n)]
        scalars = [RNG.randrange(R_MOD) for _ in range(n)]
        want = C.g1_msm(bases, scalars)
        d.init_bases(bases)
        d.worker_procs[1].kill()
        d.worker_procs[1].wait(timeout=10)
        assert d.msm(scalars) == want
        assert d._adopted == {1: 0}
        # memoized: repeated msm works and keeps the adoption
        assert d.msm(scalars) == want
        assert d._adopted == {1: 0}
        # re-provisioning with one worker dead still succeeds lazily
        bases2 = bases[::-1]
        d.init_bases(bases2)
        assert d._adopted == {}
        assert d.msm(scalars) == C.g1_msm(bases2, scalars)
        assert d._adopted == {1: 0}
    finally:
        gen.close()


def test_ntt_routes_around_dead_worker(tmp_path_factory):
    """Whole-poly NTT offload is stateless, so a dead worker is skipped."""
    gen = _spawn_fleet(tmp_path_factory, "python", 27000, 30)
    d = gen.__next__()
    try:
        n = 64
        domain = P.Domain(n)
        values = [RNG.randrange(R_MOD) for _ in range(n)]
        d.worker_procs[0].kill()
        d.worker_procs[0].wait(timeout=10)
        # worker index 0 is the preferred target; must fall through to 1
        assert d.ntt(values, worker=0) == P.fft(domain, values)
        assert d.ntt_many([(values, True, False)]) == \
            [P.ifft(domain, values)]
    finally:
        gen.close()
