"""Both bucket-plane update strategies (one-hot / put) must agree with
the host oracle bit-for-bit, and the group-width knob must sanitize its
input (the TPU default is onehot — the round-4 4.4x MSM fix — while CPU
tests otherwise only exercise put; this locks the other path in CI)."""

import random

import jax
import pytest

from distributed_plonk_tpu import curve as C
from distributed_plonk_tpu.constants import R_MOD
from distributed_plonk_tpu.backend import msm_jax as M

RNG = random.Random(0x1407)


# tier-1 keeps the TPU default (onehot, packed planes); `put` is what
# every other CPU test already runs
@pytest.mark.parametrize("mode,pack", [
    pytest.param("put", True, marks=pytest.mark.tier2), ("onehot", True),
    pytest.param("onehot", False, marks=pytest.mark.tier2)])
def test_update_strategies_match_oracle(mode, pack, monkeypatch):
    monkeypatch.setattr(M, "_BUCKET_UPDATE", mode)
    monkeypatch.setattr(M, "_PLANE_PACK", pack)
    # the strategy branch is resolved at trace time inside jitted scans:
    # drop cached executables so the patched mode actually traces
    jax.clear_caches()
    n = 256
    pts = [C.g1_mul(C.G1_GEN, RNG.randrange(1, R_MOD))
           for _ in range(32)] * (n // 32)
    ks = [RNG.randrange(R_MOD) for _ in range(n)]
    try:
        assert M.msm(pts, ks) == C.g1_msm(pts, ks)
    finally:
        jax.clear_caches()


def test_group_max_knob_sanitized(monkeypatch):
    monkeypatch.setenv("DPT_MSM_GROUP_MAX", "768")  # non-power-of-two
    assert M._group_size(1 << 20) == 512  # rounded down, not collapsed to 1
    monkeypatch.setenv("DPT_MSM_GROUP_MAX", "0")
    assert M._group_size(1 << 20) >= 1
    monkeypatch.setenv("DPT_MSM_GROUP_MAX", "2048")
    # the g*1024 > n fold-work cap still applies above the default
    assert M._group_size(1 << 20) == 1024
    assert M._group_size(1 << 10) == 1