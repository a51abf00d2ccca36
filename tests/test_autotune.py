"""Kernel autotuner + calibration artifacts (ISSUE 14).

The acceptance surface of the measured kernel-dispatch plan:

  - a plan round-trips through the content-addressed store byte for byte
  - a fingerprint mismatch (foreign/hand-copied plan) means REBUILD,
    never crash and never another machine's winners
  - the winner parity gate rejects a fast-but-WRONG candidate (injected
    via a lying fake timer)
  - an explicit DPT_* knob beats the plan at every resolver
  - DPT_AUTOTUNE=off (and a plan-less load) is byte- and counter-
    identical to the pre-autotune tree
  - ProofService and a fleet worker pick a store plan up at startup with
    zero measurement runs, and a mid-process plan reload can never serve
    a kernel memo entry traced under the previous plan (cache_key folds
    the plan revision into every memo key)

Everything runs at tiny shapes on XLA:CPU (the `ci.sh autotune` smoke
tier, which `ci.sh fast` includes).
"""

import threading

import numpy as np
import pytest
import jax.numpy as jnp

from distributed_plonk_tpu.backend import autotune as AT
from distributed_plonk_tpu.backend import field_jax as FJ
from distributed_plonk_tpu.backend import msm_jax as MJ
from distributed_plonk_tpu.backend import ntt_jax as NJ
from distributed_plonk_tpu.constants import FR_LIMBS, FR_MONT_R, R_MOD
from distributed_plonk_tpu.backend.limbs import ints_to_limbs
from distributed_plonk_tpu.service.metrics import Metrics
from distributed_plonk_tpu.store import ArtifactStore, calibration

N = 64  # tiny calibration shape: every kernel compiles in seconds on CPU


@pytest.fixture(autouse=True)
def _clean_plan(monkeypatch):
    """Every test starts knob-free and plan-free, and leaves the
    process-global plan the way it found it."""
    for k in ("DPT_AUTOTUNE", "DPT_NTT_RADIX", "DPT_NTT_KERNEL",
              "DPT_MSM_GROUP_MAX", "DPT_FIELD_MUL", "DPT_MSM_C"):
        monkeypatch.delenv(k, raising=False)
    prev = AT.active_plan()
    AT.set_active_plan(None)
    yield
    AT.set_active_plan(prev)


def _mont_vec(n, seed=7):
    rng = np.random.default_rng(seed)
    vals = rng.integers(1, 1 << 62, size=n, dtype=np.int64)
    return jnp.asarray(ints_to_limbs(
        [int(v) * FR_MONT_R % R_MOD for v in vals], FR_LIMBS))


def _plan_for_here(cells):
    return AT.KernelPlan(AT.machine_fingerprint(), cells)


# --- plan artifact mechanics -------------------------------------------------

def test_parse_shapes():
    assert calibration.parse_shapes("2^10, 2^14,4096") == [1024, 4096, 16384]


def test_plan_store_roundtrip_byte_identical(tmp_path):
    store = ArtifactStore(str(tmp_path))
    plan = _plan_for_here({("ntt", N): {"params": {"radix": 2,
                                                   "kernel": "xla"}},
                           ("field", N): {"params": {"mul": "f32"}}})
    plan.meta = {"budget_s": 1.0}
    digest1 = calibration.store_plan(store, plan)
    blob = store.get(calibration.plan_store_key(plan.fingerprint))
    assert blob == plan.to_json_bytes()
    back = calibration.load_plan(store)
    assert back is not None
    assert back.to_json_bytes() == plan.to_json_bytes()
    assert back.cells == plan.cells and back.meta == plan.meta
    # canonical JSON: re-storing the identical plan is the identical blob
    assert calibration.store_plan(store, back) == digest1


def test_foreign_fingerprint_means_rebuild_not_crash(tmp_path, monkeypatch):
    store = ArtifactStore(str(tmp_path))
    fp = AT.machine_fingerprint()
    # a hand-copied artifact: OUR key, ANOTHER machine's embedded id
    foreign = AT.KernelPlan("feedfacef00d",
                            {("ntt", N): {"params": {"radix": 2}}})
    store.put(calibration.plan_store_key(fp), foreign.to_json_bytes())
    assert calibration.load_plan(store) is None

    calls = []

    class FakeTuner:
        def __init__(self, shapes, budget_s=None, metrics=None, **kw):
            calls.append(shapes)

        def run(self, aot=False):
            return _plan_for_here({("ntt", N): {"params": {"radix": 4}}})

    monkeypatch.setattr(AT, "Autotuner", FakeTuner)
    rep = calibration.load_or_run(store, mode="run", shapes=[N], aot=False)
    assert rep["source"] == "fresh" and calls == [[N]]
    assert AT.active_plan().fingerprint == fp
    # the rebuilt plan replaced the foreign blob under the same key
    assert calibration.load_plan(store).lookup("ntt", "radix") == 4


def test_future_plan_version_is_ignored(tmp_path):
    store = ArtifactStore(str(tmp_path))
    plan = _plan_for_here({})
    blob = plan.to_json_bytes().replace(b'"version": 1',
                                        b'"version": 999')
    store.put(calibration.plan_store_key(plan.fingerprint), blob)
    assert calibration.load_plan(store) is None
    assert AT.KernelPlan.from_json_bytes(b"not json at all") is None


def test_calibration_lock_measures_once(tmp_path, monkeypatch):
    """Concurrent starters against one store: one measures under the
    fcntl lock, the loser loads the winner's plan."""
    store = ArtifactStore(str(tmp_path))
    runs = []

    class SlowTuner:
        def __init__(self, shapes, budget_s=None, metrics=None, **kw):
            pass

        def run(self, aot=False):
            runs.append(1)
            return _plan_for_here({("ntt", N): {"params": {"radix": 2}}})

    monkeypatch.setattr(AT, "Autotuner", SlowTuner)
    reports = []
    threads = [threading.Thread(target=lambda: reports.append(
        calibration.load_or_run(store, mode="run", shapes=[N], aot=False)))
        for _ in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert len(runs) == 1
    assert sorted(r["source"] for r in reports) == ["fresh", "store",
                                                    "store"]


# --- precedence: env knob > plan > default -----------------------------------

def test_plan_drives_resolvers_and_env_overrides(monkeypatch):
    AT.set_active_plan(_plan_for_here({
        ("ntt", N): {"params": {"radix": 2, "kernel": "xla"}},
        ("msm", N): {"params": {"bucket_update": "onehot",
                                "group_max": 1024, "c": 8}},
        ("field", N): {"params": {"mul": "u32"}},
    }))
    # plan wins over built-in defaults (radix 4 / put-on-cpu / 512 / 7)
    assert NJ._active_radix(n=N) == 2
    assert MJ._use_onehot_update(N) is True
    assert MJ._group_max_knob(N) == 1024
    assert MJ._c_batch_knob(1 << 10) == 8
    assert FJ._mul_path(N) == "u32" and FJ._f32_active(N) is False
    # nearest-cell lookup: a nearby size resolves to the calibrated cell
    assert NJ._active_radix(n=2 * N) == 2
    # explicit env knobs win over the plan at every resolver
    monkeypatch.setenv("DPT_NTT_RADIX", "4")
    monkeypatch.setenv("DPT_MSM_GROUP_MAX", "256")
    assert NJ._active_radix(n=N) == 4
    assert MJ._group_max_knob(N) == 256
    # attr-latched knobs: a test/registry patch away from the default
    # counts as explicit too
    monkeypatch.setattr(MJ, "_BUCKET_UPDATE", "put")
    monkeypatch.setattr(FJ, "_MUL_MODE", "f32")
    assert MJ._use_onehot_update(N) is False
    assert FJ._mul_path(N) == "f32" and FJ._f32_active(N) is True
    monkeypatch.setenv("DPT_MSM_C", "7")
    monkeypatch.setattr(MJ.MsmContext, "_C_BATCH", 7)
    assert MJ._c_batch_knob(1 << 10) == 7


def test_malformed_plan_values_fall_back_to_defaults():
    """A plan is machine state, not operator input: values outside the
    accepted choices (or non-numeric garbage) resolve to the built-in
    defaults instead of raising at dispatch time — a broken plan must
    never break a prove (only explicit knobs may raise)."""
    from distributed_plonk_tpu.backend import field_pallas as FP

    AT.set_active_plan(_plan_for_here(
        {("msm", 1 << 10): {"params": {"c": 9, "group_max": "junk"}},
         ("ntt", 1 << 10): {"params": {"radix": 3}},
         ("field", 1 << 10): {"params": {"lane_tile": 0}}}))
    assert MJ._c_batch_knob(1 << 10) == 7
    assert MJ._group_max_knob(1 << 10) == 512
    assert NJ._active_radix(n=1 << 10) == 4
    # lane_tile divides the padded lane count: 0/non-power-of-two plan
    # values must never reach the BlockSpec math
    assert FP.lane_tile(1 << 10) == FP.LANE_TILE_DEFAULT


# --- off / plan-less parity --------------------------------------------------

def test_off_mode_touches_nothing(tmp_path):
    store = ArtifactStore(str(tmp_path))
    calibration.store_plan(
        store, _plan_for_here({("ntt", N): {"params": {"radix": 2}}}))
    v = _mont_vec(N)
    before = np.asarray(NJ.get_plan(N).kernel(boundary="mont")(v)).tobytes()
    m = Metrics()
    rep = calibration.load_or_run(store, mode="off", metrics=m)
    assert rep == {"source": "off"}
    assert AT.active_plan() is None  # the stored plan was not even read
    assert m.snapshot()["counters"] == {}
    after = np.asarray(NJ.get_plan(N).kernel(boundary="mont")(v)).tobytes()
    assert after == before


def test_plan_less_load_is_counter_free(tmp_path):
    m = Metrics()
    rep = calibration.load_or_run(ArtifactStore(str(tmp_path)), mode="load",
                                  metrics=m)
    assert rep["source"] == "none" and rep["measure_runs"] == 0
    assert AT.active_plan() is None
    assert m.snapshot()["counters"] == {}
    with pytest.raises(ValueError):
        calibration.load_or_run(ArtifactStore(str(tmp_path)), mode="bogus")


# --- the measure pass --------------------------------------------------------

def test_parity_gate_rejects_lying_candidate():
    """A candidate that returns WRONG bytes with a too-good-to-be-true
    timer must lose to the (slower) parity core, and be counted."""

    class LyingTuner(AT.Autotuner):
        def _run_candidate(self, kind, n, cand):
            out, dt, aux = super()._run_candidate(kind, n, cand)
            if cand.get("radix") == 4:  # the non-parity candidate lies
                return b"fast wrong answer", 1e-9, aux
            return out, dt, aux

    m = Metrics()
    plan = LyingTuner([N], budget_s=600, kinds=("ntt",), metrics=m).run()
    cell = plan.cell("ntt", N)
    assert cell is not None
    assert cell["params"]["radix"] == 2  # the liar was NOT adopted
    assert cell["parity_rejects"] >= 1
    assert m.snapshot()["counters"]["autotune_parity_rejects"] >= 1


def test_cell_abandoned_when_parity_core_fails():
    """If the PARITY CORE itself cannot be measured, the cell is dropped
    (defaults stay in force) — the next candidate must never silently
    become the bit-identity reference."""

    class BrokenParityTuner(AT.Autotuner):
        def _run_candidate(self, kind, n, cand):
            if cand == self.PARITY[kind]:
                raise RuntimeError("parity core refused to run")
            return super()._run_candidate(kind, n, cand)

    m = Metrics()
    plan = BrokenParityTuner([N], budget_s=600, kinds=("ntt",),
                             metrics=m).run()
    assert plan.cell("ntt", N) is None
    assert m.snapshot()["counters"]["autotune_candidate_errors"] >= 1
    # what the candidate raised is carried out in the plan, not swallowed
    (err,) = plan.meta["candidate_errors"][f"ntt:{N}"]
    assert "parity core refused to run" in err["error"]
    assert "autotune_parity_rejects" not in m.snapshot()["counters"]


def test_cell_dropped_when_budget_stops_before_default():
    """A budget that expires after the parity reference but before the
    knob-free default config was measured leaves the cell UNDECIDED: it
    must be dropped, not persisted with the (slow) parity core as its
    winner — a truncated run stays 'always safe' (defaults in force)."""

    class OneMeasureTuner(AT.Autotuner):
        def _run_candidate(self, kind, n, cand):
            out = super()._run_candidate(kind, n, cand)
            self._deadline = 0.0  # budget gone after the first measure
            return out

    plan = OneMeasureTuner([N], budget_s=600, kinds=("ntt",)).run()
    assert plan.cell("ntt", N) is None


def test_tiny_calibration_fresh_then_store(tmp_path, monkeypatch):
    """Real measure pass (ntt + field at 2^6 on XLA:CPU) through
    load_or_run: first start calibrates + persists, the second adopts
    the stored plan with ZERO measurement runs (Autotuner poisoned)."""
    store = ArtifactStore(str(tmp_path))
    m = Metrics()
    real = AT.Autotuner

    def small_tuner(shapes, budget_s=None, metrics=None, **kw):
        return real(shapes, budget_s=budget_s, metrics=metrics,
                    kinds=("ntt", "field"), **kw)

    monkeypatch.setattr(AT, "Autotuner", small_tuner)
    rep = calibration.load_or_run(store, mode="run", shapes=[N],
                                  budget_s=600, metrics=m, aot=False)
    assert rep["source"] == "fresh" and rep["measure_runs"] > 0
    plan = AT.active_plan()
    assert plan is not None and plan.cell("ntt", N) is not None
    ntt_cell = plan.cell("ntt", N)
    assert ntt_cell["params"]["kernel"] == "xla"
    assert ntt_cell["params"]["radix"] in NJ.RADIX_CHOICES
    assert plan.cell("field", N)["params"]["mul"] in ("f32", "u32")
    assert m.snapshot()["counters"]["autotune_plan_stores"] == 1

    def poisoned(*a, **kw):
        raise AssertionError("second start must not measure")

    monkeypatch.setattr(AT, "Autotuner", poisoned)
    m2 = Metrics()
    rep2 = calibration.load_or_run(store, mode="run", shapes=[N],
                                   metrics=m2, aot=False)
    assert rep2["source"] == "store" and rep2["measure_runs"] == 0
    assert m2.snapshot()["counters"]["autotune_plan_loads"] == 1
    assert m2.snapshot()["counters"].get("autotune_measure_runs", 0) == 0
    assert AT.active_plan().to_json_bytes() == plan.to_json_bytes()
    # the winner's dispatch is bit-identical to the parity core
    v = _mont_vec(N)
    with_plan = np.asarray(
        NJ.get_plan(N).kernel(boundary="mont")(v)).tobytes()
    AT.set_active_plan(None)
    parity = np.asarray(NJ.get_plan(N).kernel(
        boundary="mont", radix=2, kernel="xla")(v)).tobytes()
    assert with_plan == parity


def test_msm_candidates_collapse_through_resolvers(monkeypatch):
    """Candidate dedup: an env-pinned dimension collapses the grid onto
    what would actually run, so pinned configs are measured once."""
    tuner = AT.Autotuner([N], budget_s=600)
    monkeypatch.setenv("DPT_MSM_GROUP_MAX", "512")
    sigs = {tuple(sorted(tuner._resolved("msm", N, c).items()))
            for c in tuner._candidates("msm", N)}
    assert all(dict(s)["group_max"] == 512 for s in sigs)
    assert len(sigs) == 2  # only the bucket_update axis survives on CPU


# --- memo invalidation across plan reloads -----------------------------------

def test_plan_reload_invalidates_kernel_memos():
    rev0 = AT.plan_revision()
    assert AT.cache_key("a", 1) == ("a", 1, rev0)
    plan = _plan_for_here({("ntt", N): {"params": {"radix": 2}}})
    AT.set_active_plan(plan)
    p = NJ.get_plan(N)
    p.kernel(boundary="mont")
    n_fns = len(p._fns)
    # same plan re-installed (a reload): same resolved config, but the
    # revision bump means the old compiled entry is never served
    AT.set_active_plan(plan)
    assert AT.plan_revision() > rev0
    p.kernel(boundary="mont")
    assert len(p._fns) == n_fns + 1
    # MsmContext chunk/calibration keys fold the revision in too
    ctx = MJ.MsmContext([(1, 2)] * 8)
    k1 = ctx._chunk_key(8, 4)
    c1 = ctx._calib_key()
    AT.set_active_plan(plan)
    assert ctx._chunk_key(8, 4) != k1 and ctx._calib_key() != c1


def test_plan_rate_seeds_chunk_sizing(monkeypatch):
    """A calibrated adds/s rate sizes MSM chunks from the FIRST call —
    but only when the context dispatches the kernel the plan measured
    (an explicit override to the other kernel must not size chunks from
    the wrong rate)."""
    n = 300  # >= 256: the wide signed pipeline with c_batch
    AT.set_active_plan(_plan_for_here({("msm", n): {"params": {
        "kernel": "xla", "adds_per_s": 1e9}}}))
    ctx = MJ.MsmContext([(1, 2)] * n)
    assert ctx._plan_rate() == 1e9
    # env-forced pallas while the plan's rate was measured under xla
    monkeypatch.setattr(MJ, "_MSM_KERNEL", "pallas")
    assert MJ.MsmContext([(1, 2)] * n)._plan_rate() is None


# --- service + fleet worker pickup -------------------------------------------

def test_service_picks_up_store_plan(tmp_path):
    from distributed_plonk_tpu.service import ProofService

    store_dir = str(tmp_path / "store")
    calibration.store_plan(
        ArtifactStore(store_dir),
        _plan_for_here({("ntt", N): {"params": {"radix": 2}}}))
    svc = ProofService(port=0, prover_workers=1,
                       store_dir=store_dir).start()
    try:
        assert svc.autotune["source"] == "store"
        assert svc.autotune["measure_runs"] == 0
        snap = svc.metrics.snapshot()
        assert snap["counters"]["autotune_plan_loads"] == 1
        assert snap["counters"].get("autotune_measure_runs", 0) == 0
        assert snap["gauges"]["autotune_plan_source"] == "store"
        assert AT.active_plan().fingerprint == AT.machine_fingerprint()
    finally:
        svc.shutdown()


def test_worker_picks_up_store_plan(tmp_path):
    import socket

    from distributed_plonk_tpu.runtime import native, protocol, worker
    from distributed_plonk_tpu.runtime.netconfig import NetworkConfig

    store_dir = str(tmp_path / "wstore")
    calibration.store_plan(
        ArtifactStore(store_dir),
        _plan_for_here({("field", N): {"params": {"mul": "u32"}}}))
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    ready = threading.Event()
    t = threading.Thread(
        target=worker.serve,
        args=(0, NetworkConfig([f"127.0.0.1:{port}"])),
        kwargs={"backend_name": "python", "ready_event": ready,
                "store_dir": store_dir},
        daemon=True)
    t.start()
    assert ready.wait(timeout=30)
    try:
        plan = AT.active_plan()
        assert plan is not None
        assert plan.lookup("field", "mul") == "u32"
    finally:
        conn = native.connect("127.0.0.1", port)
        conn.send(protocol.SHUTDOWN)
        assert conn.recv()[0] == protocol.OK
        conn.close()
        t.join(timeout=15)
