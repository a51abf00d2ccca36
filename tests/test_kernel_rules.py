"""Which kernel runs is a rule in the code: each resolver reads its own
module attribute (latched from its DPT_* variable at import, patchable
here) and, where the answer depends on the platform,
jax.default_backend(). This file is the table of those rules, the
precedence of an explicit knob over them, the memo keys that follow the
resolved mode, the MSM chunk rule (a commit's chunk sizes from its shape
alone) and its counters, the compile cache's partition, and a start of
the service and of a joined worker on a store
that still holds an older run's `autotune:<fp>` artifact."""

import hashlib
import os
import platform
import random
import threading

import jax
import jax.numpy as jnp
import pytest

from distributed_plonk_tpu.backend import field_jax as FJ
from distributed_plonk_tpu.backend import field_pallas as FP
from distributed_plonk_tpu.backend import msm_jax as MJ
from distributed_plonk_tpu.backend import ntt_jax as NJ

_KNOB_ENV = ("DPT_NTT_RADIX", "DPT_MSM_GROUP_MAX")


@pytest.fixture
def knob_free(monkeypatch):
    """No DPT_* kernel knob set: the module attributes hold what an
    import without the variables latches."""
    for k in _KNOB_ENV:
        monkeypatch.delenv(k, raising=False)
    monkeypatch.setattr(FJ, "_MUL_MODE", "auto")
    monkeypatch.setattr(MJ, "_BUCKET_UPDATE", "auto")
    monkeypatch.setattr(MJ, "_MSM_KERNEL", "auto")
    monkeypatch.setattr(MJ, "_PLANE_PACK", True)
    monkeypatch.setattr(MJ.MsmContext, "_C_BATCH", 7)
    return monkeypatch


# --- the rule, by platform ----------------------------------------------------

_WIDE = (16, 1 << 13)  # past _PALLAS_MIN_LANES: what a prove multiplies at

_RULE = {
    "ntt_radix": (lambda: NJ._active_radix(), {"tpu": 4, "cpu": 4}),
    "msm_kernel": (lambda: MJ._kernel_mode(), {"tpu": "xla", "cpu": "xla"}),
    "bucket_update": (lambda: MJ._use_onehot_update(),
                      {"tpu": True, "cpu": False}),   # onehot | put
    "packed_planes": (lambda: MJ._use_packed_planes(),
                      {"tpu": True, "cpu": False}),   # only with onehot
    # `auto` on both; what auto means is the fused Pallas multiply for a
    # wide shape on a TPU and the XLA f32 path everywhere else
    "field_mul": (lambda: (FJ._mul_path(), FJ._use_pallas(_WIDE),
                           FJ.pallas_mul_possible(), FJ._f32_active()),
                  {"tpu": ("auto", True, True, True),
                   "cpu": ("auto", False, False, True)}),
}


@pytest.mark.parametrize("decision", sorted(_RULE))
@pytest.mark.parametrize("backend", ["tpu", "cpu"])
def test_auto_rule(knob_free, backend, decision):
    knob_free.setattr(jax, "default_backend", lambda: backend)
    resolve, want = _RULE[decision]
    assert resolve() == want[backend]


# --- the window table's rule ---------------------------------------------------
# msm_jax.use_window_table(signed, kernel, n, c): what a commit context can
# observe, and nothing else, decides whether it holds 2^(c*w) * P_i for
# every window (and so ends a commit in the bucket running sum alone).

_GIB = 1 << 30

_TABLE_RULE = {
    # the served key: 16,416 points at c = 7 is 117 MB of table
    "served-2p14": ((True, "xla", 16416, 7), True),
    "mesh-2p14-c8": ((True, "xla", 16448, 8), True),
    # the source's own 2^18 is inside the budget, 2^20 (7.5 GB) outside
    "source-2p18": ((True, "xla", (1 << 18) + 32, 7), True),
    "over-budget-2p20": ((True, "xla", (1 << 20) + 32, 7), False),
    # the unsigned small-window path (a key under 256 points)
    "unsigned": ((False, "xla", 200, 4), False),
    # the fused Pallas scan takes one point a step
    "pallas-kernel": ((True, "pallas", 16416, 7), False),
}


@pytest.mark.parametrize("row", sorted(_TABLE_RULE))
def test_window_table_rule(knob_free, row):
    args, want = _TABLE_RULE[row]
    assert MJ.use_window_table(*args) is want


def test_window_table_budget_is_bytes_of_the_table():
    assert MJ.table_bytes(16416, 7) == 37 * 16416 * 192 == 116_619_264
    assert MJ.table_bytes((1 << 18) + 32, 7) < MJ._TABLE_BYTES_BUDGET \
        == 2 * _GIB < MJ.table_bytes((1 << 20) + 32, 7)


def _ctx_wide_key(mp):
    ctx = MJ.MsmContext([(1, 2)] * 300)
    assert ctx.signed and ctx.padded_n == 304      # whole 8-row tiles
    assert ctx.table is not None and ctx._preweighted()
    assert ctx.table[0].shape == (304 // 8, 8, 24 * 37)
    # a kernel flipped after the build: the table stays, and is not served
    mp.setattr(MJ, "_MSM_KERNEL", "pallas")
    assert not ctx._preweighted()


def _ctx_small_key(mp):
    ctx = MJ.MsmContext([(1, 2)] * 8)
    assert not ctx.signed and ctx.table is None and not ctx._preweighted()


def _ctx_pallas_kernel(mp):
    mp.setattr(MJ, "_MSM_KERNEL", "pallas")
    ctx = MJ.MsmContext([(1, 2)] * 300)
    assert ctx.signed and ctx.table is None and not ctx._preweighted()


def _ctx_over_budget(mp):
    mp.setattr(MJ, "_TABLE_BYTES_BUDGET", MJ.table_bytes(304, 7) - 1)
    assert MJ.MsmContext([(1, 2)] * 300).table is None
    mp.setattr(MJ, "_TABLE_BYTES_BUDGET", MJ.table_bytes(304, 7))
    assert MJ.MsmContext([(1, 2)] * 300).table is not None


@pytest.mark.parametrize("case", [
    _ctx_wide_key, _ctx_small_key, _ctx_pallas_kernel, _ctx_over_budget],
    ids=lambda f: f.__name__[len("_ctx_"):])
def test_window_table_follows_the_rule_in_a_context(knob_free, case):
    case(knob_free)


def _commit_share(metric, counter, base, before, after):
    """A commit counter's share as the benchmark reads it: the metric's
    data file through the `service_metric` reader over two METRICS
    snapshots."""
    import json
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo not in sys.path:
        sys.path.insert(0, repo)
    from benchmark.lib import readers
    with open(os.path.join(repo, "benchmark", "layer_metrics",
                           metric + ".json")) as f:
        spec = json.load(f)
    assert spec == {"kind": "service_metric", "counter": counter,
                    "percent_of": base,
                    "layer": "kernels", "moves": "proofs_per_s"}
    return readers.read_service_metric(
        spec, readers.Evidence(metrics_open=before, metrics_close=after))


def _preweighted_pct(before, after):
    return _commit_share("msm_preweighted_pct", "msm_commit_polys_preweighted",
                         "msm_commit_polys", before, after)


def _chunks_pct(before, after):
    return _commit_share("msm_chunks_pct", "msm_commit_chunks",
                         "msm_commit_calls", before, after)


@pytest.fixture
def served_backend():
    """(backend, service): a started service whose one pool worker has
    handed its JaxBackend the service's Metrics (`attach`)."""
    from distributed_plonk_tpu.backend.jax_backend import JaxBackend
    from distributed_plonk_tpu.service import ProofService

    be = JaxBackend()
    assert be.metrics is None
    svc = ProofService(port=0, prover_workers=1,
                       backend=be).start()
    try:
        for _ in range(600):
            if be.metrics is not None:
                break
            threading.Event().wait(0.05)
        assert be.metrics is svc.metrics
        yield be, svc
    finally:
        svc.shutdown()


def test_commit_counters_reach_the_service_and_read_as_a_share(
        knob_free, served_backend):
    """The one-chip half of ISSUE 36's counter: a pool worker hands its
    JaxBackend the service's Metrics when it starts (`attach`, the hook a
    leased MeshBackend already had), the backend's commit contexts count
    `msm_commit_polys` and, when the window table served the commit,
    `msm_commit_polys_preweighted`, and the benchmark's data file reads
    the share: 100 where every commit came from a table, less where a
    narrow key's did not, nothing where no commit ran. (A served prove at
    a toy size commits over a narrow key: test_chip_smoke reads its 13
    polynomials and a share of 0.)"""
    from distributed_plonk_tpu import curve as C

    be, svc = served_backend
    rng = random.Random(36)
    pts = [C.g1_mul(C.G1_GEN, rng.randrange(1, 1 << 200)) for _ in range(4)]
    wide, narrow = (pts * 64)[:256], pts * 2
    polys = [[rng.randrange(1 << 250) for _ in range(200)], [5, 0, 7]]
    s0 = svc.metrics.snapshot()
    assert _preweighted_pct(s0, s0) is None         # no commit ran
    assert "msm_commit_polys" not in s0["counters"]
    assert be.commit_many(wide, polys) == [
        C.g1_msm(wide[:len(p)], p) for p in polys]
    s1 = svc.metrics.snapshot()
    assert s1["counters"]["msm_commit_polys"] == 2
    assert s1["counters"]["msm_commit_polys_preweighted"] == 2
    assert _preweighted_pct(s0, s1) == 100.0
    assert _preweighted_pct(s1, s1) is None
    be.commit_many(narrow, [p[:8] for p in polys])   # an 8-point key
    s2 = svc.metrics.snapshot()
    assert s2["counters"]["msm_commit_polys"] == 4
    assert s2["counters"]["msm_commit_polys_preweighted"] == 2
    assert _preweighted_pct(s0, s2) == 50.0
    assert _preweighted_pct(s1, s2) == 0.0
    # a service of the parent commit has neither counter
    assert _preweighted_pct({"counters": {"jobs_completed": 0}},
                            {"counters": {"jobs_completed": 9}}) is None


# --- an explicit knob wins over the rule --------------------------------------

def _inner_mul_width(lanes):
    """Padded lane count the fused multiplier's pallas program sees."""
    arg = jax.ShapeDtypeStruct((16, lanes), jnp.uint32)
    jaxpr = jax.make_jaxpr(lambda a, b: FP.mont_mul(FJ.FR, a, b))(arg, arg)
    (inner,) = [e for e in jaxpr.eqns
                if e.params.get("name") == "_mont_mul_flat"]
    return inner.invars[0].aval.shape[1]


def _knob_mul_mode(mp):
    mp.setattr(jax, "default_backend", lambda: "tpu")
    mp.setattr(FJ, "_MUL_MODE", "u32")
    assert FJ._mul_path() == "u32" and not FJ._f32_active()
    assert not FJ._use_pallas(_WIDE) and not FJ.pallas_mul_possible()
    mp.setattr(FJ, "_MUL_MODE", "pallas")
    mp.setattr(jax, "default_backend", lambda: "cpu")
    assert FJ._use_pallas(_WIDE) and FJ.pallas_mul_possible()
    with FJ.pallas_disabled():     # the guard wins even over the knob
        assert not FJ._use_pallas(_WIDE) and not FJ.pallas_mul_possible()


def _knob_bucket_update(mp):
    mp.setattr(jax, "default_backend", lambda: "cpu")
    mp.setattr(MJ, "_BUCKET_UPDATE", "onehot")
    assert MJ._use_onehot_update() and MJ._use_packed_planes()
    mp.setattr(MJ, "_PLANE_PACK", False)
    assert not MJ._use_packed_planes()
    mp.setattr(jax, "default_backend", lambda: "tpu")
    mp.setattr(MJ, "_BUCKET_UPDATE", "put")
    assert not MJ._use_onehot_update()


def _knob_msm_kernel(mp):
    mp.setattr(MJ, "_MSM_KERNEL", "pallas")
    assert MJ._kernel_mode() == "pallas"
    assert MJ.MsmContext([(1, 2)] * 8)._mode() == "pallas"
    with FJ.pallas_disabled():
        assert MJ._kernel_mode() == "xla"


def _knob_group_max(mp):
    assert MJ._group_size(1 << 20) == 512
    mp.setenv("DPT_MSM_GROUP_MAX", "64")     # lowers the cap too
    assert MJ._group_size(1 << 20) == 64
    assert MJ._group_size_batch(1 << 20, 1, 7, signed=True) == 64


def _knob_msm_c(mp):
    assert MJ.MsmContext([(1, 2)] * 300).c_batch == 7
    mp.setattr(MJ.MsmContext, "_C_BATCH", 8)
    ctx = MJ.MsmContext([(1, 2)] * 300)
    assert ctx.c_batch == 8 and ctx.signed
    # a tiny key keeps the unsigned small-window scan whatever the knob
    assert MJ.MsmContext([(1, 2)] * 8).c_batch == MJ.window_bits(8)


def _knob_lane_tile(mp):
    assert _inner_mul_width(20) == FP.LANE_TILE
    mp.setattr(FP, "LANE_TILE", 8)
    assert _inner_mul_width(20) == 24


@pytest.mark.parametrize("knob", [
    _knob_mul_mode, _knob_bucket_update, _knob_msm_kernel, _knob_group_max,
    _knob_msm_c, _knob_lane_tile], ids=lambda f: f.__name__[len("_knob_"):])
def test_explicit_knob_wins(knob_free, knob):
    knob(knob_free)


# --- memo keys follow the resolved mode ---------------------------------------

def _lowered(fn, consts, n):
    return fn.lower(jax.ShapeDtypeStruct((16, n), jnp.uint32),
                    consts).as_text()


def _site_ntt_plan(mp):
    plan = NJ.NttPlan(16)
    mp.setenv("DPT_NTT_RADIX", "2")
    fn2, c2 = plan.traced_kernel()
    mp.setenv("DPT_NTT_RADIX", "4")
    fn4, c4 = plan.traced_kernel()
    assert set(plan._fns) == {(False, False, "mont", 2),
                              (False, False, "mont", 4)}
    assert "exps" in c2 and "exps4" in c4
    assert _lowered(fn2, c2, 16) != _lowered(fn4, c4, 16)


def _site_mesh_ntt_plan(mp):
    from distributed_plonk_tpu.parallel.mesh import make_mesh
    from distributed_plonk_tpu.parallel.ntt_mesh import MeshNttPlan

    plan = MeshNttPlan(make_mesh(2, platform="cpu"), 16)
    mp.setenv("DPT_NTT_RADIX", "2")
    plan.kernel()
    mp.setenv("DPT_NTT_RADIX", "4")
    plan.kernel()
    k2, k4 = (False, False, "mont", 2), (False, False, "mont", 4)
    assert set(plan._fns) == {k2, k4}
    (fn2, c2), (fn4, c4) = plan._fns[k2], plan._fns[k4]
    assert "exps" in c2["core_r"] and "exps4" in c4["core_r"]
    assert _lowered(fn2, c2, 16) != _lowered(fn4, c4, 16)


def _site_stage_kernels(mp):
    from distributed_plonk_tpu.runtime.jax_stages import StageKernels

    sk = StageKernels()
    mp.setenv("DPT_NTT_RADIX", "2")
    t2 = sk._plan_consts(16, False)
    mp.setenv("DPT_NTT_RADIX", "4")
    t4 = sk._plan_consts(16, False)
    assert set(sk._tables) == {("plan", 16, False, 2), ("plan", 16, False, 4)}
    assert "exps" in t2 and "exps4" in t4
    assert sk._plan_consts(16, False) is t4


def _site_msm_chunk(mp):
    ctx = MJ.MsmContext([(1, 2)] * 8)
    assert ctx._chunk_key(8, 4) == (8, 4, "xla")
    fx = ctx._chunk_fn(8, 4)
    mp.setattr(MJ, "_MSM_KERNEL", "pallas")
    assert ctx._chunk_key(8, 4) == (8, 4, "pallas")
    fp = ctx._chunk_fn(8, 4)
    assert fp is not fx and len(ctx._chunk_fns) == 2
    mp.setattr(MJ, "_MSM_KERNEL", "xla")
    assert ctx._chunk_fn(8, 4) is fx


def _site_msm_finish(mp):
    ctx = MJ.MsmContext([(1, 2)] * 300)
    assert ctx._preweighted()
    fx = ctx._finish_fn(5)
    mp.setattr(MJ, "_MSM_KERNEL", "pallas")     # the table was built for xla
    assert not ctx._preweighted()
    fp = ctx._finish_fn(5)
    assert fp is not fx and set(ctx._finish_fns) == {(5, True), (5, False)}
    mp.setattr(MJ, "_MSM_KERNEL", "xla")
    assert ctx._finish_fn(5) is fx


@pytest.mark.parametrize("site", [
    _site_ntt_plan, _site_mesh_ntt_plan, _site_stage_kernels,
    _site_msm_chunk, _site_msm_finish],
    ids=lambda f: f.__name__[len("_site_"):])
def test_memo_key_follows_resolved_mode(knob_free, site):
    site(knob_free)


# --- the MSM chunk rule: a commit's chunk sizes from its shape alone ----------

W = 37            # windows of a 255-bit scalar at c = 7
SPLIT_ADDS = 80_000   # a budget under which a 1,104-point commit is one
#                       call at B = 1 and two (1,024 + 80) at B = 5


def _chunks(budget, n, B):
    """The chunk sizes `_exec_chunked` cuts a commit of n points into."""
    chunk = max(1024, (budget // (B * W)) & ~1023)
    return [min(chunk, n - i0) for i0 in range(0, n, chunk)]


class _Counted:
    """A wide-window context over real points that counts into a dict."""

    def __init__(self, n=1100):
        from distributed_plonk_tpu import curve as C
        rng = random.Random(38)
        pts = [C.g1_mul(C.G1_GEN, rng.randrange(1, 1 << 200))
               for _ in range(4)]
        self.bases = (pts * (n // 4 + 1))[:n]
        self.counted = {}
        self.ctx = MJ.MsmContext(self.bases, count=self._count)
        self.polys = [[rng.randrange(1 << 250) for _ in range(n - 7 * j)]
                      for j in range(5)]

    def _count(self, name, by=1):
        self.counted[name] = self.counted.get(name, 0) + by

    def commit(self, polys):
        """(points, device calls made) of one `_exec_chunked`."""
        before = dict(self.counted)
        out = self.ctx.msm_many(polys)
        assert self.counted["msm_commit_calls"] \
            == before.get("msm_commit_calls", 0) + 1
        return out, (self.counted["msm_commit_chunks"]
                     - before.get("msm_commit_chunks", 0))


@pytest.fixture(scope="module")
def counted():
    return _Counted()


def _rule_served_shapes(mp, counted):
    # the commit keys of the sizes served today (2^13, 2^14, 2^16: n + 3
    # points padded to whole tiles), at every batch width a prove or a
    # batch of proves commits: one device call each
    assert MJ.MsmContext._CALL_ADDS == 1 << 27
    for n in (8224, 16416, 65568):
        for B in (1, 2, 5, 8):
            assert counted.ctx._chunk_lanes(B, W) >= n
            assert _chunks(1 << 27, n, B) == [n]


def _rule_is_the_shape_alone(mp, counted):
    # one expression of (budget, B, W): no context state, no history
    other = MJ.MsmContext([(1, 2)] * 8)      # a narrow key, no table
    for B, w in ((1, 37), (5, 37), (8, 32), (64, 64)):
        want = max(1024, ((1 << 27) // (B * w)) & ~1023)
        assert counted.ctx._chunk_lanes(B, w) == want
        assert other._chunk_lanes(B, w) == want
    assert not any("adds_per_s" in k or "calib" in k
                   for k in vars(MJ.MsmContext))


def _rule_floor(mp, counted):
    mp.setattr(MJ.MsmContext, "_CALL_ADDS", 1)
    assert counted.ctx._chunk_lanes(5, W) == 1024
    mp.setattr(MJ.MsmContext, "_CALL_ADDS", 1 << 27)
    assert counted.ctx._chunk_lanes(1 << 20, 64) == 1024


def _rule_alignment(mp, counted):
    for budget in (8_000_000, 12_345_678, 1 << 27):
        mp.setattr(MJ.MsmContext, "_CALL_ADDS", budget)
        for B in (1, 2, 5, 8):
            lanes = counted.ctx._chunk_lanes(B, W)
            assert lanes % 1024 == 0 and lanes * B * W <= budget
            assert (lanes + 1024) * B * W > budget
    # the budget this tree had before: a 2^16 commit at B = 5 was two calls
    assert _chunks(8_000_000, 65568, 5) == [43008, 22560]


def _rule_budget_is_the_one_knob(mp, counted):
    # what DPT_MSM_CALL_ADDS sets, on one chip and (per device) on a mesh
    from distributed_plonk_tpu.parallel.msm_mesh import MeshMsmContext
    assert MeshMsmContext._CALL_ADDS == 8_000_000
    mp.setattr(MJ.MsmContext, "_CALL_ADDS", SPLIT_ADDS)
    n = counted.ctx.padded_n
    assert n == 1104
    assert _chunks(SPLIT_ADDS, n, 1) == [n]
    assert _chunks(SPLIT_ADDS, n, 5) == [1024, 80]
    assert counted.ctx._chunk_lanes(5, W) == 1024


def _rule_a_split_commit_equals_the_one_call(mp, counted):
    from distributed_plonk_tpu import curve as C
    whole, calls = counted.commit(counted.polys)
    assert calls == 1
    mp.setattr(MJ.MsmContext, "_CALL_ADDS", SPLIT_ADDS)
    split, calls = counted.commit(counted.polys)
    assert calls == 2
    assert split == whole == [C.g1_msm(counted.bases[:len(p)], p)
                              for p in counted.polys]


@pytest.mark.parametrize("case", [
    _rule_served_shapes, _rule_is_the_shape_alone, _rule_floor,
    _rule_alignment, _rule_budget_is_the_one_knob,
    _rule_a_split_commit_equals_the_one_call],
    ids=lambda f: f.__name__[len("_rule_"):])
def test_chunk_rule(knob_free, counted, case):
    case(knob_free, counted)


@pytest.mark.parametrize("order", [(5, 1, 5, 1), (1, 5, 1, 5)],
                         ids=["wide-first", "narrow-first"])
def test_chunks_do_not_follow_the_process_history(knob_free, counted, order):
    """What the rate latch got wrong: a process whose first warm call was
    slow (a compile, another job's queued work) ran every later commit in
    several small chunks. Here the process first runs a commit and waits
    for it on the host, the fence the latch timed; then commits at B = 5
    and B = 1 in either order make the device calls their shapes give, and
    give the same points."""
    knob_free.setattr(MJ.MsmContext, "_CALL_ADDS", SPLIT_ADDS)
    want = {5: 2, 1: 1}
    first, calls = counted.commit(counted.polys)        # slow, fenced
    assert calls == want[5]
    for B in order:
        got, calls = counted.commit(counted.polys[:B])
        assert calls == want[B]
        assert got == first[:B]


def test_chunk_counters_reach_the_service_and_read_as_a_share(
        knob_free, counted, served_backend):
    """`msm_commit_calls` and `msm_commit_chunks` through a started
    service, read by the benchmark's data file: 100 when every commit of
    the window was one device call, 200 when each was two."""
    be, svc = served_backend
    s0 = svc.metrics.snapshot()
    assert _chunks_pct(s0, s0) is None              # no commit ran
    whole = be.commit_many(counted.bases, counted.polys)
    s1 = svc.metrics.snapshot()
    assert s1["counters"]["msm_commit_calls"] == 1
    assert s1["counters"]["msm_commit_chunks"] == 1
    assert _chunks_pct(s0, s1) == 100.0
    knob_free.setattr(MJ.MsmContext, "_CALL_ADDS", SPLIT_ADDS)
    assert be.commit_many(counted.bases, counted.polys) == whole
    s2 = svc.metrics.snapshot()
    assert s2["counters"]["msm_commit_calls"] == 2
    assert s2["counters"]["msm_commit_chunks"] == 3
    assert _chunks_pct(s1, s2) == 200.0
    assert _chunks_pct(s0, s2) == 150.0
    # a service of the parent commit has neither counter
    assert _chunks_pct({"counters": {"msm_commit_polys": 0}},
                       {"counters": {"msm_commit_polys": 9}}) is None


# --- the compile cache's partition --------------------------------------------

def _fingerprint_here():
    cpu = ""
    try:
        with open("/proc/cpuinfo") as f:
            cpu = next((line for line in f if line.startswith("flags")), "")
    except OSError:
        pass
    return hashlib.sha256(
        f"{platform.machine()}|{cpu}".encode()).hexdigest()[:12]


def test_compile_cache_partition_fingerprint():
    fp = FJ.machine_fingerprint()
    assert fp == _fingerprint_here()
    assert len(fp) == 12 and int(fp, 16) >= 0
    # what the process configured at import lives under it (or where the
    # variable says)
    want = os.environ.get("JAX_COMPILATION_CACHE_DIR") or fp
    assert jax.config.jax_compilation_cache_dir.endswith(want)


def test_compile_cache_partition_dir(tmp_path, monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    before_min = jax.config.jax_persistent_cache_min_compile_time_secs
    try:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        got = FJ.configure_compile_cache(str(tmp_path))
        assert got == str(tmp_path / _fingerprint_here())
        assert jax.config.jax_compilation_cache_dir == got
        # the variable places the cache from outside: nothing else is set
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "env"))
        assert FJ.configure_compile_cache(str(tmp_path / "other")) \
            == str(tmp_path / "env")
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          before_min)


# --- a store with an older run's plan artifact --------------------------------

def _stale_store(root):
    """A store that still holds what a calibrating run of an older tree
    left: an ordinary blob nothing reads."""
    from distributed_plonk_tpu.store import ArtifactStore

    key = "autotune:" + FJ.machine_fingerprint()
    ArtifactStore(root).put(
        key, b'{"version": 1, "cells": {"ntt:64": {"params": {"radix": 2}}}}',
        meta={"kind": "autotune_plan"})
    return key


def _no_autotune_series(snapshot):
    return not [name for kind in snapshot.values() if isinstance(kind, dict)
                for name in kind if name.startswith("autotune")]


def _start_service(tmp_path, monkeypatch, proven):
    from distributed_plonk_tpu.service import ProofService, ServiceClient

    root = str(tmp_path / "store")
    key = _stale_store(root)
    svc = ProofService(port=0, prover_workers=1, store_dir=root).start()
    try:
        assert svc.autotune == {"source": "off"}
        with ServiceClient("127.0.0.1", svc.port) as c:
            jid = c.submit({"kind": "toy", "gates": 8, "seed": 3})["job_id"]
            assert c.wait(jid, timeout_s=180)["state"] == "done"
            assert c.result(jid)[1]
        snap = svc.metrics.snapshot()
        assert snap["counters"]["jobs_completed"] == 1
        assert _no_autotune_series(snap)
        assert key in svc.store.keys()      # untouched; the GC may evict it
    finally:
        svc.shutdown()


def _start_joined_worker(tmp_path, monkeypatch, proven):
    from distributed_plonk_tpu.prover import prove
    from distributed_plonk_tpu.runtime import worker
    from distributed_plonk_tpu.runtime.dispatcher import (Dispatcher,
                                                          RemoteBackend)
    from distributed_plonk_tpu.runtime.netconfig import NetworkConfig
    from distributed_plonk_tpu.service.metrics import Metrics

    ckt, pk, _vk, proof_host = proven
    root = str(tmp_path / "wstore")
    _stale_store(root)
    # _make_store points a later jax import's cache under the store through
    # the environment; this process has jax already, so keep it out of ours
    monkeypatch.setenv("DPT_JAX_CACHE_DIR", str(tmp_path / "jc"))
    metrics = Metrics()
    d = Dispatcher(NetworkConfig([]), metrics=metrics)
    mserver = d.enable_membership()
    t = threading.Thread(
        target=worker.serve_joined, args=(("127.0.0.1", mserver.port),),
        kwargs={"backend_name": "python", "store_dir": root}, daemon=True)
    t.start()
    try:
        for _ in range(600):
            if len(d.workers) >= 1 and len(d.tracker.usable_set()) >= 1:
                break
            threading.Event().wait(0.05)
        assert len(d.workers) == 1
        proof = prove(random.Random(1), ckt, pk,
                      RemoteBackend(d, dist_fft_min=ckt.n))
        assert proof.opening_proof == proof_host.opening_proof
        assert proof.wires_poly_comms == proof_host.wires_poly_comms
        assert _no_autotune_series(metrics.snapshot())
    finally:
        try:
            d.shutdown()
        finally:
            d.pool.shutdown(wait=False)
        t.join(timeout=15)
    assert not t.is_alive()


@pytest.mark.parametrize("start", [_start_service, _start_joined_worker],
                         ids=["service", "joined_worker"])
def test_service_and_worker_start_without_calibration(tmp_path, monkeypatch,
                                                      proven, start):
    start(tmp_path, monkeypatch, proven)
