"""MeshBackend: the full 5-round prover over a device mesh.

The mesh analog of the reference's fully-distributed v2 prover
(/root/reference/src/dispatcher2.rs:192-713): where the reference's
dispatcher drives per-FFT and per-MSM RPC fan-outs to workers and
reassembles results on the host between every phase
(dispatcher2.rs:731-787, 834-893), here the whole prover state lives
SHARDED on a jax.sharding.Mesh for all 5 rounds:

  - poly handles are (16, L) Montgomery limb arrays laid out
    P(None, "shards") over the mesh axis — each device owns a contiguous
    coefficient range, the moral equivalent of the reference's
    FftWorkload row/col ranges (src/utils.rs:3-19) but resident across
    rounds instead of re-scattered per call;
  - NTTs run as the one-program 4-step mesh NTT (ntt_mesh.MeshNttPlan:
    sharded butterfly stages + a single lax.all_to_all transpose over
    ICI), replacing the reference's 4 network phases per FFT;
  - commitments run as the range-sharded signed Pippenger
    (msm_mesh.MeshMsmContext): on-device digit extraction per shard,
    bucket planes folded across the mesh with all_gather + projective
    adds, replacing the reference's host-side partial-sum fold;
  - the remaining round math (permutation product, quotient evaluation,
    blinding, evaluation, linear combination, synthetic division)
    reuses the single-device jitted kernels on sharded inputs — XLA's
    SPMD partitioner inserts the cross-shard collectives (the log-depth
    prefix-product scans become collective-permute ladders), which is
    the TPU-native replacement for writing per-phase RPCs.

Domains too small to 2D-shard across the mesh (r or c not divisible by
the device count) fall back to the replicated single-device kernels on
the same mesh devices — correctness is placement-independent, and the
tiny-domain case is exactly where sharding has nothing to win. Which path
an NTT took is counted (`mesh_ntt_calls`, `mesh_ntt_sharded`), so a
deployment whose NTTs fell back shows as one.

prove(rng, ckt, pk, MeshBackend(mesh)) produces byte-identical proofs to
the host oracle and the single-device backend (asserted in
tests/test_mesh_backend_prove.py and, served, in
tests/test_mesh_served.py), matching the reference's invariant that the
distributed result equals the single-node one (SURVEY.md §4).

What a mesh prove records. It is SYNCHRONOUS: no async hook, no round
pipeline, no streamed round 3, one job alone on its lease. It is seen by
the tracing every prove is seen by (trace.Tracer, trace.DeviceLedger,
service Metrics), nothing of its own:
  - a round opens on `device_ledger` at `prover._feed` and closes when
    its commitments or evaluations are back on the host
    (`prover._fetched`), so a mesh job has `device/round<N>` events and
    STATUS `device` like any other. The charge is the whole mesh's wall
    time for the round, host moments inside it included;
  - a backend made alone keeps a ledger of its own. A service has ONE
    fed/unfed account: the pool worker that runs a leased unit hands the
    backend the ledger it reports its own phases to, and the service's
    Metrics, through `attach` (service/pool.py::_run_item), so the mesh's
    rounds feed the same `device_unfed_s` / `phase_clock_s` and no second
    is charged twice;
  - its programs go through field_jax.named_jit as `mesh_ntt_<mode>`,
    `mesh_msm_digits` / `_chunk` / `_merge` / `_finish` and
    `mesh_domain_tables`, so a device trace tells the all-to-all program
    from the all-gather program and both from the inherited round math;
  - counters, in the attached Metrics (none without one):
    `mesh_ntt_calls`, `mesh_ntt_sharded`, `mesh_all_to_all_bytes`,
    `mesh_msm_chunks`, `mesh_all_gather_bytes`, and the four every commit
    context counts, `msm_commit_polys` / `msm_commit_polys_preweighted` /
    `msm_commit_calls` / `msm_commit_chunks`.
"""

import functools
import os

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..constants import FR_GENERATOR, FR_LIMBS
from ..backend import field_jax as FJ
from ..backend import prover_jax as PJ
from ..backend.jax_backend import JaxBackend
from .mesh import SHARD_AXIS
from .ntt_mesh import MeshNttPlan, _split_rc
from .msm_mesh import MeshMsmContext

import jax.numpy as jnp


class MeshBackend(JaxBackend):
    """Backend whose poly handles are mesh-sharded device arrays."""

    name = "mesh"
    # memory strategy here is sharding, not streaming+packing: slicing a
    # GSPMD-sharded lane axis per quotient chunk would reshard every slice
    quotient_streamed = None
    quotient_poly_streamed = None
    # MeshMsmContext has no stacked-chunk commit path; prove_many's
    # getattr falls back to commit_many_h (and mesh placements are
    # single-job groups anyway — big proves shard, they don't batch)
    commit_batch = None
    # nor a deferred-decode one, and the inherited eval_many_async would
    # trace the evaluation outside the pallas_disabled wrapper below: the
    # prover's getattr falls back to commit_many_h / eval_many_h (a mesh
    # prove is one job on its own lease, there is no second member whose
    # host work the deferral could overlap)
    commit_many_async = None
    eval_many_async = None

    # minimum per-device coefficient count for sharding a handle: below
    # this, elementwise/scan round math runs REPLICATED on the mesh
    # (sharding 32 coefficients over 8 devices buys nothing and costs an
    # SPMD-partitioned compile of every scan kernel — measured ~45 s per
    # kernel per shape on the 8-device CPU mesh). The explicit collective
    # paths (4-step mesh NTT, range-sharded mesh MSM) are always sharded;
    # this knob only gates GSPMD propagation through the round math.
    _MIN_LOCAL = int(os.environ.get("DPT_MESH_MIN_LOCAL", "1024"))

    def __init__(self, mesh):
        # the inherited `device_ledger` is this backend's own until a
        # service attaches its account: the mesh is then THE device of
        # that account, fed while one of its (sync) rounds is open
        super().__init__()
        self.mesh = mesh
        self.d = mesh.devices.size
        self._mesh_plans = {}

    def attach(self, ledger, metrics):
        """Report to the service this backend is leased to: the mesh
        counters count in its `metrics` and rounds open on its `ledger`
        (trace.DeviceLedger; one fed/unfed account per service). A pool
        whose own backend has no ledger passes None, and this backend
        keeps its own."""
        super().attach(ledger, metrics)
        if ledger is not None and ledger is not self.device_ledger:
            self.device_ledger.close_threads()   # its own: beacon, watcher
            self.device_ledger = ledger

    # --- placement hooks ----------------------------------------------------

    def _sharding1(self, L):
        """Sharding for a (16, L) handle: coefficient-sharded when the
        length divides evenly and the local slice is worth it, replicated
        otherwise."""
        sharded = L % self.d == 0 and L // self.d >= self._MIN_LOCAL
        spec = P(None, SHARD_AXIS) if sharded else P(None)
        return NamedSharding(self.mesh, spec)

    def _lift_arr(self, arr):
        return jax.device_put(arr, self._sharding1(arr.shape[1]))

    def _lift_tab(self, arr, w, n):
        sharded = n % self.d == 0 and n // self.d >= self._MIN_LOCAL
        spec = P(None, None, SHARD_AXIS) if sharded else P(None)
        return jax.device_put(arr.reshape(FR_LIMBS, w, n),
                              NamedSharding(self.mesh, spec))

    # --- NTT: 4-step mesh kernel with small-domain fallback -----------------

    def _plan(self, n):
        if n not in self._mesh_plans:
            r, c = _split_rc(n)
            self._mesh_plans[n] = (MeshNttPlan(self.mesh, n)
                                   if r % self.d == 0 and c % self.d == 0
                                   else None)
        return self._mesh_plans[n]

    def _count_ntts(self, plan, polys):
        """`polys` NTTs are about to run: all on the 4-step plan, or all
        on the replicated fallback where the domain has none."""
        self._count("mesh_ntt_calls", polys)
        if plan is not None:
            self._count("mesh_ntt_sharded", polys)
            # the one all_to_all: each chip keeps 1/d of its n/d elements
            # (16 uint32 limbs, 64 bytes) and sends the rest
            self._count("mesh_all_to_all_bytes",
                        polys * 64 * plan.n * (self.d - 1) // self.d)

    def _kernel(self, domain, h, inverse, coset):
        plan = self._plan(domain.size)
        self._count_ntts(plan, 1)
        if plan is None:
            return super()._kernel(domain, h, inverse, coset)
        if h.shape[1] < domain.size:
            h = jnp.pad(h, ((0, 0), (0, domain.size - h.shape[1])))
        assert h.shape[1] == domain.size
        return plan.kernel(inverse=inverse, coset=coset, boundary="mont")(h)

    def _kernel_many(self, domain, hs, inverse, coset):
        plan = self._plan(domain.size)
        self._count_ntts(plan, len(hs))
        if plan is None:
            return super()._kernel_many(domain, hs, inverse, coset)
        # one 4-step mesh program per poly: at mesh-worthy sizes the
        # single-poly program already fills the devices, and a fixed
        # shape set (one per mode) keeps compiles bounded
        fn = plan.kernel(inverse=inverse, coset=coset, boundary="mont")
        out = []
        for h in hs:
            if h.shape[1] < domain.size:
                h = jnp.pad(h, ((0, 0), (0, domain.size - h.shape[1])))
            out.append(fn(h))
        return out

    # --- MSM: range-sharded signed Pippenger --------------------------------

    def _make_msm_ctx(self, bases):
        return MeshMsmContext(self.mesh, bases, count=self._count)

    # --- quotient tables pinned to the mesh ---------------------------------

    def _domain_tables(self, m, n, group_gen):
        # the parent's domain_tables_jit has no array inputs, so it would
        # compute on the process-default device — possibly a different
        # platform than the mesh. Pin computation + placement to the mesh
        # via out_shardings.
        key = (m, n)
        with self._cache_lock:
            hit = self._domain_tabs.get(key)
        if hit is None:
            sh = self._sharding1(m)
            fn = FJ.named_jit("mesh_domain_tables", PJ.domain_tables,
                              static_argnums=(0, 1, 2, 3),
                              out_shardings={"ep": sh, "zh_inv": sh,
                                             "shifted_inv": sh})
            hit = fn(m, n, FR_GENERATOR, group_gen)
            with self._cache_lock:
                self._domain_tabs[key] = hit
        return hit


def _no_pallas(name):
    """Wrap an inherited round-math method in field_jax.pallas_disabled():
    these run as GSPMD-auto-sharded jit programs on the mesh, where a
    pallas_call (no SPMD partitioning rule) must not appear. The explicit
    shard_map paths — mesh NTT and mesh MSM, the hot 95% — keep the Pallas
    multiplier (per-device local)."""
    parent = getattr(JaxBackend, name)

    @functools.wraps(parent)
    def wrapped(self, *args, **kwargs):
        with FJ.pallas_disabled():
            return parent(self, *args, **kwargs)

    return wrapped


for _name in ("blind", "eval_h", "eval_many_h", "lin_comb_h", "synth_div_h",
              "perm_product", "quotient", "degree_is", "split",
              "dump_h", "load_h"):
    setattr(MeshBackend, _name, _no_pallas(_name))
del _name
