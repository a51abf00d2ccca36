"""Sharded variable-base MSM over a device mesh.

TPU-native replacement for the reference's distributed MSM
(/root/reference/src/dispatcher2.rs:834-893 + src/worker.rs:159-185):
bases and scalars are range-sharded across the mesh (the MsmWorkload
convention, with the v1 full-coverage semantics — SURVEY.md §2.3.1),
every device runs the sort-free Pippenger bucket pipeline on its slice,
and the per-device BUCKET PLANES fold ON DEVICE via all_gather + the same
scanned fold body the group fold uses — replacing the reference's
host-side sum-reduce of partial totals (dispatcher2.rs:888-890). (G1
addition is not a ring sum, so `psum` does not apply; the all_gather+fold
is the collective equivalent.) A single finish machine then turns the
globally folded buckets into the result.

This is the full prover commitment surface, not just a host-scalar demo:
like the single-device MsmContext, the mesh context

  - runs the SIGNED radix-256 batched pipeline (128 buckets, sign folded
    into y) whenever the per-device slice is large enough, falling back
    to the unsigned small-window scan only for tiny slices where the
    signed recode has no overflow margin;
  - accepts (16, L) MONTGOMERY poly handles and extracts digits on
    device (`msm_mont_limbs_many`), so a mesh-backed prove commits
    device-resident polynomials without a host round-trip;
  - batches B polynomials through shared scan steps and chunks the
    point range so one device execution stays under the per-call budget
    (see MsmContext's chunking note).

Data layout: points live as (24, D, local) arrays sharded on the device
axis — device d owns the contiguous base range [d*local, (d+1)*local) —
so chunk slices along the LOCAL axis never reshard.

What a context holds on the device besides: where msm_jax's
`use_window_table` says so (the signed pipeline, a table within the byte
budget), the window table of its points, 2^(c*w) * P_i for each of the W
windows (msm_jax.window_table), built on ONE device over the whole padded
key before anything is sharded and then placed as the points are, (D,
local/8, 8, 24*W) sharded on the device axis: W * 192 B a point, 101 MB
over the four chips for the 16,448 padded points of a 2^14 key at c = 8.
A chip then adds its windows' planes before the all_gather, which moves
B x 128 bucket rows a chip and not B x 32 x 128, and the finish on the
one chip is the bucket running sum alone (msm_jax.finish_preweighted:
129 steps on B lanes where the ladder path takes 382 on 32 x B).
"""

import os
from functools import partial

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..constants import FQ_LIMBS
from ..backend import msm_jax
from ..backend import curve_jax as CJ
from ..backend import field_jax as FJ
from ..backend.msm_jax import (
    SCALAR_BITS, DeviceCommitKey, window_bits, _group_size_batch,
    bucket_planes_batch, bucket_planes_batch_signed, fold_planes,
    finish_batch, finish_preweighted, digits_of_scalars,
    signed_digits_of_scalars, digits_from_mont, signed_digits_from_mont,
    points_to_device, use_window_table, window_table, TABLE_TILE,
    _proj_limbs_to_affine,
)
from .mesh import SHARD_AXIS, pallas_guard


class MeshMsmContext:
    """Device-mesh-resident base set: every device holds its contiguous
    1/D range of the SRS (the v1 init semantics the rebuild standardizes
    on, /root/reference/src/dispatcher.rs:572-578)."""

    # per-call lane-add budget PER DEVICE (all devices run concurrently);
    # same knob semantics as MsmContext's chunking
    _CALL_ADDS = int(os.environ.get("DPT_MSM_CALL_ADDS", "8000000"))

    def __init__(self, mesh, bases, count=None):
        # count(name, by): where `mesh_msm_chunks`, `mesh_all_gather_bytes`,
        # `msm_commit_polys`, `msm_commit_polys_preweighted`,
        # `msm_commit_calls` (one an `_exec`) and `msm_commit_chunks` (its
        # bucket-scan device calls) go (MeshBackend._count), if anywhere
        self._count = count or (lambda name, by=1: None)
        self.mesh = mesh
        self.d = d = mesh.devices.size
        n = len(bases)
        self.n = n
        # pad so the local slice is even-sized and groupable; identity
        # padding columns never change the sum
        self.padded_n = n + (-n) % (16 * d)
        self.local_n = self.padded_n // d
        # window choice from the PER-DEVICE slice (what each device's
        # bucket pipeline actually sees): signed radix-256 once the slice
        # is big enough, like MsmContext.c_batch
        self.c = 8 if self.local_n >= 256 else window_bits(self.local_n)
        self.signed = self.c == 8
        self.windows = SCALAR_BITS // self.c

        pad = self.padded_n - n
        if isinstance(bases, DeviceCommitKey):
            # device-built SRS (Jacobian, arbitrary Z): normalize once via
            # batched inversion on whatever device it lives on, then
            # reshard onto the mesh
            point = bases.point
            if pad:
                point = tuple(jnp.pad(p, ((0, 0), (0, pad))) for p in point)
            ax, ay, ainf = CJ.batch_to_affine(point)
        else:
            ax, ay, ainf = points_to_device(bases, pad)  # host numpy

        pt_sh = NamedSharding(mesh, P(None, SHARD_AXIS, None))
        inf_sh = NamedSharding(mesh, P(SHARD_AXIS, None))
        resh = (np.reshape if isinstance(ax, np.ndarray) else jnp.reshape)
        self.point = (
            jax.device_put(resh(ax, (FQ_LIMBS, d, self.local_n)), pt_sh),
            jax.device_put(resh(ay, (FQ_LIMBS, d, self.local_n)), pt_sh),
            jax.device_put(resh(ainf, (d, self.local_n)), inf_sh),
        )

        # the window table: built over the whole key on one device, then
        # dealt like the points (the window axis is in the rows, unsharded)
        self.table = None
        if use_window_table(self.signed, msm_jax._kernel_mode(),
                            self.padded_n, self.c):
            tab_sh = NamedSharding(mesh, P(SHARD_AXIS, None, None, None))
            with jax.default_device(self._local_device()):
                table = window_table(ax, ay, ainf, self.c)
            self.table = tuple(
                jax.device_put(
                    t.reshape(d, self.local_n // TABLE_TILE, TABLE_TILE, -1),
                    tab_sh)
                for t in table)

        self._digits_sh = NamedSharding(mesh, P(None, None, SHARD_AXIS, None))
        self._digits_fns = {}
        self._chunk_fns = {}
        self._finish_fns = {}

        # pallas_disabled at TRACE time: this jit runs on mesh-replicated
        # operands under the GSPMD partitioner, where a pallas_call (no
        # SPMD partitioning rule) would fail to partition or silently
        # gather — same invariant as MeshBackend's round math. The
        # explicit shard_map chunk bodies keep the kernel (per-device
        # local shapes).
        def _merge(a, b):
            with FJ.pallas_disabled():
                return CJ.proj_add(tuple(a), tuple(b))

        # program names (field_jax.named_jit): mesh_msm_digits, _chunk (the
        # shard_map'd scan with the all_gather + fold), _merge, _finish
        self._merge_fn = FJ.named_jit("mesh_msm_merge", _merge)

    def _local_device(self):
        """The mesh device this process does one-device work on (the table
        build, the finish tail): its own first, under multi-controller."""
        return next((dv for dv in self.mesh.devices.ravel()
                     if dv.process_index == jax.process_index()),
                    self.mesh.devices.ravel()[0])

    # --- digit extraction ----------------------------------------------------

    def _digits_np(self, scalars):
        """Host ints -> (W, D, local) numpy digits."""
        if self.signed:
            dg = signed_digits_of_scalars(scalars, self.padded_n)
        else:
            dg = digits_of_scalars(scalars, self.padded_n, self.c)
        return dg.reshape(self.windows, self.d, self.local_n)

    def _digits_of_handles(self, hs):
        """B Montgomery (16, L) handles -> (B, W, D, local) device digits,
        extracted on device (no host round-trip before a commitment)."""
        key = tuple(h.shape[1] for h in hs)
        fn = self._digits_fns.get(key)
        if fn is None:
            W, d, loc = self.windows, self.d, self.local_n

            def build(handles):
                # pallas_disabled: handles arrive mesh-sharded and this
                # jit is GSPMD-partitioned (not shard_map'd) — a traced
                # pallas mont_mul here would break on a real TPU mesh
                with FJ.pallas_disabled():
                    outs = []
                    for h in handles:
                        if self.signed:
                            dg = signed_digits_from_mont(h, self.padded_n)
                        else:
                            dg = digits_from_mont(h, self.c, self.padded_n)
                        outs.append(dg.reshape(W, d, loc))
                    return jnp.stack(outs)

            fn = FJ.named_jit("mesh_msm_digits", build,
                              out_shardings=self._digits_sh)
            self._digits_fns[key] = fn
        return fn(list(hs))

    # --- sharded bucket accumulation ----------------------------------------

    def _chunk_fn(self, jc, group, B):
        """shard_map'd program: per-device bucket planes on a jc-wide local
        chunk, then cross-device all_gather + fold -> replicated planes."""
        key = (jc, group, B)
        if key not in self._chunk_fns:
            table = self.table is not None
            # a table was built for the XLA scan and is served by it
            scan = (partial(bucket_planes_batch_signed, preweighted=True,
                            kernel="xla") if table else
                    bucket_planes_batch_signed if self.signed
                    else bucket_planes_batch)
            # the bases' device axis: leading in the table, second in the
            # points
            base_spec, local = ((P(SHARD_AXIS, None, None, None),
                                 lambda a: a[0]) if table else
                                (P(None, SHARD_AXIS, None),
                                 lambda a: a[:, 0]))

            def body(ax, ay, ainf, digits):
                # pallas only if the mesh devices are TPUs (mesh.pallas_guard)
                with pallas_guard(self.mesh):
                    # local block: ax/ay (24, 1, jc) or the table's
                    # (1, jc/8, 8, 24*W), ainf (1, jc), digits (B, W, 1, jc)
                    acc = scan(local(ax), local(ay), ainf[0],
                               digits[:, :, 0], group=group)
                    # fold bucket planes across the mesh on device (the
                    # reference folds partial totals on the dispatcher host,
                    # dispatcher2.rs:888-890); the fold body is identical to
                    # the group fold's -> compiled once
                    gathered = tuple(lax.all_gather(b, SHARD_AXIS) for b in acc)
                    return fold_planes(*gathered)

            # check_vma=False: the all_gather+fold makes the outputs
            # replicated in value, which the varying-axes checker cannot
            # infer statically
            self._chunk_fns[key] = FJ.named_jit(
                "mesh_msm_chunk", jax.shard_map(
                    body, mesh=self.mesh,
                    in_specs=(base_spec, base_spec, P(SHARD_AXIS, None),
                              P(None, None, SHARD_AXIS, None)),
                    out_specs=(P(None, None, None),) * 3, check_vma=False))
        return self._chunk_fns[key]

    def _finish_fn(self, batch):
        if batch not in self._finish_fns:
            def _finish(ax, ay, az):
                with pallas_guard(self.mesh):
                    if self.table is not None:
                        return finish_preweighted(ax, ay, az)
                    return finish_batch(ax, ay, az, batch=batch,
                                        signed=self.signed)
            self._finish_fns[batch] = FJ.named_jit("mesh_msm_finish",
                                                   _finish)
        return self._finish_fns[batch]

    def _exec(self, digits):
        """digits (B, W, D, local) -> B affine points (host ints/None)."""
        B = digits.shape[0]
        W = self.windows
        ax, ay, ainf = self.point
        self._count("msm_commit_polys", B)
        self._count("msm_commit_calls")
        if self.table is not None:
            self._count("msm_commit_polys_preweighted", B)
        chunk = max(16, (self._CALL_ADDS // (B * W)) & ~15)
        acc = None
        j0 = 0
        while j0 < self.local_n:
            jc = min(chunk, self.local_n - j0)
            g = _group_size_batch(jc, B, self.c, signed=self.signed)
            fn = self._chunk_fn(jc, g, B)
            if self.table is not None:
                t0, t1 = j0 // TABLE_TILE, (j0 + jc) // TABLE_TILE
                bases = tuple(t if jc == self.local_n else t[:, t0:t1]
                              for t in self.table)
            else:
                bases = ax[:, :, j0:j0 + jc], ay[:, :, j0:j0 + jc]
            part = fn(*bases, ainf[:, j0:j0 + jc],
                      digits[:, :, :, j0:j0 + jc])
            self._count("mesh_msm_chunks")
            self._count("msm_commit_chunks")
            # the all_gather: each of d chips takes the other d-1 chips'
            # bucket planes, which have the shape of the folded `part`
            self._count("mesh_all_gather_bytes",
                        self.d * (self.d - 1) * sum(p.nbytes for p in part))
            if acc is None:
                acc = part
            else:
                acc = tuple(self._merge_fn(acc, part))
            j0 += jc
        # commit the replicated fold result to ONE device before the
        # O(W * buckets) finish tail: otherwise the finish jit inherits the
        # D-way replicated sharding and every device redundantly executes
        # the whole tail. Under multi-controller the global array is not
        # fully addressable, so each process pulls its LOCAL replica
        # (identical by construction).
        dev = self._local_device()
        acc = tuple(jax.device_put(a.addressable_data(0), dev) for a in acc)
        tx, ty, tz = self._finish_fn(B)(*acc)
        tx, ty, tz = np.asarray(tx), np.asarray(ty), np.asarray(tz)
        return [_proj_limbs_to_affine(tx[:, j], ty[:, j], tz[:, j])
                for j in range(B)]

    # --- public surface (mirrors MsmContext) --------------------------------

    def msm(self, scalars):
        """Σ scalars_i * bases_i -> affine point (host ints) or None."""
        return self.msm_many([scalars])[0]

    def msm_many(self, scalar_lists):
        """B MSMs over host int scalar lists in one batched mesh launch."""
        for s in scalar_lists:
            assert len(s) <= self.n
        digits = np.stack([self._digits_np(s) for s in scalar_lists])
        return self._exec(jax.device_put(digits, self._digits_sh))

    def msm_mont_limbs(self, h):
        """Commit a (16, L <= padded_n) Montgomery coefficient handle."""
        return self.msm_mont_limbs_many([h])[0]

    # like MsmContext: fixed chunk width keeps the compiled batch-shape set
    # small across prover rounds (8, then the 5/2-size residuals)
    _BATCH_CHUNK = int(os.environ.get("DPT_MSM_BATCH", "8"))

    def msm_mont_limbs_many(self, hs):
        """Commit B Montgomery coefficient handles; digit extraction and
        bucket accumulation run sharded on the mesh, only the resulting
        group elements return to the host (for the transcript)."""
        for h in hs:
            assert h.shape[1] <= self.padded_n, (h.shape, self.padded_n)
        out = []
        for i in range(0, len(hs), self._BATCH_CHUNK):
            digits = self._digits_of_handles(hs[i:i + self._BATCH_CHUNK])
            out.extend(self._exec(digits))
        return out
