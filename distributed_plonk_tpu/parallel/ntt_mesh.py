"""Sharded 4-step NTT over a device mesh: one `all_to_all`, no host hops.

TPU-native replacement for the reference's distributed FFT protocol
(driver /root/reference/src/dispatcher2.rs:731-787; worker stage kernels
src/worker.rs:66-115; peer all-to-all src/worker.rs:293-344,412-438).
Where the reference pays 4 network phases per FFT through the dispatcher,
here the whole decomposition is ONE compiled program: the row/column FFT
stages run sharded under shard_map and the inter-stage transpose is a
single `jax.lax.all_to_all` over the mesh axis (ICI on real hardware).

Math (Bailey/4-step; the reference's spec is src/playground.rs:21-80,
derived here from first principles): for N = r*c, w = w_N,

  X[k1 + r*k2] = sum_{j2<c} w^{j2 k1} w_c^{j2 k2}
                   [ sum_{j1<r} x[j2 + c*j1] w_r^{j1 k1} ]

  1. A[j2, j1] = x[j2 + c*j1]; r-point NTT per row j2   (sharded over j2)
  2. A[j2, k1] *= w^{j2*k1}                             (elementwise)
  3. transpose -> B[k1, j2]                             (all_to_all)
  4. c-point NTT per row k1                             (sharded over k1)
  output: X[k1 + r*k2] = B_hat[k1, k2].

Coset and inverse variants fold their scalings into the same program:
forward-coset pre-scales the input by g^j, inverse post-scales the output
by 1/N (plain) or g^-j/N (coset), matching poly.py bit-for-bit.
"""

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..constants import R_MOD, FR_GENERATOR, FR_LIMBS
from ..fields import fr_inv, fr_root_of_unity
from ..backend import field_jax as FJ
from ..backend.field_jax import FR
from ..backend import ntt_jax
from ..backend.limbs import ints_to_limbs, limbs_to_ints
from .mesh import SHARD_AXIS, pallas_guard


def _split_rc(n):
    """n = r*c with r = 2^floor(log2(n)/2) (the reference's split,
    /root/reference/src/worker.rs:142-155)."""
    log_n = n.bit_length() - 1
    r = 1 << (log_n // 2)
    return r, n // r


class MeshNttPlan:
    """Tables + cached compiled programs for one (mesh, N) pair."""

    def __init__(self, mesh, n):
        assert n & (n - 1) == 0
        self.mesh = mesh
        self.n = n
        self.r, self.c = _split_rc(n)
        d = mesh.devices.size
        assert self.r % d == 0 and self.c % d == 0, (
            f"mesh size {d} must divide both r={self.r} and c={self.c}")
        self.plan_r = ntt_jax.get_plan(self.r)
        self.plan_c = ntt_jax.get_plan(self.c)
        self._fns = {}

        w = fr_root_of_unity(n)
        w_inv = fr_inv(w) if n > 1 else 1
        g = FR_GENERATOR
        g_inv = fr_inv(g)
        n_inv = fr_inv(n % R_MOD)
        r, c = self.r, self.c

        # mid twiddles: T[j2, k1] = w^{±j2*k1}, built incrementally per row
        def mid_table(base):
            rows = []
            row_base = 1
            for j2 in range(c):
                rows.extend(ntt_jax._powers(row_base, r))
                row_base = row_base * base % R_MOD
            return ntt_jax._mont_table(rows)  # (16, c*r) row-major [j2, k1]

        self.mid_fwd = mid_table(w).reshape(FR_LIMBS, c, r)
        self.mid_inv = mid_table(w_inv).reshape(FR_LIMBS, c, r)

        # forward-coset pre-scale at A[j2, j1]: g^{j2 + c*j1}
        pre = []
        for j2 in range(c):
            pre.extend(ntt_jax._powers(pow(g, c, R_MOD), r, start=pow(g, j2, R_MOD)))
        self.pre_coset = ntt_jax._mont_table(pre).reshape(FR_LIMBS, c, r)

        # inverse post-scale at out[k1, k2]: n_inv * g^-(k1 + r*k2)
        post = []
        for k1 in range(r):
            post.extend(ntt_jax._powers(pow(g_inv, r, R_MOD), c,
                                        start=n_inv * pow(g_inv, k1, R_MOD)))
        self.post_coset = ntt_jax._mont_table(post).reshape(FR_LIMBS, r, c)
        self.post_plain = ntt_jax._mont_table([n_inv])  # (16, 1)

    def kernel(self, inverse=False, coset=False, boundary="mont"):
        """Compiled (16, n) -> (16, n) mesh program for one mode (at the
        active DPT_NTT_RADIX — part of the cache key, like the
        single-device kernels)."""
        key = (inverse, coset, boundary, ntt_jax._active_radix())
        if key in self._fns:
            fn, consts = self._fns[key]
            return lambda v: fn(v, consts)
        # can the TRACED body contain a pallas_call — the fused
        # multiplier the stage cores dispatch for wide shapes on a TPU?
        # Resolve under the same guard the trace runs under
        # (pallas_guard disables it for a non-TPU mesh), so check_vma
        # below is only relaxed for programs that can genuinely contain
        # one
        with pallas_guard(self.mesh):
            pallas_active = FJ.pallas_mul_possible()

        n, r, c = self.n, self.r, self.c
        d = self.mesh.devices.size
        plain = boundary == "plain"

        # host numpy constants: jit moves them onto the mesh's devices (which
        # may not be the process default backend, e.g. cpu mesh + tpu default)
        # — the row/column stage tables come from the SAME shared stage core
        # the single-device kernels run (ntt_jax.run_stages), so the active
        # radix (DPT_NTT_RADIX) covers the sharded path too
        consts = {
            "core_r": self.plan_r.core_consts(inverse),
            "core_c": self.plan_c.core_consts(inverse),
            "mid": self.mid_inv if inverse else self.mid_fwd,
        }
        if coset and not inverse:
            consts["pre"] = self.pre_coset
        if inverse:
            consts["post"] = (self.post_coset if coset else self.post_plain)

        row_spec = P(None, SHARD_AXIS, None)
        # every stage-core table is replicated (O(n) twiddles/exponents,
        # no per-shard content), whatever the radix's table set is
        const_specs = {
            "core_r": {k: P(*([None] * np.ndim(a)))
                       for k, a in consts["core_r"].items()},
            "core_c": {k: P(*([None] * np.ndim(a)))
                       for k, a in consts["core_c"].items()},
            "mid": row_spec,
        }
        if "pre" in consts:
            const_specs["pre"] = row_spec
        if "post" in consts:
            const_specs["post"] = (row_spec if consts["post"].ndim == 3
                                   else P(None, None))

        def sharded_body(a, cs):
            # a: (16, c/d, r) local rows of A
            if "pre" in cs:
                a = FJ.mont_mul(FR, a, cs["pre"])
            v = ntt_jax.run_stages(a, cs["core_r"])
            v = FJ.mont_mul(FR, v, cs["mid"])
            # the ONE inter-stage transpose: (16, c/d, r) -> (16, c, r/d)
            v = lax.all_to_all(v, SHARD_AXIS, split_axis=2, concat_axis=1,
                               tiled=True)
            v = v.swapaxes(1, 2)  # local transpose -> (16, r/d, c)
            v = ntt_jax.run_stages(v, cs["core_c"])
            if "post" in cs:
                post = cs["post"]
                if post.ndim == 2:  # plain 1/n scalar, broadcast symbolically
                    post = jnp.broadcast_to(post[:, :, None], v.shape)
                v = FJ.mont_mul(FR, v, post)
            return v

        # a pallas_call has no shard_map replication rule: disable the
        # checker ONLY when the traced body will contain one — every
        # XLA-core program (including pallas-requested-but-guarded-off
        # on a non-TPU mesh) keeps the full replication check
        smapped = jax.shard_map(
            sharded_body, mesh=self.mesh,
            in_specs=(row_spec, const_specs), out_specs=row_spec,
            check_vma=not pallas_active)

        lane_sh = jax.sharding.NamedSharding(self.mesh, P(None, SHARD_AXIS))

        def fn(x, cs):
            # pallas only if the MESH devices are TPUs (a cpu mesh can be
            # traced in a tpu-default process — mesh.pallas_guard); the
            # plain-boundary conversions run OUTSIDE shard_map at the
            # GSPMD level, where a pallas_call must never appear even on
            # a real TPU mesh (same invariant as MeshBackend round math)
            with pallas_guard(self.mesh):
                # x: (16, n) global
                if plain:
                    with FJ.pallas_disabled():
                        x = FJ.to_mont(FR, x)
                a = x.reshape(FR_LIMBS, r, c).swapaxes(1, 2)  # A[j2, j1]
                out = smapped(a, cs)                       # (16, r, c) = X[k1, k2]
                x = out.swapaxes(1, 2).reshape(FR_LIMBS, n)  # X[k1 + r*k2]
                # PIN the output to the lane-sharded layout: the swapaxes+
                # reshape leaves the sharding unconstrained and GSPMD was
                # observed to REPLICATE the result across the mesh (the
                # mesh_prove_2p15 residency check measured 25 replicated
                # coset planes, 463 MiB/device vs the 109 MiB plan). The
                # constraint costs one relayout collective; round math
                # downstream then stays O(m/D) per device.
                if not plain:
                    x = jax.lax.with_sharding_constraint(x, lane_sh)
                if plain:
                    with FJ.pallas_disabled():
                        x = FJ.from_mont(FR, x)
                return x

        # named after the mode (mesh_ntt_inv_coset, mesh_ntt_fwd_plain,
        # ...), as ntt_jax names its fused programs: a device trace tells
        # the all-to-all program from the MSM's all-gather program
        name = "_".join(["mesh_ntt", "inv" if inverse else "fwd"]
                        + ["coset"] * coset + ["plain"] * plain)
        fn = FJ.named_jit(name, fn)
        self._fns[key] = (fn, consts)
        return lambda v: fn(v, consts)

    def run_ints(self, values, inverse=False, coset=False):
        assert len(values) <= self.n
        padded = list(values) + [0] * (self.n - len(values))
        v = ints_to_limbs(padded, FR_LIMBS)  # host numpy; jit places on mesh
        out = self.kernel(inverse, coset, boundary="plain")(v)
        if jax.process_count() > 1:
            # multi-controller: the result is sharded across hosts; gather
            # it to a replicated layout (DCN all-gather) so every process
            # can read the full vector
            rep = jax.sharding.NamedSharding(self.mesh, P(None, None))
            out = FJ.named_jit("mesh_ntt_gather", lambda x: x,
                               out_shardings=rep)(out)
        return limbs_to_ints(np.asarray(out))
