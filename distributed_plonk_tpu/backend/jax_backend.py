"""Single-device JAX backend: the full prover dataflow device-resident.

The device analog of one reference worker's compute surface
(/root/reference/src/worker.rs:125-409) — but where the reference only ever
offloaded NTT + MSM and kept every intermediate polynomial on the
dispatcher host, here poly handles are (16, L) Montgomery limb arrays that
STAY on device across all 5 rounds (the round3*/round5* offload the
reference declared and never built, src/hello_world.capnp:26-44): NTTs,
commitments (with on-device digit extraction), the permutation product,
quotient evaluation, blinding, evaluation, linear combination and the
opening divisions all run as jitted kernels. Host transfers during a prove
are the witness upload (once), commitment results, and transcript scalars.

Heavy state (SRS bases as Montgomery limb arrays, NTT plans/twiddles,
per-circuit witness/permutation tables, per-domain quotient tables) is
cached device-resident across calls, like the worker's `State`
(/root/reference/src/worker.rs:42-59).
"""

import os

import numpy as np
import jax
import jax.numpy as jnp

from ..constants import R_MOD, FR_GENERATOR, FR_LIMBS
from ..circuit import NUM_WIRE_TYPES
from . import ntt_jax
from . import prover_jax as PJ
from . import field_jax as FJ
from .field_jax import FR
from .msm_jax import MsmContext
from .limbs import ints_to_limbs

# Round-3 pointwise fusion (DPT_R3_FUSE, default on): fold the gate /
# sigma quotient products into the selector/sigma coset-FFT programs as
# epilogues, and the final quotient combine into the coset iNTT as a
# prologue (NttPlan.kernel_fused) — the quotient pipeline loses its
# standalone O(m) passes. 0 restores the separate jitted step programs
# (the value-identical reference path).
_R3_FUSE = os.environ.get("DPT_R3_FUSE", "1") != "0"

# Bit-reversal deferral for the FUSED round 3 (DPT_R3_BITREV, default on;
# only meaningful under DPT_R3_FUSE): every forward coset-FFT launch in
# the quotient pipeline emits in constant-geometry (bit-reversed) order
# (NttPlan defer_perm) and the accumulator planes stay bit-reversed all
# the way to the combine — valid because every fold is pointwise, so it
# holds in any order the operands share (the z_next roll and the domain
# tables are re-indexed once, per-plan). The ONE place the order returns
# to natural is the consuming coset-iNTT's input gather (kernel_fused
# input_perm), fused into that program's first stage reads: ~26
# standalone O(m) bit-reversal gathers per round 3 collapse into 1.
# 0 restores per-launch output permutation (bit-identical either way).
_R3_BITREV = os.environ.get("DPT_R3_BITREV", "1") != "0"


class _DevicePending:
    """Dispatched-but-unforced device result (commit_many_async /
    eval_many_async): jax has already enqueued the launches; force() pays
    the device→host transfer. The prover's pipeline driver forces only at
    the owning member's host-finalize."""

    __slots__ = ("force", "_arrays")

    def __init__(self, force, arrays=()):
        self.force = force
        self._arrays = arrays

    def arrays(self):
        """The device arrays whose readiness is this result's completion
        (what the device ledger's watcher blocks on)."""
        return self._arrays


class JaxBackend:
    """Backend over single-device jitted kernels.

    Poly handles: (16, L) uint32 Montgomery limb jnp arrays. The plain
    int-list compute API (fft/msm/...) is kept for the worker daemon and
    fleet dispatcher surface."""

    name = "jax"

    def __init__(self):
        import threading
        self._msm_ctxs = {}
        self._circuit_tabs = {}
        self._pk_polys = {}
        self._domain_tabs = {}
        self._domain_tabs_packed = {}
        # guards check-then-insert on the capped caches: the worker daemon
        # runs kernels outside its state lock, so two connections can hit a
        # backend cache concurrently (an eviction between check and read
        # would KeyError)
        self._cache_lock = threading.Lock()
        # host-boundary transfer counters (asserted on in tests: mid-prove
        # traffic must be scalars only). `drains` counts the round-3
        # queue-bounding fences (1-element fetches) separately from the
        # protocol `lowers`.
        self.lifts = 0
        self.lowers = 0
        self.drains = 0
        # completion stamps and the fed/unfed account of THE device: one
        # ledger, shared by every pool worker that proves on this backend
        self.device_ledger = self._make_ledger()
        # where the kernels' counters go (`_count`): the Metrics of the
        # service this backend proves for, once a pool worker has attached
        # it; none without one
        self.metrics = None

    def attach(self, ledger, metrics):
        """Report to the service this backend proves for: the kernels'
        counters count in its `metrics` (a pool worker calls this with its
        own backend's ledger, which changes nothing else here; the mesh
        backend also takes the ledger)."""
        self.metrics = metrics

    def _count(self, name, by=1):
        if self.metrics is not None:
            self.metrics.inc(name, by)

    def _make_ledger(self):
        from ..trace import DeviceLedger
        return DeviceLedger()

    def device_info(self):
        """Platform, device kind and device count as jax reports them for
        this process (the service's listening line and the MFU gauges
        read it)."""
        devs = jax.devices()
        return {"platform": devs[0].platform,
                "device_kind": devs[0].device_kind, "devices": len(devs)}

    # --- plain int-list compute API (worker daemon / dispatcher surface) ----

    def fft(self, domain, values):
        return ntt_jax.get_plan(domain.size).run_ints(values)

    def ifft(self, domain, values):
        return ntt_jax.get_plan(domain.size).run_ints(values, inverse=True)

    def coset_fft(self, domain, values):
        return ntt_jax.get_plan(domain.size).run_ints(values, coset=True)

    def coset_ifft(self, domain, values):
        return ntt_jax.get_plan(domain.size).run_ints(values, inverse=True, coset=True)

    def _make_msm_ctx(self, bases):
        """MSM context factory hook (the mesh backend overrides this to
        build a mesh-sharded context; the caching in _ctx is shared).
        `count=`: the context says how many polynomials it committed, how
        many of them from its window table, and in how many calls and
        bucket-scan device calls."""
        return MsmContext(bases, count=self._count)

    def _ctx(self, bases):
        # keyed by identity; the bases reference is retained so the id can
        # never be recycled by a different object while cached. Capped like
        # the other device caches: an uncapped map keyed by commit keys
        # retains every SRS's Jacobian arrays forever (HBM leak in a
        # long-lived worker process serving many circuits).
        # Double-checked: the EXPENSIVE build (MsmContext runs a batched
        # affine normalization at SRS scale) happens outside the lock so
        # concurrent cache hits never wait on it; a lost race costs one
        # duplicate build, not correctness.
        key = id(bases)
        with self._cache_lock:
            hit = self._msm_ctxs.get(key)
        if hit is None:
            built = self._make_msm_ctx(bases)
            with self._cache_lock:
                if key not in self._msm_ctxs:
                    self._cache_put(self._msm_ctxs, key, (bases, built))
                hit = self._msm_ctxs[key]
        return hit[1]

    def msm(self, bases, scalars):
        """Variable-base MSM; scalars zero-padded to |bases| on device."""
        return self._ctx(bases).msm(scalars)

    def commit(self, ck, coeffs):
        return self.msm(ck, coeffs)

    def commit_many(self, ck, coeff_lists):
        """B commitments over the same key in one batched launch."""
        return self._ctx(ck).msm_many(coeff_lists)

    # --- poly-handle protocol: handles are (16, L) Montgomery arrays --------

    def _lift_arr(self, arr):
        """Host (16, K) limb array -> device array. Placement hook: the
        single-device backend uses the default device; the mesh backend
        overrides this to device_put with a sharded layout."""
        return jnp.asarray(arr)

    def lift(self, values):
        self.lifts += 1
        return self._lift_arr(PJ.lift(values))

    def lift_many(self, value_lists):
        """Upload B equal-length int lists as ONE transfer -> B handles
        (preprocess lifts its 18 selector/sigma columns this way: one
        host-to-device transfer instead of 18)."""
        n = len(value_lists[0])
        assert all(len(v) == n for v in value_lists)
        flat = [x for vs in value_lists for x in vs]
        self.lifts += 1
        h = self._lift_arr(PJ.lift(flat))
        return [h[:, i * n:(i + 1) * n] for i in range(len(value_lists))]

    def lower(self, h):
        self.lowers += 1
        return PJ.lower(h)

    # checkpoint dump/load (checkpoint.py): CANONICAL (16, L) uint32 limb
    # arrays — the same layout limbs.ints_to_limbs produces — so snapshots
    # are portable across backends. The int round-trip is skipped: one
    # device from_mont/to_mont pass instead of 2^20 Python conversions.
    def dump_h(self, h):
        return np.asarray(PJ._from_mont_jit(h)).astype(np.uint32, copy=False)

    def load_h(self, arr):
        return PJ._to_mont_jit(self._lift_arr(np.asarray(arr, np.uint32)))

    def wire_values(self, circuit):
        tabs = self._circuit_tables(circuit)
        return [tabs["wires"][:, i] for i in range(NUM_WIRE_TYPES)]

    _CACHE_CAP = 4  # bound the per-pk/per-circuit device caches

    @staticmethod
    def _cache_put(cache, key, value):
        if len(cache) >= JaxBackend._CACHE_CAP:
            cache.pop(next(iter(cache)))
        cache[key] = value

    def pk_polys(self, pk):
        key = id(pk)
        with self._cache_lock:
            hit = self._pk_polys.get(key)
        if hit is None:
            self.lifts += 1  # O(n) upload: proving-key polys, once per pk
            sel = [self._lift_arr(PJ.lift(s)) for s in pk.selectors]
            sig = [self._lift_arr(PJ.lift(s)) for s in pk.sigmas]
            with self._cache_lock:
                if key not in self._pk_polys:
                    self._cache_put(self._pk_polys, key, (pk, sel, sig))
                hit = self._pk_polys[key]
        return hit[1], hit[2]

    def register_pk_polys(self, pk, sel_h, sig_h):
        """Seed the pk-poly cache with handles preprocess just computed on
        device, so the prover never lowers+re-lifts 18 selector/sigma
        polynomials through the host (kzg.preprocess batched path)."""
        with self._cache_lock:
            self._cache_put(self._pk_polys, id(pk), (pk, list(sel_h), list(sig_h)))

    def warm_stages(self, domain_size, ck=None):
        """AOT warm-start for one shape bucket (store/warmstart.py's hook).

        Pre-lowers/compiles the NTT kernel variants for the bucket's
        evaluation domain AND its quotient domain (the two sizes a prove
        of this shape launches, prover.py:59), at both single-poly and the
        batch widths _kernel_batches would pick — so the executables are
        in the persistent compile cache before the first job lands. With
        `ck`, also builds the commit key's MsmContext and AOT-lowers its
        commitment pipeline (`MsmContext.aot_compile`) at the prover's
        commit-batch widths — the wire batch (NUM_WIRE_TYPES), the
        opening pair, and single commits. A stage the compiler refuses
        is counted under `failed` with its message under `errors` in
        that kernel's report; nothing runs in its place."""
        from ..poly import Domain
        report = {"ntt": {}}
        quot = Domain((NUM_WIRE_TYPES + 1) * (domain_size + 1) + 1)
        for dom_n in sorted({domain_size, quot.size}):
            chunk = self._ntt_chunk(dom_n)
            report["ntt"][dom_n] = ntt_jax.get_plan(dom_n).aot_compile(
                batch_sizes=(chunk,) if chunk > 1 else ())
        if ck is not None:
            # digit widths = the blinded coefficient-handle widths the
            # prover actually commits: wires/quotient-splits/openings are
            # n+2 wide, the permutation poly n+3 (prover.py rounds 1-5)
            report["msm"] = self._ctx(ck).aot_compile(
                batch_sizes=(1, 2, NUM_WIRE_TYPES),
                digit_widths=(domain_size + 2, domain_size + 3))
            report["msm_warmed"] = True
        return report

    def _kernel(self, domain, h, inverse, coset):
        plan = ntt_jax.get_plan(domain.size)
        if h.shape[1] < domain.size:
            h = jnp.pad(h, ((0, 0), (0, domain.size - h.shape[1])))
        assert h.shape[1] == domain.size
        return plan.kernel(inverse=inverse, coset=coset, boundary="mont")(h)

    def ifft_h(self, domain, h):
        return self._kernel(domain, h, True, False)

    # batch NTTs run as single multi-poly launches, chunked by a B*n cap.
    # The XLA f32 mul path materializes its column tensor (~1 KB/elem) so
    # it needs B*n <= 2^21 (~2 GB transient); the fused Pallas multiplier
    # keeps those in VMEM, so the cap rises to 2^23 (working set is then
    # the (16, B, n) stage arrays, ~0.5 GB per copy at the cap) — at the
    # 2^21 quotient domain that turns round 3's 25 per-poly coset-FFT
    # launches into 7, saving ~18 x the ~120 ms per-call dispatch.
    # DPT_NTT_BATCH caps the chunk width.
    _NTT_BATCH = int(os.environ.get("DPT_NTT_BATCH", "8"))

    @staticmethod
    def _pad_to(h, size):
        # padding happens PER BATCH, never up front: materializing all 25
        # round-3 inputs at the quotient-domain width was 6.4 GB of
        # transient at m=2^22 — the dominant term of the measured 2^19
        # OOM (scale_2p19_r05.log attempt 1); inputs stay at their n-scale
        # widths until the launch that consumes them
        return (jnp.pad(h, ((0, 0), (0, size - h.shape[1])))
                if h.shape[1] < size else h)

    def _ntt_chunk(self, domain_size):
        """Batch width of one NTT launch: B*n capped by the mul-path
        transient budget (the ONE copy of the cap heuristic —
        _kernel_batches, the fused round-3 launches, and AOT warmup all
        pick their widths here so they can never desync)."""
        elems_cap = 1 << (23 if FJ._use_pallas((16, 1 << 22)) else 21)
        return max(1, min(self._NTT_BATCH, elems_cap // domain_size))

    def _kernel_batches(self, domain, hs, inverse, coset, defer_perm=False):
        """Yield (16, B, m) NTT result batches covering hs in order, B
        capped by the launch budget (_ntt_chunk). _kernel_many collects,
        quotient_streamed folds each batch into accumulators so no batch
        outlives its consumption. defer_perm: bit-reversed-order output
        (the round-3 deferral, DPT_R3_BITREV)."""
        plan = ntt_jax.get_plan(domain.size)
        chunk = self._ntt_chunk(domain.size)
        if chunk == 1 and not defer_perm:
            fn1 = plan.kernel(inverse=inverse, coset=coset, boundary="mont")
            for h in hs:
                yield fn1(self._pad_to(h, domain.size))[:, None]
            return
        fn = plan.kernel_batch(inverse=inverse, coset=coset,
                               defer_perm=defer_perm)
        for i in range(0, len(hs), max(chunk, 1)):
            yield fn(jnp.stack([self._pad_to(h, domain.size)
                                for h in hs[i:i + max(chunk, 1)]], axis=1))

    def _kernel_many(self, domain, hs, inverse, coset, post=None,
                     defer_perm=False):
        """B NTTs in capped batches; `post` (if given) maps each launch's
        (16, B, m) result before results are split out — e.g. the round-3
        limb packing, applied while at most one batch is unpacked."""
        out = []
        for res in self._kernel_batches(domain, hs, inverse, coset,
                                        defer_perm=defer_perm):
            if post is not None:
                res = post(res)
            out.extend(res[:, j] for j in range(res.shape[1]))
        return out

    def ifft_many(self, domain, hs):
        return self._kernel_many(domain, hs, True, False)

    def coset_fft_many(self, domain, hs):
        return self._kernel_many(domain, hs, False, True)

    # --- streaming round 3 ---------------------------------------------------
    # The single-device memory strategy for the quotient round
    # (/root/reference/src/dispatcher2.rs:382-507): the quotient formula
    # reads each SELECTOR plane once (a gate term) and each SIGMA plane
    # once (an acc2 factor), so both fold into running accumulators right
    # after their coset FFT and are dropped. Only ~10 planes stay
    # resident — 5 wires, z, z_next/acc2, pi→gate — all LIMB-PACKED
    # (8, m), and the final combine runs in lane slices that unpack on
    # the fly. Residency: ~2.5 GB at m=2^23 vs 6.4 GB all-packed and
    # 12.8 GB naive — the measured single-chip budget is ~7-9.5 GB
    # (scale_2p19_r05 attempt logs). The mesh backend opts out
    # (quotient_streamed = None): its memory strategy is sharding, and
    # slicing a GSPMD-sharded lane axis would reshard every chunk.

    _QUOT_SLICE = int(os.environ.get("DPT_QUOT_SLICE", str(1 << 20)))
    # drain the device queue every K streamed launches once the quotient
    # domain is huge: a fully-async warm round 3 enqueues the whole
    # 25-FFT pipeline before anything frees, and the queued buffer
    # lifetimes overlap enough to OOM at m=2^23 (scale_2p20_r05b.log
    # attempts 1-2: cold passes — compile pauses drain the queue — warm
    # RESOURCE_EXHAUSTEDs). A 1-element fetch costs ~0.1 s per drain.
    _STREAM_SYNC_EVERY = int(os.environ.get("DPT_STREAM_SYNC_EVERY", "4"))
    _STREAM_SYNC_MIN_M = int(os.environ.get("DPT_STREAM_SYNC_MIN_M",
                                            str(1 << 23)))

    def coset_fft_many_packed(self, domain, hs, defer_perm=False):
        """coset_fft_many, but each (16, m) result returns limb-packed
        (8, m). Packing rides the launch loop so at most one batch of
        unpacked outputs is ever resident. defer_perm: results stay in
        bit-reversed order (DPT_R3_BITREV pipeline)."""
        return self._kernel_many(domain, hs, False, True, post=PJ.pack_jit,
                                 defer_perm=defer_perm)

    def _domain_tables_packed(self, m, n, group_gen, bitrev=False):
        """Packed quotient-domain tables; bitrev=True re-indexes every
        lane through the bit-reversal permutation so the tables line up
        with the deferred-order accumulator planes (one extra gather at
        cache build, amortized over every prove of the shape)."""
        key = (m, n, bitrev)
        with self._cache_lock:
            hit = self._domain_tabs_packed.get(key)
        if hit is None:
            tabs = PJ.domain_tables_jit(m, n, FR_GENERATOR, group_gen)
            if bitrev:
                perm = jnp.asarray(ntt_jax.get_plan(m).perm)
                tabs = {kk: v[:, perm] for kk, v in tabs.items()}
            hit = {kk: PJ.pack_jit(v) for kk, v in tabs.items()}
            with self._cache_lock:
                self._domain_tabs_packed[key] = hit
        return hit

    def _roll_perm(self, m, ratio):
        """Gather index array carrying the z -> z_next roll INTO the
        bit-reversed plane order: with perm the bit-reversal permutation,
        bitrev(roll(natural, ratio))[i] = bitrev(z)[perm[(perm[i] +
        ratio) % m]] — one precomputed gather replaces the natural-order
        roll (both are pure data movement)."""
        key = ("roll_perm", m, ratio)
        with self._cache_lock:
            hit = self._domain_tabs_packed.get(key)
        if hit is None:
            perm = ntt_jax.get_plan(m).perm.astype(np.int64)
            hit = jnp.asarray(perm[(perm + ratio) % m].astype(np.int32))
            with self._cache_lock:
                self._domain_tabs_packed[key] = hit
        return hit

    # selector index -> (UNJITTED step body, wire-plane operand indices);
    # the round-3 FUSED path (DPT_R3_FUSE) traces these as the epilogue
    # of the selector coset-FFT program itself, so XLA fuses the gate
    # product with the NTT's final stage / output permutation and the
    # (16, B, m) selector planes never round-trip HBM between the FFT
    # and their one consuming multiply. Same circuit.py order as the
    # jitted gate_steps table below.
    _R3_GATE_STEPS = (
        [(PJ.gate_linear_step, (i,)) for i in range(4)]             # Q_LC
        + [(PJ.gate_mul2_step, (0, 1)), (PJ.gate_mul2_step, (2, 3))]  # Q_MUL
        + [(PJ.gate_pow5_step, (i,)) for i in range(4)]             # Q_HASH
        + [(PJ.gate_out_step, (4,)),                                # Q_O
           (PJ.gate_const_step, ()),                                # Q_C
           (PJ.gate_ecc_step, (0, 1, 2, 3, 4))]                     # Q_ECC
    )

    @classmethod
    def _gate_epilogue(cls, start, width):
        steps = cls._R3_GATE_STEPS[start:start + width]

        def epi(res, gate_p, *wires):
            for j, (fn, widx) in enumerate(steps):
                gate_p = fn(gate_p, res[:, j], *[wires[x] for x in widx])
            return gate_p
        return epi

    @staticmethod
    def _sigma_epilogue(start, width):
        def epi(res, acc2_p, beta_c, gamma_c, *wires):
            for j in range(width):
                acc2_p = PJ.sigma_step(acc2_p, res[:, j], wires[start + j],
                                       beta_c, gamma_c)
            return acc2_p
        return epi

    @staticmethod
    def _combine_prologue(m):
        def pro(w0, w1, w2, w3, w4, z_p, gate_p, acc2_p, ep, zh, sh,
                k_arr, beta, gamma, alpha, asdn):
            ev = PJ.quotient_combine_slice(
                [w0, w1, w2, w3, w4], z_p, gate_p, acc2_p, ep, zh, sh,
                k_arr, beta, gamma, alpha, asdn, jnp.uint32(0), chunk=m)
            return ev[:, None, :]
        return pro

    def _r3_accumulate(self, n, m, quot_domain, beta, gamma, sel_h, sigma_h,
                       wire_polys, perm_poly, pi_coeffs, bitrev=False):
        """Shared front half of round 3: base coset FFTs + gate/sigma
        plane folding. Returns (wires_p, z_p, gate_p, acc2_p, throttle).
        Under DPT_R3_FUSE each selector/sigma batch's fold runs as the
        EPILOGUE of its own coset-FFT program (NttPlan.kernel_fused) —
        value-identical to the standalone jitted steps, minus their
        write-plane + read-plane HBM pass per batch.

        bitrev=True (DPT_R3_BITREV, fused path only): every FFT launch
        defers its output bit-reversal, so all returned planes are in
        constant-geometry order — the folds are pointwise, so they are
        value-identical in any shared order; the z_next roll becomes one
        re-indexed gather (_roll_perm). The caller owns getting back to
        natural order (the consuming iNTT's input_perm)."""
        ratio = m // n
        bitrev = bitrev and _R3_FUSE  # only the fused folds speak deferred
        base = self.coset_fft_many_packed(
            quot_domain, list(wire_polys) + [perm_poly, pi_coeffs],
            defer_perm=bitrev)
        wires_p = base[:5]
        z_p = base[5]
        gate_p = base[6]               # gate accumulator starts as pi plane
        # acc2 starts as z_next: a natural-order roll, or — deferred —
        # the same data movement through the re-indexed gather
        acc2_p = (z_p[:, self._roll_perm(m, ratio)] if bitrev
                  else PJ.roll_jit(z_p, ratio))
        del base

        sync_every = (self._STREAM_SYNC_EVERY
                      if m >= self._STREAM_SYNC_MIN_M else 0)
        launches = [0]

        def _throttle(h):
            launches[0] += 1
            if sync_every and launches[0] % sync_every == 0:
                # 1-element fetch: bounds the async queue. Counted in
                # `drains`, NOT `lowers` — the lowers counter audits
                # PROTOCOL transfers (transcript scalars); this is a
                # fence whose payload is 4 bytes
                self.drains += 1
                np.asarray(h[:1, :1])

        _throttle(acc2_p)

        beta_c = jnp.asarray(PJ.lift_scalar(beta))
        gamma_c = jnp.asarray(PJ.lift_scalar(gamma))
        w = wires_p
        if _R3_FUSE:
            plan = ntt_jax.get_plan(quot_domain.size)
            chunk = self._ntt_chunk(quot_domain.size)
            for i in range(0, len(sel_h), chunk):
                hs = [self._pad_to(h, quot_domain.size)
                      for h in sel_h[i:i + chunk]]
                fnk = plan.kernel_fused(
                    False, True, key=("r3gate", i, len(hs)),
                    epilogue=self._gate_epilogue(i, len(hs)),
                    defer_perm=bitrev)
                gate_p = fnk((jnp.stack(hs, axis=1),),
                             (gate_p,) + tuple(w))
                _throttle(gate_p)
            for i in range(0, len(sigma_h), chunk):
                hs = [self._pad_to(h, quot_domain.size)
                      for h in sigma_h[i:i + chunk]]
                fnk = plan.kernel_fused(
                    False, True, key=("r3sigma", i, len(hs)),
                    epilogue=self._sigma_epilogue(i, len(hs)),
                    defer_perm=bitrev)
                acc2_p = fnk((jnp.stack(hs, axis=1),),
                             (acc2_p, beta_c, gamma_c) + tuple(w))
                _throttle(acc2_p)
            return wires_p, z_p, gate_p, acc2_p, _throttle

        # unfused reference path: standalone jitted step programs
        # (13 selectors share 6 compiled programs, circuit.py order)
        gate_steps = (
            [(PJ.gate_linear_step_jit, (w[i],)) for i in range(4)]      # Q_LC
            + [(PJ.gate_mul2_step_jit, (w[0], w[1])),                   # Q_MUL
               (PJ.gate_mul2_step_jit, (w[2], w[3]))]
            + [(PJ.gate_pow5_step_jit, (w[i],)) for i in range(4)]      # Q_HASH
            + [(PJ.gate_out_step_jit, (w[4],)),                         # Q_O
               (PJ.gate_const_step_jit, ()),                            # Q_C
               (PJ.gate_ecc_step_jit, tuple(w))]                        # Q_ECC
        )
        idx = 0
        for res in self._kernel_batches(quot_domain, list(sel_h), False, True):
            for j in range(res.shape[1]):
                fn, operands = gate_steps[idx]
                gate_p = fn(gate_p, res[:, j], *operands)
                idx += 1
            _throttle(gate_p)
        sj = 0
        for res in self._kernel_batches(quot_domain, list(sigma_h), False, True):
            for j in range(res.shape[1]):
                acc2_p = PJ.sigma_step_jit(acc2_p, res[:, j], w[sj],
                                           beta_c, gamma_c)
                sj += 1
            _throttle(acc2_p)
        return wires_p, z_p, gate_p, acc2_p, _throttle

    def quotient_streamed(self, n, m, quot_domain, k, beta, gamma, alpha,
                          alpha_sq_div_n, sel_h, sigma_h, wire_polys,
                          perm_poly, pi_coeffs):
        """Round 3 from coefficient handles: coset FFTs + quotient
        evaluation in one streaming pass (see class comment). Returns
        unpacked (16, m) quotient evals for the coset iFFT (the sliced
        combine; `quotient_poly_streamed` is the fused path that skips
        this materialization entirely)."""
        tabs = self._domain_tables_packed(m, n, quot_domain.group_gen)
        wires_p, z_p, gate_p, acc2_p, _throttle = self._r3_accumulate(
            n, m, quot_domain, beta, gamma, sel_h, sigma_h, wire_polys,
            perm_poly, pi_coeffs)

        chunk = min(self._QUOT_SLICE, m)
        assert m % chunk == 0
        k_arr = jnp.asarray(PJ.lift(list(k))).reshape(FR_LIMBS, len(k), 1)
        scal = [jnp.asarray(PJ.lift_scalar(x))
                for x in (beta, gamma, alpha, alpha_sq_div_n)]
        outs = []
        for j0 in range(0, m, chunk):
            outs.append(PJ.quotient_combine_slice_jit(
                list(wires_p), z_p, gate_p, acc2_p,
                tabs["ep"], tabs["zh_inv"], tabs["shifted_inv"],
                k_arr, *scal, np.uint32(j0), chunk=chunk))
            _throttle(outs[-1])
        return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)

    def quotient_poly_streamed(self, n, m, quot_domain, k, beta, gamma,
                               alpha, alpha_sq_div_n, sel_h, sigma_h,
                               wire_polys, perm_poly, pi_coeffs):
        """Round 3 all the way to the quotient POLYNOMIAL: the streaming
        accumulation, then — under DPT_R3_FUSE (default on) — the final
        pointwise combine runs as the PROLOGUE of the coset iNTT program
        (NttPlan.kernel_fused), fusing into the first inverse stage's
        reads so the (16, m) quotient-eval array never materializes as a
        standalone pass. With the knob off this is exactly
        quotient_streamed + coset_ifft_h (the sliced reference path)."""
        if not _R3_FUSE:
            evals = self.quotient_streamed(
                n, m, quot_domain, k, beta, gamma, alpha, alpha_sq_div_n,
                sel_h, sigma_h, wire_polys, perm_poly, pi_coeffs)
            return self.coset_ifft_h(quot_domain, evals)
        # DPT_R3_BITREV: the whole accumulation runs in bit-reversed
        # order (no per-launch output gathers) and the combine's result
        # returns to natural order through the consuming iNTT's input
        # gather — the one bit-reversal pass left in round 3
        bitrev = _R3_BITREV
        tabs = self._domain_tables_packed(m, n, quot_domain.group_gen,
                                          bitrev=bitrev)
        wires_p, z_p, gate_p, acc2_p, _throttle = self._r3_accumulate(
            n, m, quot_domain, beta, gamma, sel_h, sigma_h, wire_polys,
            perm_poly, pi_coeffs, bitrev=bitrev)
        k_arr = jnp.asarray(PJ.lift(list(k))).reshape(FR_LIMBS, len(k), 1)
        scal = [jnp.asarray(PJ.lift_scalar(x))
                for x in (beta, gamma, alpha, alpha_sq_div_n)]
        plan = ntt_jax.get_plan(quot_domain.size)
        fnk = plan.kernel_fused(True, True, key=("r3combine",),
                                prologue=self._combine_prologue(m),
                                input_perm=bitrev)
        poly = fnk(tuple(wires_p) + (z_p, gate_p, acc2_p, tabs["ep"],
                                     tabs["zh_inv"], tabs["shifted_inv"],
                                     k_arr) + tuple(scal))[:, 0]
        _throttle(poly)
        return poly

    def coset_fft_h(self, domain, h):
        return self._kernel(domain, h, False, True)

    def coset_ifft_h(self, domain, h):
        return self._kernel(domain, h, True, True)

    def blind(self, h, blinds, n):
        return PJ.blind_jit(h, jnp.asarray(PJ.lift(blinds)), n)

    def commit_h(self, ck, h):
        ctx = self._ctx(ck)
        return ctx.msm_mont_limbs(h)

    def commit_many_h(self, ck, hs):
        return self._ctx(ck).msm_mont_limbs_many(hs)

    # cross-job commit batching (the placement layer's data-parallel
    # path): one launch covers up to DPT_MSM_JOB_BATCH handles — wider
    # than the per-prove DPT_MSM_BATCH because a batch of N small jobs
    # commits 5N same-shape wire polys per round, and the per-launch
    # fixed cost is what batching across jobs exists to amortize. Plane
    # memory scales with the chunk (B*W*buckets), so the default stays
    # modest; small domains are exactly where it is cheap.
    _MSM_JOB_BATCH = int(os.environ.get("DPT_MSM_JOB_BATCH", "16"))

    def commit_batch(self, ck, hs):
        """Multi-proof commit path (prover.prove_many): B commitments —
        typically the SAME round of N different jobs — in launches of up
        to _MSM_JOB_BATCH, with same-width handles sharing ONE stacked
        digit-extraction launch (MsmContext._digits_many_fn). Results are
        bit-identical to commit_many_h per handle (each MSM is
        independent; grouping only changes launch boundaries)."""
        return self._ctx(ck).msm_mont_limbs_many(
            hs, chunk=max(1, self._MSM_JOB_BATCH))

    def commit_many_async(self, ck, hs):
        """Async commit dispatch (prover round pipeline): enqueue the MSM
        launches for `hs` and return an unforced pending whose force()
        performs the host-side decode. Values are bit-identical to
        commit_many_h — only WHEN the host blocks moves, which is what
        lets a pipelined member's host-finalize overlap another member's
        dispatched device work."""
        return self._ctx(ck).msm_mont_limbs_many_async(hs)

    def eval_many_async(self, pairs):
        """Async eval_many_h: the batched evaluation launch is enqueued
        here; the transfer + canonical decode run at pending.force()."""
        from .limbs import limbs_to_ints

        L = max(h.shape[1] for h, _ in pairs)
        polys = jnp.stack([jnp.pad(h, ((0, 0), (0, L - h.shape[1])))
                           for h, _ in pairs])  # (B, 16, L)
        zs = jnp.stack([jnp.asarray(PJ.lift_scalar(p)) for _, p in pairs])
        out = PJ.poly_eval_many_jit(polys, zs)  # (16, B) canonical

        def force():
            self.lowers += 1  # B scalars cross in one transfer
            return limbs_to_ints(np.asarray(out))
        return _DevicePending(force, (out,))

    def degree_is(self, h, d):
        if h.shape[1] <= d:
            return False
        top_nonzero = not PJ.tail_is_zero(h, d - 1)
        return PJ.tail_is_zero(h, d) and top_nonzero

    def split(self, h, size, count, total):
        assert count * size >= total
        if h.shape[1] < count * size:
            h = jnp.pad(h, ((0, 0), (0, count * size - h.shape[1])))
        return [h[:, i:i + size] for i in range(0, count * size, size)]

    def eval_h(self, h, point):
        self.lowers += 1  # one scalar crosses the boundary
        zc = jnp.asarray(PJ.lift_scalar(point))
        return PJ.lower(PJ.poly_eval_jit(h, zc))[0]

    def eval_many_h(self, pairs):
        """[(handle, point)] -> evaluations, in ONE device call: round 4's
        10 evaluations would otherwise pay 10 dispatch round-trips for 10
        scalars (SURVEY §7 hard part (d))."""
        from .limbs import limbs_to_ints

        L = max(h.shape[1] for h, _ in pairs)
        polys = jnp.stack([jnp.pad(h, ((0, 0), (0, L - h.shape[1])))
                           for h, _ in pairs])  # (B, 16, L)
        zs = jnp.stack([jnp.asarray(PJ.lift_scalar(p)) for _, p in pairs])
        out = PJ.poly_eval_many_jit(polys, zs)  # (16, B) canonical
        self.lowers += 1  # B scalars cross in one transfer
        return limbs_to_ints(np.asarray(out))

    def lin_comb_h(self, polys, coeffs):
        L = max(p.shape[1] for p in polys)
        stacked = jnp.stack(
            [jnp.pad(p, ((0, 0), (0, L - p.shape[1]))) for p in polys], axis=1)
        cf = jnp.asarray(PJ.lift(coeffs)).reshape(16, len(coeffs), 1)
        return PJ.lin_comb_jit(stacked, cf)

    def synth_div_h(self, h, point):
        zc = jnp.asarray(PJ.lift_scalar(point))
        return PJ.synthetic_divide_jit(h, zc)

    def _circuit_tables(self, circuit):
        """Per-circuit device tables: witness wires, identity-permutation
        values, and sigma-mapped identity values — lifted once."""
        key = id(circuit)
        with self._cache_lock:
            hit = self._circuit_tabs.get(key)
        if hit is not None:
            return hit[1]
        tabs = self._build_circuit_tables(circuit)
        with self._cache_lock:
            if key not in self._circuit_tabs:
                self._cache_put(self._circuit_tabs, key, (circuit, tabs))
            return self._circuit_tabs[key][1]

    def _build_circuit_tables(self, circuit):
        self.lifts += 1  # O(n) upload: witness + permutation tables
        n = len(circuit.wire_variables[0])
        w = NUM_WIRE_TYPES
        wire_vals = [circuit.wire_values(i) for i in range(w)]
        flat = [v for vals in wire_vals for v in vals]
        wires = self._lift_tab(PJ.lift(flat), w, n)
        id_flat = [circuit.extended_id_permutation[i][j]
                   for i in range(w) for j in range(n)]
        id_tab = self._lift_tab(PJ.lift(id_flat), w, n)
        sig_flat = []
        for i in range(w):
            for j in range(n):
                pi, pj = circuit.wire_permutation[i][j]
                sig_flat.append(circuit.extended_id_permutation[pi][pj])
        sig_tab = self._lift_tab(PJ.lift(sig_flat), w, n)
        return {"wires": wires, "id": id_tab, "sig": sig_tab, "n": n}

    def _lift_tab(self, arr, w, n):
        """Host (16, w*n) limb array -> (16, w, n) device table (placement
        hook, like _lift_arr)."""
        return jnp.asarray(arr).reshape(FR_LIMBS, w, n)

    # below this n the circuit tables stay cached across proves: the
    # release exists for round-3 HBM headroom at 2^19+, while re-lifting
    # the ~3*(16,5,n) tables host-to-device costs real wall-clock
    # (measured +8.6s on the 2^18 warm prove, scale_2p18_r05.json r1)
    _RELEASE_TABLES_MIN = int(os.environ.get("DPT_RELEASE_TABLES_MIN",
                                             str(1 << 19)))

    def release_circuit_tables(self, circuit):
        """Free the witness/permutation device tables (≈0.5 GB at n=2^19)
        when the circuit is large enough that round 3 needs the HBM.

        The prover calls this after round 2 — wire_values (round 1) and
        perm_product (round 2) are the only consumers. Above the
        threshold a subsequent prove re-lifts them (one O(n) upload);
        below it they stay cached keyed by circuit IDENTITY (the
        long-standing _circuit_tabs contract: mutating a circuit's
        witness in place and re-proving the same object is not
        supported — build a new circuit)."""
        if len(circuit.wire_variables[0]) < self._RELEASE_TABLES_MIN:
            return
        with self._cache_lock:
            self._circuit_tabs.pop(id(circuit), None)

    def perm_product(self, circuit, beta, gamma, n):
        tabs = self._circuit_tables(circuit)
        assert tabs["n"] == n
        return PJ.perm_product_jit(
            tabs["wires"], tabs["id"], tabs["sig"],
            jnp.asarray(PJ.lift_scalar(beta, 3)),
            jnp.asarray(PJ.lift_scalar(gamma, 3)))

    def _domain_tables(self, m, n, group_gen):
        key = (m, n)
        with self._cache_lock:
            if key not in self._domain_tabs:
                self._domain_tabs[key] = PJ.domain_tables_jit(
                    m, n, FR_GENERATOR, group_gen)
            return self._domain_tabs[key]

    def quotient(self, n, m, quot_domain, k, beta, gamma, alpha, alpha_sq_div_n,
                 selectors_coset, sigmas_coset, wires_coset, z_coset, pi_coset):
        tabs = self._domain_tables(m, n, quot_domain.group_gen)
        sel = jnp.stack(selectors_coset, axis=1)
        sig = jnp.stack(sigmas_coset, axis=1)
        wir = jnp.stack(wires_coset, axis=1)
        k_arr = jnp.asarray(PJ.lift(list(k))).reshape(FR_LIMBS, len(k), 1)
        ratio = m // n
        return PJ.quotient_evals_jit(
            sel, sig, wir, z_coset, pi_coset, tabs, k_arr,
            jnp.asarray(PJ.lift_scalar(beta)),
            jnp.asarray(PJ.lift_scalar(gamma)),
            jnp.asarray(PJ.lift_scalar(alpha)),
            jnp.asarray(PJ.lift_scalar(alpha_sq_div_n)), ratio)
