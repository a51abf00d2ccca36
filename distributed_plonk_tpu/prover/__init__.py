"""The 5-round TurboPlonk prover.

Round structure and math mirror the reference's fully-distributed v2 prover
(`Prover::prove`, /root/reference/src/dispatcher2.rs:192-713); ALL
polynomial work — NTTs, MSMs, and the per-round vector math (permutation
product, quotient evaluation, blinding, linear combination, evaluation,
synthetic division) — is delegated to a pluggable backend through an opaque
poly-handle API. On the host oracle backend a handle is an int list; on the
device backend it is a device-resident Montgomery limb array that never
leaves the device between rounds — realizing the fully-offloaded round
structure the reference declared but never implemented (the 12 dead
round3*/round5* RPCs, /root/reference/src/hello_world.capnp:26-44). Only
transcript scalars (commitments, challenges, evaluations) cross the host
boundary mid-prove.

Fiat-Shamir challenge schedule (beta, gamma, alpha, zeta, v) and transcript
bytes match FakeStandardTranscript exactly.

Each round is factored into an explicit STAGE with a device-launch half
(challenge derivation, host vector math, and the round's commit/eval
dispatch — returns an unforced pending) and a host-finalize half (forces
the pending, absorbs the results into the member's transcript, persists
the round checkpoint). Three drivers share the stages:

  * `prove`          — one job, stages run back-to-back (the reference's
                       sequential round loop).
  * `prove_many`     — N same-shape jobs in LOCKSTEP with cross-job
                       launches batched (PR 11).
  * `prove_pipelined`— N independent jobs in a SOFTWARE PIPELINE over the
                       rounds: up to DPT_PIPELINE_DEPTH members in flight,
                       so job B's round-1 commit MSMs are dispatched while
                       job A's round-2 transcript hashing and checkpoint
                       fsync run on host. The per-round checkpoint
                       boundaries are the stage latches.

All three produce byte-identical proofs for the same (rng, circuit, pk):
everything Fiat-Shamir or blinding touches is per-member state that never
crosses members, and pipelining only moves WHEN a launch happens, never
what it computes.
"""

import os
import random
import time
from collections import deque
from concurrent.futures import ThreadPoolExecutor

from ..checkpoint import (_point_dec, _point_enc, dump_handle, load_handle,
                          workload_fingerprint)
from ..constants import R_MOD
from ..fields import fr_inv
from ..poly import Domain
from ..circuit import NUM_WIRE_TYPES, Q_LC, Q_MUL, Q_HASH, Q_O, Q_C, Q_ECC
from ..trace import NULL_TRACER, msm_flops, ntt_flops
from ..transcript import StandardTranscript

# DPT_PIPELINE=0 is the bit-parity escape hatch: prove_pipelined degrades
# to a plain sequential prove loop and the worker pool stops coalescing.
# DPT_PIPELINE_DEPTH bounds in-flight members per pipelined prove. Module
# attributes (not call-time getenv) so tests and operators can flip them
# per-process, same idiom as service/placement.py's knobs.
PIPELINE = os.environ.get("DPT_PIPELINE", "1") != "0"
PIPELINE_DEPTH = max(1, int(os.environ.get("DPT_PIPELINE_DEPTH", "4")))


class Proof:
    def __init__(self, wires_poly_comms, prod_perm_poly_comm, split_quot_poly_comms,
                 opening_proof, shifted_opening_proof, wires_evals,
                 wire_sigma_evals, perm_next_eval):
        self.wires_poly_comms = wires_poly_comms
        self.prod_perm_poly_comm = prod_perm_poly_comm
        self.split_quot_poly_comms = split_quot_poly_comms
        self.opening_proof = opening_proof
        self.shifted_opening_proof = shifted_opening_proof
        self.wires_evals = wires_evals
        self.wire_sigma_evals = wire_sigma_evals
        self.perm_next_eval = perm_next_eval


def _rand(rng, count):
    return [rng.randrange(R_MOD) for _ in range(count)]


# -- pendings: what a stage's launch half hands its finalize half -------------

class _Ready:
    """Already-computed stage result (sync backends, or device work the
    launch half had to block on anyway). force() is free."""

    __slots__ = ("_values",)

    def __init__(self, values):
        self._values = values

    def force(self):
        return self._values


# how long force() waits for the watcher's completion stamp of a result it
# has just fetched: the stamp is normally there already (both wake on the
# same device event); past this the round goes without a device-true time
# rather than the worker waiting on its own instrument
_STAMP_WAIT_S = 2.0


class _KernelPending:
    """A dispatched-but-unforced device result, the last of its round.
    force() blocks until the device delivers, then records two events on
    the device's own clock (trace.DeviceLedger's completion stamps):
    `device/round<N>`, the device-true seconds the round is charged with
    the round's summed flops/bytes model, and `kernels/<name>`, whose
    `dur_s` is that same charge (an upper bound on the kernel's own time)
    and whose `wait_s` is dispatch→force on the host's clock, which under
    the pipeline includes other members' turns. Neither name is a
    top-level span, so Tracer.totals(depth=1) round accounting never
    double-books them; Metrics.observe_kernels folds both into the
    per-stage gflops/MFU gauges via the last path segment. Without a
    stamp (no ledger, or the watcher is late) the kernels/ event keeps
    the host-clock duration and carries no flops."""

    __slots__ = ("_force", "_tr", "_name", "_attrs", "_w0", "_p0", "_round")

    def __init__(self, force, tr, name, device_round=None, **attrs):
        self._force = force
        self._tr = tr
        self._name = name
        self._attrs = attrs
        self._round = device_round      # (round no, DeviceRound, work)
        self._w0 = time.time()
        self._p0 = time.perf_counter()

    def force(self):
        values = self._force()
        wait_s = dur_s = time.perf_counter() - self._p0
        attrs = {}
        if self._round is not None \
                and self._round[1].done.wait(_STAMP_WAIT_S):
            no, rnd, (flops, data_bytes) = self._round
            self._tr.add_event("device/round%d" % no,
                               ts=self._tr.wall(rnd.start),
                               dur_s=rnd.charge, flops=flops,
                               data_bytes=data_bytes)
            dur_s, attrs = rnd.charge, self._attrs
        self._tr.add_event("kernels/" + self._name, ts=self._w0,
                           dur_s=dur_s, wait_s=round(wait_s, 6), **attrs)
        return values


class _ProveCtx:
    """Read-only per-(pk, backend) state shared by the round stages:
    domains, the proving key's selector/sigma handles, and the backend's
    optional capability hooks. One instance serves any number of members
    (sequential, lockstep, or pipelined); nothing here is written after
    construction, so stages running on different threads share it freely."""

    def __init__(self, pk, backend):
        self.pk = pk
        self.backend = backend
        self.n = pk.domain_size
        self.domain = pk.domain
        self.nw = NUM_WIRE_TYPES
        self.quot_domain = Domain((self.nw + 1) * (self.n + 1) + 1)
        self.m = self.quot_domain.size
        self.ck = pk.ck
        self.sel_h, self.sigma_h = backend.pk_polys(pk)
        self.release = getattr(backend, "release_circuit_tables", None)
        # quotient_streamed: single-device backends fold each selector/
        # sigma coset plane into running accumulators as it is produced,
        # so only ~10 limb-packed planes are ever resident (the round-3
        # working set was the single-chip scale ceiling); the host oracle
        # and the mesh backend (whose memory strategy is sharding) run the
        # one-shot unpacked path. Both compute identical values.
        self.stream = getattr(backend, "quotient_streamed", None)
        # quotient_poly_streamed: same streaming accumulation, but the
        # final pointwise combine fuses into the coset iNTT program —
        # round 3 straight to the quotient polynomial with no standalone
        # O(m) passes (DPT_R3_FUSE)
        self.stream_poly = getattr(backend, "quotient_poly_streamed", None)
        self.commit_async = getattr(backend, "commit_many_async", None)
        self.eval_async = getattr(backend, "eval_many_async", None)
        # the device's completion-stamp ledger (trace.DeviceLedger) of a
        # backend that has one: an async backend's rounds are stamped by
        # the ledger's watcher, a sync one's (the mesh) when the commit or
        # the evaluations are back on the host (`_fetched`)
        self.ledger = getattr(backend, "device_ledger", None)

    def round_work(self, no):
        """Model (flops, data_bytes) of what round `no` puts on the
        device for one member: the sums of the attributions its kernel
        spans carry on a sync backend."""
        n, m, nw = self.n, self.m, self.nw
        if no == 1:
            return (ntt_flops(n, nw) + msm_flops(n + 2, nw),
                    nw * n * 32 + nw * (n + 2) * 32)
        if no == 2:
            return ntt_flops(n) + msm_flops(n + 3), n * 32 + (n + 3) * 32
        if no == 3:
            polys = len(self.sel_h) + 2 * nw + 2
            return (ntt_flops(m, polys + 1) + msm_flops(n + 2, nw),
                    (polys + 1) * m * 32 + nw * (n + 2) * 32)
        if no == 5:
            return msm_flops(n + 2, 2), 2 * (n + 2) * 32
        return 0, 0


class _Member:
    """One job's slice of a batched or pipelined prove: its own rng,
    transcript, tracer, checkpoint, and round outputs — everything
    Fiat-Shamir or blinding touches stays strictly per member, which is
    what makes both drivers byte-identical to N sequential proves."""

    def __init__(self, i, rng, ckt, tracer, checkpoint):
        self.i = i
        self.rng = rng or random.Random()
        self.ckt = ckt
        self.tr = tracer or NULL_TRACER
        self.checkpoint = checkpoint
        self.transcript = StandardTranscript()
        self.pub = ckt.public_input()
        self.fp = None
        self.ck_arrays = {}
        self.ck_meta = {}
        self.dev = None        # (round no, trace.DeviceRound) while open


def _feed(cx, mb, no):
    """The round's first dispatch is about to go out: open its interval
    on the device ledger (the device is fed from here on, and the round's
    device-true time starts no earlier). Placed by each launch half after
    its host prelude; idempotent within a round."""
    if cx.ledger is not None and mb.dev is None:
        mb.dev = (no, cx.ledger.open(mb.tr.worker))


def _unfeed(cx, mb):
    """The launch half is over: a round that never reached its commit
    dispatch (the launch raised) is closed here, so the ledger never
    believes the device fed by a round that will not complete."""
    if mb.dev is not None:
        cx.ledger.close(mb.dev[1])
        mb.dev = None


def _watched(cx, mb, dev):
    """Hand the round's last device arrays to the ledger's watcher;
    returns what _KernelPending needs to report the round."""
    if mb.dev is None:
        return None
    no, rnd = mb.dev
    cx.ledger.watch(rnd, dev.arrays())
    return no, rnd, cx.round_work(no)


def _fetched(cx, mb):
    """A sync backend has the round's last result on the host: close the
    round on the ledger at once and record its `device/round<N>` event,
    as `_KernelPending.force` does for a watched one."""
    if mb.dev is None:
        return
    no, rnd = mb.dev
    mb.dev = None
    cx.ledger.close(rnd)
    flops, data_bytes = cx.round_work(no)
    mb.tr.add_event("device/round%d" % no, ts=mb.tr.wall(rnd.start),
                    dur_s=rnd.charge, flops=flops, data_bytes=data_bytes)


def _kspan(cx, mb, name, **attrs):
    """A kernel span. On a backend with async dispatch the span times the
    enqueue, so it carries no flops/bytes attribution (the round's
    device/round<N> event does, against device-true time); on a sync
    backend the span times the compute and keeps them."""
    if cx.commit_async is not None:
        attrs.pop("flops", None)
        attrs.pop("data_bytes", None)
    return mb.tr.span(name, **attrs)


def _save_member(cx, mb, round_no):
    """THE round-boundary checkpoint latch — the one shared implementation
    (sequential, lockstep, and pipelined drivers all call it right after
    the round's finalize half, as a top-level `checkpoint_save` span of
    its own, never inside the round's span), so the snapshot payload can
    never drift between paths. Every guard control point (kill/drain/TTL
    check, journal ROUND record, fault injection) fires inside
    checkpoint.save's subclass hooks, so pipelined members still hit them
    at their OWN stage boundaries."""
    if mb.checkpoint is None:
        return
    with mb.tr.span("checkpoint_save", round=round_no):
        mb.checkpoint.save(
            round_no, mb.fp, mb.rng, mb.transcript,
            {k: dump_handle(cx.backend, h) for k, h in mb.ck_arrays.items()},
            mb.ck_meta)


def _loadh(cx, ck_state, name):
    return load_handle(cx.backend, ck_state["arrays"][name])


def _points(meta_val):
    return [_point_dec(v) for v in meta_val]


def _dispatch_commit(cx, mb, hs, name, span_attrs):
    """Dispatch the round's commit MSMs over `hs`. Async-capable backends
    enqueue the launches and return an unforced pending (the member's
    host-finalize forces it — that is the pipeline overlap window);
    backends without async dispatch compute inline under the same kernel
    span the sequential prover always recorded, so the host-oracle and
    mesh trace/MFU attribution is unchanged. `span_attrs` carries the
    flops/bytes model: on the kernel span for the sync path, moved onto
    the force-side `kernels/<name>` event for the async path, where the
    commit's device arrays also close the round on the device ledger."""
    if cx.commit_async is not None:
        with _kspan(cx, mb, name, **span_attrs):
            dev = cx.commit_async(cx.ck, hs)
        attrs = {k: span_attrs[k] for k in ("flops", "data_bytes")
                 if k in span_attrs}
        return _KernelPending(dev.force, mb.tr, name,
                              device_round=_watched(cx, mb, dev), **attrs)
    with mb.tr.span(name, **span_attrs):
        comms = cx.backend.commit_many_h(cx.ck, hs)
    _fetched(cx, mb)
    return _Ready(comms)


def _dispatch_evals(cx, mb, pairs):
    """Round-4 evaluation dispatch; same contract as _dispatch_commit."""
    if cx.eval_async is not None:
        dev = cx.eval_async(pairs)
        return _KernelPending(dev.force, mb.tr, "eval_many",
                              device_round=_watched(cx, mb, dev))
    evals = cx.backend.eval_many_h(pairs)
    _fetched(cx, mb)
    return _Ready(evals)


# -- the five round stages ----------------------------------------------------
# Each launch half runs challenges + host math + kernel dispatch and returns
# a pending; each finalize half takes its forced values and absorbs them
# into the transcript, after which the driver saves the round checkpoint
# (`_Stage.latch`, the stage latch). Each restore half reproduces
# the resume path from a round-`no` snapshot, bit-for-bit the pre-stage
# behavior. The cumulative checkpoint payload rule still holds: every
# snapshot carries all state the REMAINING rounds read (wire/perm/quotient
# handles + commitments + challenges), since earlier snapshots are
# overwritten.

def _launch_r1(cx, mb):
    # --- Round 1: wire polynomials (reference src/dispatcher2.rs:293-323)
    # kernel spans carry the flops/bytes attribution model (trace.py) so
    # the merged timeline and the live MFU gauges (Metrics.observe_kernels)
    # can say where device time went, not just that it went
    be, n, nw = cx.backend, cx.n, cx.nw
    with _kspan(cx, mb, "ifft_wires", polys=nw, flops=ntt_flops(n, nw),
                data_bytes=nw * n * 32):
        # the witness tables are built and uploaded by the host first
        wires = be.wire_values(mb.ckt)
        _feed(cx, mb, 1)
        # one batch call: concurrent across the fleet (join_all,
        # reference dispatcher2.rs:294-306) / one launch on device
        wire_coeffs = be.ifft_many(cx.domain, wires)
        mb.wire_polys = [be.blind(coeffs, _rand(mb.rng, 2), n)
                         for coeffs in wire_coeffs]
    return _dispatch_commit(
        cx, mb, mb.wire_polys, "commit_wires",
        {"polys": nw, "flops": msm_flops(n + 2, nw),
         "data_bytes": nw * (n + 2) * 32})


def _finalize_r1(cx, mb, comms):
    mb.wires_poly_comms = list(comms)
    mb.transcript.append_commitments(b"witness_poly_comms",
                                     mb.wires_poly_comms)
    if mb.checkpoint is not None:
        mb.ck_arrays.update({"wire_poly_%d" % i: h
                             for i, h in enumerate(mb.wire_polys)})
        mb.ck_meta["wires_poly_comms"] = [_point_enc(p)
                                          for p in mb.wires_poly_comms]


def _restore_r1(cx, mb, ck_state):
    mb.wire_polys = [_loadh(cx, ck_state, "wire_poly_%d" % i)
                     for i in range(cx.nw)]
    mb.wires_poly_comms = _points(ck_state["meta"]["wires_poly_comms"])
    mb.ck_arrays.update({"wire_poly_%d" % i: h
                         for i, h in enumerate(mb.wire_polys)})
    mb.ck_meta.update(ck_state["meta"])


def _launch_r2(cx, mb):
    # --- Round 2: permutation product (reference src/dispatcher2.rs:325-357)
    be, n = cx.backend, cx.n
    mb.beta = mb.transcript.get_and_append_challenge(b"beta")
    mb.gamma = mb.transcript.get_and_append_challenge(b"gamma")
    _feed(cx, mb, 2)
    with mb.tr.span("perm_product"):
        product_h = be.perm_product(mb.ckt, mb.beta, mb.gamma, n)
    with _kspan(cx, mb, "ifft_perm", flops=ntt_flops(n), data_bytes=n * 32):
        perm_coeffs = be.ifft_h(cx.domain, product_h)
    mb.permutation_poly = be.blind(perm_coeffs, _rand(mb.rng, 3), n)
    return _dispatch_commit(
        cx, mb, [mb.permutation_poly], "commit_perm",
        {"flops": msm_flops(n + 3), "data_bytes": (n + 3) * 32})


def _finalize_r2(cx, mb, comms):
    mb.prod_perm_poly_comm = comms[0]
    mb.transcript.append_commitment(b"perm_poly_comms",
                                    mb.prod_perm_poly_comm)
    if mb.checkpoint is not None:
        mb.ck_arrays["permutation_poly"] = mb.permutation_poly
        mb.ck_meta["beta"] = hex(mb.beta)
        mb.ck_meta["gamma"] = hex(mb.gamma)
        mb.ck_meta["prod_perm_poly_comm"] = \
            _point_enc(mb.prod_perm_poly_comm)


def _restore_r2(cx, mb, ck_state):
    mb.permutation_poly = _loadh(cx, ck_state, "permutation_poly")
    mb.ck_arrays["permutation_poly"] = mb.permutation_poly
    mb.beta = int(mb.ck_meta["beta"], 16)
    mb.gamma = int(mb.ck_meta["gamma"], 16)
    mb.prod_perm_poly_comm = _point_dec(mb.ck_meta["prod_perm_poly_comm"])


def _launch_r3(cx, mb):
    # --- Round 3: quotient polynomial (reference src/dispatcher2.rs:360-533)
    be, n, m, nw = cx.backend, cx.n, cx.m, cx.nw
    # rounds 3-5 never read the witness/permutation tables; a backend may
    # reclaim that device memory for round 3's quotient-domain working set
    if cx.release is not None:
        cx.release(mb.ckt)
    mb.alpha = mb.transcript.get_and_append_challenge(b"alpha")
    alpha_sq_div_n = mb.alpha * mb.alpha % R_MOD * fr_inv(n % R_MOD) % R_MOD
    pub_h = be.lift(mb.pub + [0] * (n - len(mb.pub)))
    _feed(cx, mb, 3)
    pi_coeffs = be.ifft_h(cx.domain, pub_h)
    quot_evals = None
    n_coset_polys = len(cx.sel_h) + 2 * nw + 2
    if cx.stream_poly is not None:
        with _kspan(cx, mb, "quotient_stream_fused", m=m,
                    polys=n_coset_polys,
                    flops=ntt_flops(m, n_coset_polys + 1),
                    data_bytes=n_coset_polys * m * 32):
            quotient_poly = cx.stream_poly(
                n, m, cx.quot_domain, cx.pk.vk.k, mb.beta, mb.gamma,
                mb.alpha, alpha_sq_div_n, cx.sel_h, cx.sigma_h,
                mb.wire_polys, mb.permutation_poly, pi_coeffs)
    elif cx.stream is not None:
        with _kspan(cx, mb, "quotient_stream", m=m, polys=n_coset_polys,
                    flops=ntt_flops(m, n_coset_polys),
                    data_bytes=n_coset_polys * m * 32):
            quot_evals = cx.stream(
                n, m, cx.quot_domain, cx.pk.vk.k, mb.beta, mb.gamma,
                mb.alpha, alpha_sq_div_n, cx.sel_h, cx.sigma_h,
                mb.wire_polys, mb.permutation_poly, pi_coeffs)
    else:
        with _kspan(cx, mb, "coset_ffts", polys=n_coset_polys,
                    flops=ntt_flops(m, n_coset_polys),
                    data_bytes=n_coset_polys * m * 32):
            # the 24 coset-FFTs go out as one batch (concurrent across
            # the fleet / one device launch; dispatcher2.rs:382-423)
            batch = be.coset_fft_many(
                cx.quot_domain,
                list(cx.sel_h) + list(cx.sigma_h) + mb.wire_polys
                + [mb.permutation_poly, pi_coeffs])
            ns = len(cx.sel_h)
            selectors_coset = batch[:ns]
            sigmas_coset = batch[ns:ns + nw]
            wires_coset = batch[ns + nw:ns + 2 * nw]
            z_coset = batch[ns + 2 * nw]
            pi_coset = batch[ns + 2 * nw + 1]
        with mb.tr.span("quotient_evals", m=m):
            quot_evals = be.quotient(
                n, m, cx.quot_domain, cx.pk.vk.k, mb.beta, mb.gamma,
                mb.alpha, alpha_sq_div_n, selectors_coset, sigmas_coset,
                wires_coset, z_coset, pi_coset,
            )
            del batch, selectors_coset, sigmas_coset, wires_coset
            del z_coset, pi_coset
    if quot_evals is not None:
        with _kspan(cx, mb, "coset_ifft_quot", flops=ntt_flops(m),
                    data_bytes=m * 32):
            quotient_poly = be.coset_ifft_h(cx.quot_domain, quot_evals)

    expected_degree = nw * (n + 1) + 2
    assert be.degree_is(quotient_poly, expected_degree), expected_degree
    # split into num_wire_types chunks of n+2 coefficients
    # (reference src/dispatcher2.rs:511-525)
    mb.split_quot_polys = be.split(quotient_poly, n + 2, nw,
                                   expected_degree + 1)
    return _dispatch_commit(
        cx, mb, mb.split_quot_polys, "commit_quot",
        {"polys": nw, "flops": msm_flops(n + 2, nw),
         "data_bytes": nw * (n + 2) * 32})


def _finalize_r3(cx, mb, comms):
    mb.split_quot_poly_comms = list(comms)
    mb.transcript.append_commitments(b"quot_poly_comms",
                                     mb.split_quot_poly_comms)
    if mb.checkpoint is not None:
        mb.ck_arrays.update({"split_quot_poly_%d" % i: h
                             for i, h in enumerate(mb.split_quot_polys)})
        mb.ck_meta["alpha"] = hex(mb.alpha)
        mb.ck_meta["split_quot_poly_comms"] = [
            _point_enc(p) for p in mb.split_quot_poly_comms]


def _restore_r3(cx, mb, ck_state):
    # the round-3 snapshot was taken AFTER the quot-comms transcript
    # absorb, so restoring it must not absorb them again
    if cx.release is not None:
        cx.release(mb.ckt)
    mb.alpha = int(mb.ck_meta["alpha"], 16)
    mb.split_quot_polys = [_loadh(cx, ck_state, "split_quot_poly_%d" % i)
                           for i in range(cx.nw)]
    mb.split_quot_poly_comms = _points(mb.ck_meta["split_quot_poly_comms"])
    mb.ck_arrays.update({"split_quot_poly_%d" % i: h
                         for i, h in enumerate(mb.split_quot_polys)})


def _launch_r4(cx, mb):
    # --- Round 4: evaluations (reference src/dispatcher2.rs:542-561)
    mb.zeta = mb.transcript.get_and_append_challenge(b"zeta")
    # all 10 evaluations in one backend call (one device round-trip)
    pairs = ([(w, mb.zeta) for w in mb.wire_polys]
             + [(s, mb.zeta) for s in cx.sigma_h[:cx.nw - 1]]
             + [(mb.permutation_poly,
                 mb.zeta * cx.domain.group_gen % R_MOD)])
    _feed(cx, mb, 4)
    return _dispatch_evals(cx, mb, pairs)


def _finalize_r4(cx, mb, evals):
    nw = cx.nw
    mb.wires_evals = evals[:nw]
    mb.wire_sigma_evals = evals[nw:2 * nw - 1]
    mb.perm_next_eval = evals[-1]
    mb.transcript.append_proof_evaluations(
        mb.wires_evals, mb.wire_sigma_evals, mb.perm_next_eval)
    if mb.checkpoint is not None:
        mb.ck_meta["zeta"] = hex(mb.zeta)
        mb.ck_meta["wires_evals"] = [hex(v) for v in mb.wires_evals]
        mb.ck_meta["wire_sigma_evals"] = [hex(v)
                                          for v in mb.wire_sigma_evals]
        mb.ck_meta["perm_next_eval"] = hex(mb.perm_next_eval)


def _restore_r4(cx, mb, ck_state):
    mb.zeta = int(mb.ck_meta["zeta"], 16)
    mb.wires_evals = [int(v, 16) for v in mb.ck_meta["wires_evals"]]
    mb.wire_sigma_evals = [int(v, 16)
                           for v in mb.ck_meta["wire_sigma_evals"]]
    mb.perm_next_eval = int(mb.ck_meta["perm_next_eval"], 16)


def _launch_r5(cx, mb):
    # --- Round 5: linearization + openings (reference
    # src/dispatcher2.rs:563-692)
    be, n, nw = cx.backend, cx.n, cx.nw
    vanish_eval = (pow(mb.zeta, n, R_MOD) - 1) % R_MOD
    _feed(cx, mb, 5)
    with mb.tr.span("lin_poly"):
        lin_poly = _linearization_poly(
            be, cx.pk, cx.sel_h, cx.sigma_h, n, mb.beta, mb.gamma,
            mb.alpha, mb.zeta, vanish_eval, mb.wires_evals,
            mb.wire_sigma_evals, mb.perm_next_eval, mb.permutation_poly,
            mb.split_quot_polys,
        )
    v = mb.transcript.get_and_append_challenge(b"v")
    # batched opening at zeta: lin + wires + first 4 sigmas, powers of v
    with mb.tr.span("batch_open"):
        polys = [lin_poly] + mb.wire_polys + cx.sigma_h[:nw - 1]
        coeffs = []
        c = 1
        for _ in polys:
            coeffs.append(c)
            c = c * v % R_MOD
        batch_poly = be.lin_comb_h(polys, coeffs)
        mb.witness_poly = be.synth_div_h(batch_poly, mb.zeta)
        mb.shifted_witness_poly = be.synth_div_h(
            mb.permutation_poly, mb.zeta * cx.domain.group_gen % R_MOD)
    return _dispatch_commit(
        cx, mb, [mb.witness_poly, mb.shifted_witness_poly], "commit_open",
        {"flops": msm_flops(n + 2, 2), "data_bytes": 2 * (n + 2) * 32})


def _finalize_r5(cx, mb, comms):
    mb.opening_proof, mb.shifted_opening_proof = comms
    # a finished prove must not leave a snapshot behind: a later prove()
    # pointed at the same path would silently resume at round 5 and emit a
    # byte-identical proof with REUSED blinds instead of a fresh one
    if mb.checkpoint is not None:
        mb.checkpoint.clear()
    mb.proof = Proof(
        mb.wires_poly_comms, mb.prod_perm_poly_comm,
        mb.split_quot_poly_comms, mb.opening_proof,
        mb.shifted_opening_proof, mb.wires_evals, mb.wire_sigma_evals,
        mb.perm_next_eval,
    )


class _Stage:
    """One prover round as a pipeline stage: a device-launch half (returns
    an unforced pending), a host-finalize half (forces it and absorbs into
    the member's transcript; the driver then persists the round
    checkpoint with `_save_member` — the stage LATCH), and a restore half
    reproducing the resume path from a round-`no` snapshot (round 5 never
    snapshots, so it has none)."""

    __slots__ = ("no", "name", "launch", "finalize", "restore")

    def __init__(self, no, launch, finalize, restore=None):
        self.no = no
        self.name = "round%d" % no
        self.launch = launch
        self.finalize = finalize
        self.restore = restore

    def run_launch(self, cx, mb, force=False):
        """The launch half under its round span, returning the pending
        (or, with `force`, its values: the sequential driver's round span
        ends after the fetch). A round the launch opened on the device
        ledger and did not hand to the watcher (it raised first) is closed
        on the way out."""
        with mb.tr.span(self.name):
            try:
                pending = self.launch(cx, mb)
                return pending.force() if force else pending
            finally:
                _unfeed(cx, mb)

    def latch(self, cx, mb):
        """The round's checkpoint, after its finalize half."""
        if self.restore is not None:
            _save_member(cx, mb, self.no)


_STAGES = (
    _Stage(1, _launch_r1, _finalize_r1, _restore_r1),
    _Stage(2, _launch_r2, _finalize_r2, _restore_r2),
    _Stage(3, _launch_r3, _finalize_r3, _restore_r3),
    _Stage(4, _launch_r4, _finalize_r4, _restore_r4),
    _Stage(5, _launch_r5, _finalize_r5),
)


def prove(rng, circuit, pk, backend, tracer=None, checkpoint=None):
    """Produce a TurboPlonk proof for a finalized, satisfied circuit.

    tracer: optional trace.Tracer; records per-round and per-kernel-batch
    wall-clock spans (the reference prints these ad hoc,
    /root/reference/src/dispatcher.rs:625-942).
    checkpoint: optional checkpoint.ProverCheckpoint; after each of rounds
    1-4 the inter-round state is persisted, and a prove interrupted at any
    point resumes from the last completed round, producing byte-identical
    output (the reference has no checkpointing — SURVEY.md §5).

    This is the sequential stage driver: each round's launch half runs
    under its round span and is forced immediately, so the trace contract
    (roundN top-level spans, nested kernel spans with flops attribution)
    is the historical one."""
    cx = _ProveCtx(pk, backend)
    mb = _Member(0, rng, circuit, tracer, checkpoint)
    mb.transcript.append_vk_and_pub_input(pk.vk, mb.pub)

    # checkpoint/resume bookkeeping: `start` is the first UNFINISHED round;
    # completed rounds restore their outputs from the snapshot instead of
    # recomputing, and the transcript sponge + blinder RNG rewind to the
    # snapshot point so the challenge schedule continues bit-for-bit
    start = 0
    if checkpoint is not None:
        with mb.tr.span("guard_open"):
            mb.fp = workload_fingerprint(pk.vk, mb.pub)
            ck_state = checkpoint.load(mb.fp)
            if ck_state is not None:
                start = ck_state["round"]
                checkpoint.restore_into(ck_state, mb.rng, mb.transcript)
                for st in _STAGES[:start]:
                    st.restore(cx, mb, ck_state)

    for st in _STAGES[start:]:
        st.finalize(cx, mb, st.run_launch(cx, mb, force=True))
        st.latch(cx, mb)
    return mb.proof


def prove_many(rngs, circuits, pk, backend, tracers=None, checkpoints=None,
               abort_on=()):
    """N same-shape TurboPlonk proofs in LOCKSTEP, with the cross-job
    kernel launches batched: the round-1 wire iFFTs/commit MSMs, the
    round-2 permutation commits, the round-3 split-quotient commits, the
    round-4 evaluations, and the round-5 opening commits of ALL members
    each run as one batched backend call (`commit_batch` when the backend
    has it, else `commit_many_h`; `ifft_many`; `eval_many_h`) instead of
    N separate call sequences. This is the data-parallel small-job path
    of the placement scheduler (service/placement.py) — throughput scales
    in jobs per launch while each job's proof bytes stay IDENTICAL to a
    sequential `prove`, because per-job state (transcript sponge,
    blinding rng, challenges) never crosses members and every batched
    kernel computes each member's slice independently (MSM results are
    exact group elements; batch width only moves launch boundaries).

    rngs/circuits/tracers/checkpoints: parallel per-member lists (tracers
    and checkpoints optional). All circuits must share `pk`'s shape.

    Failure isolation: a member whose round-boundary control point raises
    (worker kill, timeout — anything the checkpoint guard fires) is
    dropped from the batch with its exception recorded, and the
    SURVIVORS finish unaffected; the dead member's snapshot is durable,
    so its retry resumes alone through the sequential path. Exception
    types in `abort_on` (e.g. a drain) propagate instead, aborting the
    whole batch. Members that already HAVE a snapshot are routed to the
    sequential prover up front — resume semantics stay the single-job
    contract pinned by tests/test_checkpoint.py.

    Returns (proofs, errors): per-member Proof-or-None and
    exception-or-None lists."""
    cx = _ProveCtx(pk, backend)
    dev = []    # the batch's round open on the device ledger, if one is
    try:
        return _prove_many(cx, dev, rngs, circuits, pk, backend, tracers,
                           checkpoints, abort_on)
    finally:
        if dev:   # a batch-wide failure between a round's feed and fed
            cx.ledger.close(dev.pop())


def _prove_many(cx, dev, rngs, circuits, pk, backend, tracers, checkpoints,
                abort_on):
    N = len(circuits)
    rngs = list(rngs)
    tracers = list(tracers) if tracers is not None else [None] * N
    checkpoints = (list(checkpoints) if checkpoints is not None
                   else [None] * N)
    n, domain, num_wire_types = cx.n, cx.domain, cx.nw
    quot_domain, m, ck = cx.quot_domain, cx.m, cx.ck
    sel_h, sigma_h = cx.sel_h, cx.sigma_h
    commit_many = (getattr(backend, "commit_batch", None)
                   or backend.commit_many_h)

    proofs = [None] * N
    errors = [None] * N
    live = []
    for i in range(N):
        mb = _Member(i, rngs[i], circuits[i], tracers[i], checkpoints[i])
        if mb.checkpoint is not None and \
                getattr(mb.checkpoint, "has_snapshot", lambda: False)():
            # mid-prove state exists: resume through the sequential
            # prover, whose restore path is the pinned contract
            try:
                proofs[i] = prove(mb.rng, mb.ckt, pk, backend,
                                  tracer=mb.tr, checkpoint=mb.checkpoint)
            except abort_on:
                raise
            except Exception as e:
                errors[i] = e
            continue
        # a batch member is in a round (the batch's, whole), in a span of
        # its own, or waiting for its batch-mates: `pipeline_wait`
        mb.tr.waits = "pipeline_wait"
        mb.tr.park("pipeline_wait")
        mb.transcript.append_vk_and_pub_input(pk.vk, mb.pub)
        if mb.checkpoint is not None:
            # round-0 control point, parity with prove(): loading the
            # (absent) snapshot runs the guard's pre-round check — a
            # kill/drain armed at round 0 fires for batch members too
            try:
                with mb.tr.span("guard_open"):
                    mb.fp = workload_fingerprint(pk.vk, mb.pub)
                    mb.checkpoint.load(mb.fp)
            except abort_on:
                raise
            except Exception as e:
                errors[i] = e
                continue
        live.append(mb)

    def each_live(fn):
        """fn(member) for every live member; a raising member is failed
        and dropped (abort_on propagates — the whole batch stops)."""
        nonlocal live
        kept = []
        for mb in live:
            try:
                fn(mb)
            except abort_on:
                raise
            except Exception as e:  # member-local failure, batch survives
                errors[mb.i] = e
                continue
            kept.append(mb)
        live = kept

    def begin_round():
        # the members' wait for the batch ends where the round begins
        for mb in live:
            mb.tr.unpark()
        return time.time(), time.perf_counter()

    def mark_round(name, wall0, dur):
        # every member's timeline shows the batch round it rode in (the
        # launches are shared, so the span IS each job's wall time); the
        # checkpoint latches that follow are spans of each member's own
        for mb in live:
            mb.tr.add_event(name, ts=wall0, dur_s=dur,
                            batched_jobs=len(live))
            mb.tr.park("pipeline_wait")

    # the batch's rounds on the device ledger (`dev` holds the open one):
    # opened at the round's first dispatch, closed when its (sync) commit
    # has returned, the charge shared equally among the members in it
    def feed():
        if cx.ledger is not None and not dev and live:
            dev.append(cx.ledger.open(live[0].tr.worker))

    def fed(no):
        if not dev:
            return
        rnd = dev.pop()
        cx.ledger.close(rnd)
        flops, data_bytes = cx.round_work(no)
        for mb in live:
            mb.tr.add_event("device/round%d" % no, ts=mb.tr.wall(rnd.start),
                            dur_s=rnd.charge / len(live), flops=flops,
                            data_bytes=data_bytes, batched_jobs=len(live))

    # --- Round 1: wire polynomials (one iFFT + one commit launch set) -------
    w0, p0 = begin_round()
    if live:
        all_wires = []
        for mb in live:
            all_wires.extend(backend.wire_values(mb.ckt))
        feed()
        coeffs = backend.ifft_many(domain, all_wires)
        polys = []
        for j, mb in enumerate(live):
            cs = coeffs[num_wire_types * j:num_wire_types * (j + 1)]
            mb.wire_polys = [backend.blind(c, _rand(mb.rng, 2), n)
                             for c in cs]
            polys.extend(mb.wire_polys)
        comms = commit_many(ck, polys)
        fed(1)
        for j, mb in enumerate(live):
            mb.wires_poly_comms = \
                comms[num_wire_types * j:num_wire_types * (j + 1)]
        each_live(lambda mb: _finalize_r1(cx, mb, mb.wires_poly_comms))
        mark_round("round1", w0, time.perf_counter() - p0)
        each_live(lambda mb: _save_member(cx, mb, 1))

    # --- Round 2: permutation product ---------------------------------------
    w0, p0 = begin_round()
    if live:
        def r2a(mb):
            mb.beta = mb.transcript.get_and_append_challenge(b"beta")
            mb.gamma = mb.transcript.get_and_append_challenge(b"gamma")
            feed()
            mb.product_h = backend.perm_product(mb.ckt, mb.beta, mb.gamma, n)
        each_live(r2a)
    if live:
        prods = backend.ifft_many(domain, [mb.product_h for mb in live])
        for mb, pc in zip(live, prods):
            mb.perm_coeffs = pc

        def r2b(mb):
            mb.permutation_poly = backend.blind(mb.perm_coeffs,
                                                _rand(mb.rng, 3), n)
        each_live(r2b)
    if live:
        comms = commit_many(ck, [mb.permutation_poly for mb in live])
        fed(2)
        for mb, c in zip(live, comms):
            mb.prod_perm_poly_comm = c
        each_live(lambda mb: _finalize_r2(cx, mb, [mb.prod_perm_poly_comm]))
        mark_round("round2", w0, time.perf_counter() - p0)
        each_live(lambda mb: _save_member(cx, mb, 2))

    if cx.release is not None:
        for mb in live:
            cx.release(mb.ckt)

    # --- Round 3: quotient polynomial (per-member pipeline, one commit) -----
    w0, p0 = begin_round()
    if live:
        pubs = [backend.lift(mb.pub + [0] * (n - len(mb.pub)))
                for mb in live]
        feed()
        pis = backend.ifft_many(domain, pubs)
        for mb, pi in zip(live, pis):
            mb.pi_coeffs = pi

        def r3(mb):
            mb.alpha = mb.transcript.get_and_append_challenge(b"alpha")
            asdn = (mb.alpha * mb.alpha % R_MOD
                    * fr_inv(n % R_MOD) % R_MOD)
            if cx.stream_poly is not None:
                quotient_poly = cx.stream_poly(
                    n, m, quot_domain, pk.vk.k, mb.beta, mb.gamma,
                    mb.alpha, asdn, sel_h, sigma_h, mb.wire_polys,
                    mb.permutation_poly, mb.pi_coeffs)
            elif cx.stream is not None:
                quot_evals = cx.stream(
                    n, m, quot_domain, pk.vk.k, mb.beta, mb.gamma,
                    mb.alpha, asdn, sel_h, sigma_h, mb.wire_polys,
                    mb.permutation_poly, mb.pi_coeffs)
                quotient_poly = backend.coset_ifft_h(quot_domain,
                                                     quot_evals)
            else:
                batch = backend.coset_fft_many(
                    quot_domain,
                    list(sel_h) + list(sigma_h) + mb.wire_polys
                    + [mb.permutation_poly, mb.pi_coeffs])
                ns, nw = len(sel_h), num_wire_types
                quot_evals = backend.quotient(
                    n, m, quot_domain, pk.vk.k, mb.beta, mb.gamma,
                    mb.alpha, asdn, batch[:ns], batch[ns:ns + nw],
                    batch[ns + nw:ns + 2 * nw], batch[ns + 2 * nw],
                    batch[ns + 2 * nw + 1])
                quotient_poly = backend.coset_ifft_h(quot_domain,
                                                     quot_evals)
            expected_degree = num_wire_types * (n + 1) + 2
            assert backend.degree_is(quotient_poly, expected_degree), \
                expected_degree
            mb.split_quot_polys = backend.split(
                quotient_poly, n + 2, num_wire_types, expected_degree + 1)
        each_live(r3)
    if live:
        comms = commit_many(ck, [h for mb in live
                                 for h in mb.split_quot_polys])
        fed(3)
        for j, mb in enumerate(live):
            mb.split_quot_poly_comms = \
                comms[num_wire_types * j:num_wire_types * (j + 1)]
        each_live(lambda mb: _finalize_r3(cx, mb, mb.split_quot_poly_comms))
        mark_round("round3", w0, time.perf_counter() - p0)
        each_live(lambda mb: _save_member(cx, mb, 3))

    # --- Round 4: evaluations (one launch across all members) ---------------
    w0, p0 = begin_round()
    if live:
        def r4a(mb):
            mb.zeta = mb.transcript.get_and_append_challenge(b"zeta")
        each_live(r4a)
    if live:
        pairs = []
        for mb in live:
            pairs.extend(
                [(w, mb.zeta) for w in mb.wire_polys]
                + [(s, mb.zeta) for s in sigma_h[:num_wire_types - 1]]
                + [(mb.permutation_poly,
                    mb.zeta * domain.group_gen % R_MOD)])
        feed()
        evals = backend.eval_many_h(pairs)
        fed(4)
        per = 2 * num_wire_types  # 5 wires + 4 sigmas + z_next
        for j, mb in enumerate(live):
            mb._evs = evals[per * j:per * (j + 1)]
        each_live(lambda mb: _finalize_r4(cx, mb, mb._evs))
        mark_round("round4", w0, time.perf_counter() - p0)
        each_live(lambda mb: _save_member(cx, mb, 4))

    # --- Round 5: linearization + openings (one commit launch) --------------
    w0, p0 = begin_round()
    if live:
        def r5a(mb):
            vanish_eval = (pow(mb.zeta, n, R_MOD) - 1) % R_MOD
            feed()
            lin_poly = _linearization_poly(
                backend, pk, sel_h, sigma_h, n, mb.beta, mb.gamma,
                mb.alpha, mb.zeta, vanish_eval, mb.wires_evals,
                mb.wire_sigma_evals, mb.perm_next_eval,
                mb.permutation_poly, mb.split_quot_polys)
            v = mb.transcript.get_and_append_challenge(b"v")
            polys = ([lin_poly] + mb.wire_polys
                     + sigma_h[:num_wire_types - 1])
            coeffs = []
            c = 1
            for _ in polys:
                coeffs.append(c)
                c = c * v % R_MOD
            batch_poly = backend.lin_comb_h(polys, coeffs)
            mb.witness_poly = backend.synth_div_h(batch_poly, mb.zeta)
            mb.shifted_witness_poly = backend.synth_div_h(
                mb.permutation_poly, mb.zeta * domain.group_gen % R_MOD)
        each_live(r5a)
    if live:
        comms = commit_many(ck, [h for mb in live
                                 for h in (mb.witness_poly,
                                           mb.shifted_witness_poly)])
        fed(5)
        for j, mb in enumerate(live):
            mb._open_comms = (comms[2 * j], comms[2 * j + 1])

        def r5b(mb):
            _finalize_r5(cx, mb, mb._open_comms)
            proofs[mb.i] = mb.proof
        each_live(r5b)
        mark_round("round5", w0, time.perf_counter() - p0)

    return proofs, errors


class PipelinedProver:
    """Round-pipelined driver: up to `depth` members in flight, each at
    its own stage. Launch halves run on a single-worker executor — THE
    device queue, which preserves per-member launch order and mirrors how
    an accelerator serializes dispatched work — while the driver thread
    runs host-finalize halves (transcript hashing, challenge derivation,
    checkpoint encode + fsync). A member's device results are forced only
    at its OWN finalize, so a younger member's launches keep the device
    queue full while an older member's host work runs: the round barrier
    of the lockstep path becomes a per-member stage latch.

    Byte-identity argument: each member's mutation happens either in its
    launch half (executor thread) or its finalize half (driver thread),
    and the driver never submits stage k+1 before finalize k returned —
    per-member op order is EXACTLY the sequential prover's, and no state
    crosses members. Pipelining changes only the interleaving between
    members, which no per-member state observes.

    observer: optional callable; called once per completed stage with
    {round, depth, stage_wait_s, force_wait_s, finalize_s} (finalize_s
    runs through the round's checkpoint latch) — the pool turns these
    into the pipeline_* metrics."""

    def __init__(self, backend, depth=None, abort_on=(), observer=None):
        self.backend = backend
        self.depth = max(1, int(depth if depth is not None
                                else PIPELINE_DEPTH))
        self.abort_on = tuple(abort_on)
        self.observer = observer
        self._ctxs = {}

    def _ctx(self, pk):
        # per-pk stage context, cached so coalesced mixed-shape members
        # of the same key reuse domains + device-side pk handles
        cx = self._ctxs.get(id(pk))
        if cx is None:
            cx = self._ctxs[id(pk)] = _ProveCtx(pk, self.backend)
        return cx

    def run(self, rngs, circuits, pks, tracers, checkpoints,
            proofs, errors):
        queue = deque()
        for i, ckt in enumerate(circuits):
            mb = _Member(i, rngs[i], ckt, tracers[i], checkpoints[i])
            mb.cx = self._ctx(pks[i])
            if mb.checkpoint is not None and \
                    getattr(mb.checkpoint, "has_snapshot",
                            lambda: False)():
                # mid-prove state exists: resume through the sequential
                # prover up front, whose restore path is the pinned
                # contract — a resumed member never re-enters the pipeline
                try:
                    proofs[i] = prove(mb.rng, mb.ckt, mb.cx.pk,
                                      self.backend, tracer=mb.tr,
                                      checkpoint=mb.checkpoint)
                except self.abort_on:
                    raise
                except Exception as e:
                    errors[i] = e
                continue
            # from here on the member is in one of its own top-level
            # spans or waiting for its turn: `pipeline_wait`, by stamps
            mb.tr.waits = "pipeline_wait"
            mb.tr.park("pipeline_wait")
            mb.transcript.append_vk_and_pub_input(mb.cx.pk.vk, mb.pub)
            if mb.checkpoint is not None:
                # round-0 control point, parity with prove()
                try:
                    with mb.tr.span("guard_open"):
                        mb.fp = workload_fingerprint(mb.cx.pk.vk, mb.pub)
                        mb.checkpoint.load(mb.fp)
                except self.abort_on:
                    raise
                except Exception as e:
                    errors[i] = e
                    continue
            mb.stage = 0
            queue.append(mb)

        inflight = []  # admission order; [0] is the oldest member

        ex = ThreadPoolExecutor(max_workers=1)

        def submit(mb):
            # the round span covers this member's launch half only; its
            # finalize half gets its own roundN_finalize span, and the
            # round's device-true time lands on the device/* and kernels/*
            # events — so a pipelined trace never double-books overlapped
            # wall time
            mb._fut = ex.submit(_STAGES[mb.stage].run_launch, mb.cx, mb)

        try:
            while queue or inflight:
                while queue and len(inflight) < self.depth:
                    nxt = queue.popleft()
                    submit(nxt)
                    inflight.append(nxt)
                # finalize the oldest READY member (admission order breaks
                # ties): forcing only at a member's own finalize is the
                # pipeline — while this member's host work runs, the
                # executor keeps draining younger members' launches
                mb = next((m for m in inflight if m._fut.done()),
                          inflight[0])
                st = _STAGES[mb.stage]
                t0 = time.perf_counter()
                t1 = force_s = None
                try:
                    pending = mb._fut.result()
                    wait_s = time.perf_counter() - t0
                    t1 = time.perf_counter()
                    with mb.tr.span(st.name + "_finalize"):
                        values = pending.force()
                        force_s = time.perf_counter() - t1
                        st.finalize(mb.cx, mb, values)
                    st.latch(mb.cx, mb)
                except self.abort_on:
                    raise
                except Exception as e:
                    # member-local failure (kill/timeout at ITS latch):
                    # record, drop, and let the rest of the pipeline run
                    errors[mb.i] = e
                    inflight.remove(mb)
                    continue
                fin_s = time.perf_counter() - t1
                if self.observer is not None:
                    self.observer({
                        "round": st.no,
                        "depth": len(inflight),
                        "stage_wait_s": wait_s,
                        "force_wait_s": force_s,
                        "finalize_s": fin_s,
                    })
                mb.stage += 1
                if mb.stage >= len(_STAGES):
                    proofs[mb.i] = mb.proof
                    inflight.remove(mb)
                else:
                    submit(mb)
        finally:
            # abort (drain) or crash: cancel queued launches, wait out the
            # one in flight — members park at their own last-saved latch
            ex.shutdown(wait=True, cancel_futures=True)
        return proofs, errors


def prove_pipelined(rngs, circuits, pk, backend, tracers=None,
                    checkpoints=None, abort_on=(), depth=None,
                    observer=None):
    """N TurboPlonk proofs through the round PIPELINE (PipelinedProver):
    members need not share a shape — `pk` may be one key or a per-member
    list, which is how the pool coalesces mixed small/mid traffic from
    the dispatch queue into one pipelined attempt.

    Same failure contract as prove_many: member-local exceptions are
    recorded in `errors` and the survivors finish; `abort_on` types
    propagate and every in-flight member parks at its own next stage
    latch (its last saved round checkpoint). Members that already have a
    snapshot resume through sequential `prove` up front.

    With DPT_PIPELINE=0 this degrades to a plain sequential prove loop —
    the bit-parity escape hatch (the pipeline is byte-identical anyway;
    the knob exists so an operator can excise the machinery entirely).

    Returns (proofs, errors) per-member lists."""
    N = len(circuits)
    rngs = list(rngs)
    tracers = list(tracers) if tracers is not None else [None] * N
    checkpoints = (list(checkpoints) if checkpoints is not None
                   else [None] * N)
    pks = list(pk) if isinstance(pk, (list, tuple)) else [pk] * N
    proofs = [None] * N
    errors = [None] * N
    if not PIPELINE:
        for i in range(N):
            try:
                proofs[i] = prove(rngs[i], circuits[i], pks[i], backend,
                                  tracer=tracers[i],
                                  checkpoint=checkpoints[i])
            except abort_on:
                raise
            except Exception as e:
                errors[i] = e
        return proofs, errors
    drv = PipelinedProver(backend, depth=depth, abort_on=abort_on,
                          observer=observer)
    return drv.run(rngs, circuits, pks, tracers, checkpoints,
                   proofs, errors)


def _linearization_poly(backend, pk, sel_h, sigma_h, n, beta, gamma, alpha,
                        zeta, vanish_eval, wires_evals, wire_sigma_evals,
                        perm_next_eval, permutation_poly, split_quot_polys):
    """lin_poly assembly (reference src/dispatcher2.rs:565-633): all scalar
    coefficients computed on host, one backend linear combination."""
    a, b, c, d, e = wires_evals
    ab = a * b % R_MOD
    cd = c * d % R_MOD

    polys = []
    coeffs = []

    def term(h, cf):
        polys.append(h)
        coeffs.append(cf % R_MOD)

    term(sel_h[Q_LC], a)
    term(sel_h[Q_LC + 1], b)
    term(sel_h[Q_LC + 2], c)
    term(sel_h[Q_LC + 3], d)
    term(sel_h[Q_MUL], ab)
    term(sel_h[Q_MUL + 1], cd)
    term(sel_h[Q_HASH], pow(a, 5, R_MOD))
    term(sel_h[Q_HASH + 1], pow(b, 5, R_MOD))
    term(sel_h[Q_HASH + 2], pow(c, 5, R_MOD))
    term(sel_h[Q_HASH + 3], pow(d, 5, R_MOD))
    term(sel_h[Q_ECC], ab * cd % R_MOD * e % R_MOD)
    term(sel_h[Q_O], -e)
    term(sel_h[Q_C], 1)

    lagrange_1_eval = vanish_eval * fr_inv(
        n % R_MOD * ((zeta - 1) % R_MOD) % R_MOD) % R_MOD
    coeff_z = alpha
    for w_eval, ki in zip(wires_evals, pk.vk.k):
        coeff_z = coeff_z * ((w_eval + beta * ki % R_MOD * zeta + gamma) % R_MOD) % R_MOD
    coeff_z = (coeff_z + alpha * alpha % R_MOD * lagrange_1_eval) % R_MOD
    term(permutation_poly, coeff_z)

    coeff_sigma = alpha * beta % R_MOD * perm_next_eval % R_MOD
    for w_eval, s_eval in zip(wires_evals[:NUM_WIRE_TYPES - 1], wire_sigma_evals):
        coeff_sigma = coeff_sigma * ((w_eval + beta * s_eval + gamma) % R_MOD) % R_MOD
    term(sigma_h[NUM_WIRE_TYPES - 1], -coeff_sigma)

    zeta_np2 = (vanish_eval + 1) * zeta % R_MOD * zeta % R_MOD
    cf = (-vanish_eval) % R_MOD
    for poly in split_quot_polys:
        term(poly, cf)
        cf = cf * zeta_np2 % R_MOD

    return backend.lin_comb_h(polys, coeffs)
