"""What the frozen prover imports of the program's checkpoint module, and no
more: the fingerprint of a workload, and the encoders the prover calls only
when it is handed a checkpoint, which the reference never does. The
program's file (snapshots to disk, zip archives, numpy) is not copied."""

import hashlib

from .transcript import g1_to_bytes_compressed, fr_to_bytes


def workload_fingerprint(vk, pub_input):
    """Hash binding a checkpoint to its circuit + proving keys."""
    h = hashlib.sha256()
    h.update(vk.domain_size.to_bytes(8, "little"))
    h.update(vk.num_inputs.to_bytes(8, "little"))
    for ki in vk.k:
        h.update(fr_to_bytes(ki))
    for comm in list(vk.selector_comms) + list(vk.sigma_comms):
        h.update(g1_to_bytes_compressed(comm))
    for x in pub_input:
        h.update(fr_to_bytes(x))
    return h.hexdigest()


def dump_handle(backend, h):
    """Poly handle -> canonical (16, L) uint32 limb array (host numpy).
    Backends may provide a fast `dump_h`; the fallback goes through the
    universal lower() int-list protocol."""
    fn = getattr(backend, "dump_h", None)
    if fn is not None:
        return fn(h)
    from .backend.limbs import ints_to_limbs
    from .constants import FR_LIMBS
    return ints_to_limbs(backend.lower(h), FR_LIMBS)


def load_handle(backend, arr):
    fn = getattr(backend, "load_h", None)
    if fn is not None:
        return fn(arr)
    from .backend.limbs import limbs_to_ints
    return backend.lift(limbs_to_ints(arr))


def _point_enc(p):
    """Affine point (x, y) host ints or None (identity) -> JSON value."""
    return None if p is None else [hex(p[0]), hex(p[1])]


def _point_dec(v):
    return None if v is None else (int(v[0], 16), int(v[1], 16))
