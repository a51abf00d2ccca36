"""Cross-host artifact fetch: pull store blobs from a serving peer.

The client side of the STORE_FETCH wire tag (runtime/protocol.py): a fresh
or replacement host asks a peer that already holds an artifact — bucket
keys, an SRS, a mid-prove checkpoint — for its bytes instead of rebuilding
them. Cold start and cross-host resume become one network copy (ROADMAP
direction 2: store-backed distributed serving).

Trust model: the peer is inside the deployment but the network is not
infallible — every fetched blob is re-hashed locally and compared to the
digest the peer advertised BEFORE it is written into the local store, so
a truncated/garbled transfer is a loud error, never a poisoned cache
(the local store then re-verifies on every read, as always).

Servers: the proof service answers STORE_FETCH when started with a store
(service/server.py); runtime workers answer it when launched with
--store (runtime/worker.py) so the fleet can serve each other without
routing through the dispatcher.
"""

import hashlib
import os
import time

from ..runtime import native, protocol
from ..runtime.health import NullMetrics

# STORE_FETCH/STORE_LIST pseudo-key prefix for jax persistent-compile-
# cache FILES (they live under the store root but outside the artifact
# manifest): `jaxcache:<cache-relative posix path>`. Syncing these is
# the compiled-exec half of warm rejoin — a replacement worker reaches
# first-kernel-launch on compile-cache HITS instead of minutes of
# recompiles (ROADMAP direction-2 remainder).
JAX_CACHE_PREFIX = "jaxcache:"


class FetchError(RuntimeError):
    pass


def serve_fetch(store, payload, conn, metrics=None,
                no_store_reason="no store on this server"):
    """Answer one STORE_FETCH request on `conn` — the server side of
    `fetch_blob`, shared by the proof service frontend
    (service/server.py) and runtime workers launched with --store
    (runtime/worker.py) so the two servers cannot skew. Advertises the
    digest the store just verified the blob against (`get_entry`)
    instead of re-hashing a possibly multi-MB blob per fetch.
    `jaxcache:<rel>` pseudo-keys serve compile-cache FILES (hashed here
    — they carry no manifest digest; escaping names are a miss)."""
    metrics = metrics or NullMetrics()
    if store is None:
        conn.send(protocol.ERR, protocol.encode_json(
            {"reason": no_store_reason}))
        return
    key = protocol.decode_json(payload).get("key")
    if key and key.startswith(JAX_CACHE_PREFIX):
        blob = store.jax_cache_read(key[len(JAX_CACHE_PREFIX):])
        if blob is None:
            metrics.inc("store_fetch_misses")
            conn.send(protocol.ERR, protocol.encode_json(
                {"reason": f"unknown key {key!r}"}))
            return
        metrics.inc("store_fetch_served")
        metrics.inc("store_fetch_bytes", len(blob))
        header = {"key": key, "digest": hashlib.sha256(blob).hexdigest(),
                  "meta": {"kind": "jax_cache"}}
        conn.send(protocol.OK, protocol.encode_result(header, blob))
        return
    hit = store.get_entry(key) if key else None
    if hit is None:
        metrics.inc("store_fetch_misses")
        conn.send(protocol.ERR, protocol.encode_json(
            {"reason": f"unknown key {key!r}"}))
        return
    blob, digest, meta = hit
    metrics.inc("store_fetch_served")
    metrics.inc("store_fetch_bytes", len(blob))
    header = {"key": key, "digest": digest, "meta": meta}
    conn.send(protocol.OK, protocol.encode_result(header, blob))


def serve_list(store, payload, conn, metrics=None,
               no_store_reason="no store on this server"):
    """Answer one STORE_LIST request: manifest keys plus jaxcache:<rel>
    pseudo-keys, filtered by the requested prefix — how a joining worker
    learns what a roster peer can serve it for warm rejoin."""
    metrics = metrics or NullMetrics()
    if store is None:
        conn.send(protocol.ERR, protocol.encode_json(
            {"reason": no_store_reason}))
        return
    prefix = protocol.decode_json(payload).get("prefix", "") or ""
    keys = [k for k in store.keys() if k.startswith(prefix)]
    keys += [k for k in (JAX_CACHE_PREFIX + rel
                         for rel in store.jax_cache_list())
             if k.startswith(prefix)]
    metrics.inc("store_list_served")
    conn.send(protocol.OK, protocol.encode_json({"keys": sorted(keys)}))


def fetch_blob(host, port, key, timeout_ms=30000):
    """-> (meta dict, blob bytes) from the peer, digest-verified.

    Raises FetchError when the peer lacks the key or the transfer fails
    integrity (callers treat either as a miss and fall back to a build).
    """
    # bound the dial too: peer fetch may run under the scheduler's bucket
    # lock, and a partitioned (SYN-dropped) peer must cost a bounded wait
    # there, not the OS connect default of minutes
    conn = native.connect(host, port, timeout_ms=timeout_ms)
    try:
        if timeout_ms:
            conn.set_timeout(timeout_ms)
        conn.send(protocol.STORE_FETCH, protocol.encode_json({"key": key}))
        rtag, rpayload = conn.recv()
    finally:
        conn.close()
    if rtag != protocol.OK:
        raise FetchError(
            f"peer {host}:{port} has no {key!r}: "
            f"{protocol.decode_json(rpayload).get('reason')}")
    header, blob = protocol.decode_result(rpayload)
    digest = hashlib.sha256(blob).hexdigest()
    if digest != header.get("digest"):
        raise FetchError(
            f"digest mismatch fetching {key!r} from {host}:{port} "
            f"({digest[:12]} != {str(header.get('digest'))[:12]})")
    return header.get("meta") or {}, blob


def fetch_into(store, host, port, key, timeout_ms=30000):
    """Fetch `key` from the peer into the local store. Returns the blob,
    or None when the peer lacks it / the transfer failed verification
    (logged by the caller's metrics, not raised: peer fetch is an
    optimization tier, the build tier still exists below it)."""
    try:
        meta, blob = fetch_blob(host, port, key, timeout_ms=timeout_ms)
    except (FetchError, ConnectionError, OSError):
        return None
    store.put(key, blob, meta=meta)
    return blob


def list_keys(host, port, prefix="", timeout_ms=10000):
    """Peer's STORE_LIST for one prefix -> [key]. Raises FetchError when
    the peer serves no store (callers treat it as an empty peer)."""
    conn = native.connect(host, port, timeout_ms=timeout_ms)
    try:
        if timeout_ms:
            conn.set_timeout(timeout_ms)
        conn.send(protocol.STORE_LIST,
                  protocol.encode_json({"prefix": prefix}))
        rtag, rpayload = conn.recv()
    finally:
        conn.close()
    if rtag != protocol.OK:
        raise FetchError(
            f"peer {host}:{port} cannot list: "
            f"{protocol.decode_json(rpayload).get('reason')}")
    return protocol.decode_json(rpayload).get("keys", [])


def sync_jax_cache(store, host, port, timeout_ms=30000, keys=None):
    """Copy the peer's jax persistent-compile-cache entries this store
    lacks (digest-verified per file, atomic installs). Returns the count
    copied. Cache entries are keyed by content inside jax, so an entry
    already present locally is never re-fetched, and a half-synced cache
    is still strictly warmer than an empty one. `keys`: a key list the
    caller already fetched from this peer (warm_sync passes its
    unprefixed listing, saving a second STORE_LIST round trip)."""
    copied = 0
    if keys is None:
        keys = list_keys(host, port, prefix=JAX_CACHE_PREFIX,
                         timeout_ms=timeout_ms)
    for key in keys:
        if not key.startswith(JAX_CACHE_PREFIX):
            continue
        rel = key[len(JAX_CACHE_PREFIX):]
        if store.jax_cache_has(rel):
            continue
        try:
            _meta, blob = fetch_blob(host, port, key, timeout_ms=timeout_ms)
            store.jax_cache_write(rel, blob)
        except (FetchError, ConnectionError, OSError, ValueError):
            continue  # one bad file must not abort the sync
        copied += 1
    return copied


# artifact-key prefixes a joining worker pulls from roster peers: bucket
# keys carry the SRS + proving/verifying keys (keycache.py layout) — the
# expensive-to-rebuild state. Checkpoints/proofs stay fetch-on-demand
# (they are job-scoped, not shape-scoped).
WARM_SYNC_PREFIXES = tuple(
    p for p in os.environ.get(
        "DPT_WARM_SYNC_PREFIXES", "bucket:").split(",") if p)


def warm_sync(store, peers, prefixes=None, timeout_ms=10000):
    """Warm-rejoin sync: pull every missing `prefixes` artifact AND the
    jax compile-cache entries from each peer in order. Per-peer/per-key
    failures are skipped — the sync is an accelerator, never a gate.
    Returns a stats dict ({warm_rejoin_s, artifacts, jax_cache_files,
    peers, errors}) for the JOIN phase=ready report."""
    t0 = time.monotonic()
    prefixes = WARM_SYNC_PREFIXES if prefixes is None else tuple(prefixes)
    stats = {"artifacts": 0, "jax_cache_files": 0, "peers": 0, "errors": 0}
    have = set(store.keys())
    for host, port in peers:
        try:
            keys = list_keys(host, port, timeout_ms=timeout_ms)
        except (FetchError, ConnectionError, OSError):
            stats["errors"] += 1
            continue
        stats["peers"] += 1
        for key in keys:
            if key in have or not key.startswith(prefixes):
                continue
            if fetch_into(store, host, port, key,
                          timeout_ms=timeout_ms) is not None:
                have.add(key)
                stats["artifacts"] += 1
        try:
            stats["jax_cache_files"] += sync_jax_cache(
                store, host, port, timeout_ms=timeout_ms, keys=keys)
        except (FetchError, ConnectionError, OSError):
            stats["errors"] += 1
    stats["warm_rejoin_s"] = round(time.monotonic() - t0, 6)
    return stats
