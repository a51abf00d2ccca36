"""Artifact store + warm start: the contracts the serving path leans on.

- bucket keys round-trip the store ELEMENT-IDENTICAL (proofs made with a
  disk-loaded proving key are byte-equal to fresh-key proofs, so golden
  fixtures and checkpoint fingerprints survive a server restart);
- the store detects corrupted/truncated artifacts, deletes them, and the
  cache falls through to a fresh build instead of crashing;
- LRU byte-budget eviction removes least-recently-USED entries first;
- a second BucketCache over the same store root (the restarted-server
  case) serves previously seen shapes from disk without ever calling
  build_bucket_keys;
- the in-memory tier is bounded (entry cap + eviction counter).

Pure host (tiny toy domains, no XLA) — runs in the fast host tier.
"""

import random

import pytest

from distributed_plonk_tpu.proof_io import deserialize_proof, serialize_proof
from distributed_plonk_tpu.prover import prove
from distributed_plonk_tpu.service.jobs import (JobSpec, build_bucket_keys,
                                                build_circuit, shape_key)
from distributed_plonk_tpu.service.metrics import Metrics
from distributed_plonk_tpu.service.scheduler import BucketCache
from distributed_plonk_tpu.service import scheduler as scheduler_mod
from distributed_plonk_tpu.store import (ArtifactStore, bucket_store_key,
                                         deserialize_bucket, load_bucket,
                                         serialize_bucket, store_bucket)
from distributed_plonk_tpu.backend.python_backend import PythonBackend
from distributed_plonk_tpu.verifier import verify

TOY = {"kind": "toy", "gates": 8}


def _spec(seed=0, **over):
    d = dict(TOY, seed=seed)
    d.update(over)
    return JobSpec.from_wire(d)


@pytest.fixture(scope="module")
def built():
    """One shared key build for the module (the expensive part)."""
    return build_bucket_keys(_spec())


# --- serialization round trip ------------------------------------------------

def test_bucket_roundtrip_element_identical(built):
    srs, pk, vk = built
    srs2, pk2, vk2 = deserialize_bucket(serialize_bucket(srs, pk, vk))
    assert srs2.powers_of_g1 == srs.powers_of_g1
    assert (srs2.g2, srs2.tau_g2) == (srs.g2, srs.tau_g2)
    assert pk2.ck == pk.ck
    assert pk2.selectors == pk.selectors and pk2.sigmas == pk.sigmas
    assert pk2.domain.size == pk.domain.size
    assert vk2.selector_comms == vk.selector_comms
    assert vk2.sigma_comms == vk.sigma_comms
    assert (vk2.domain_size, vk2.num_inputs, vk2.k) == \
        (vk.domain_size, vk.num_inputs, vk.k)


def test_proof_bytes_identical_with_loaded_keys(built, tmp_path):
    srs, pk, vk = built
    store = ArtifactStore(str(tmp_path))
    key = shape_key(_spec())
    store_bucket(store, key, srs, pk, vk, build_s=0.5)
    _srs2, pk2, vk2, meta = load_bucket(store, key)
    assert meta["build_s"] == 0.5

    spec = _spec(seed=7)
    want = serialize_proof(
        prove(random.Random(7), build_circuit(spec), pk, PythonBackend()))
    ckt = build_circuit(spec)
    got = serialize_proof(
        prove(random.Random(7), ckt, pk2, PythonBackend()))
    assert got == want
    assert verify(vk2, ckt.public_input(), deserialize_proof(got),
                  rng=random.Random(1))


# --- integrity: corruption detect-and-rebuild --------------------------------

def _corrupt_object(store, key, mutate):
    ent = store._manifest["entries"][bucket_store_key(key)]
    path = store._obj_path(ent["digest"])
    blob = open(path, "rb").read()
    with open(path, "wb") as f:
        f.write(mutate(blob))


@pytest.mark.parametrize("mutate", [
    lambda b: b[: len(b) // 2],                     # truncation
    lambda b: b[:100] + bytes([b[100] ^ 0xFF]) + b[101:],  # bit damage
], ids=["truncated", "flipped"])
def test_corrupt_artifact_rebuilds(built, tmp_path, mutate):
    srs, pk, vk = built
    metrics = Metrics()
    store = ArtifactStore(str(tmp_path), metrics=metrics.scoped("store"))
    key = shape_key(_spec())
    store_bucket(store, key, srs, pk, vk)
    _corrupt_object(store, key, mutate)

    # the store detects, logs, deletes — and reports a miss
    assert load_bucket(store, key) is None
    snap = metrics.snapshot()
    assert snap["counters"]["store_corrupt"] == 1
    assert bucket_store_key(key) not in store.keys()

    # ... so the cache's build tier repopulates instead of crashing
    cache = BucketCache(metrics, store=store)
    res = cache.get(_spec())
    assert res.vk.selector_comms == vk.selector_comms
    snap = metrics.snapshot()
    assert snap["counters"]["bucket_misses"] == 1
    assert load_bucket(store, key) is not None  # healed on disk


def test_undeserializable_blob_is_dropped(tmp_path):
    store = ArtifactStore(str(tmp_path))
    key = shape_key(_spec())
    store.put(bucket_store_key(key), b"not a bucket blob at all")
    assert load_bucket(store, key) is None  # parse fails -> treated as miss
    assert store.keys() == []               # and the stale entry is gone


# --- LRU byte-budget eviction ------------------------------------------------

def test_eviction_least_recently_used_first(tmp_path):
    metrics = Metrics()
    store = ArtifactStore(str(tmp_path), byte_budget=250,
                          metrics=metrics.scoped("store"))
    for name in ("a", "b", "c"):
        store.put(name, bytes(80), meta={"n": name})
    assert store.keys() == ["a", "b", "c"]
    assert store.get("a") is not None   # touch: a is now most recent
    store.put("d", bytes(80))           # 320 > 250: evict LRU until under
    assert store.keys() == ["a", "c", "d"]  # b (oldest-used) went first
    snap = metrics.snapshot()
    assert snap["counters"]["store_evictions"] == 1
    assert snap["gauges"]["store_bytes"] == 240
    store.put("e", bytes(200))          # forces out everything else but e
    assert "e" in store.keys()
    assert store.stats()["bytes"] <= 250


def test_orphaned_blobs_swept_on_open(tmp_path):
    import os
    store = ArtifactStore(str(tmp_path))
    store.put("k", b"payload")
    path = store._obj_path(store._manifest["entries"]["k"]["digest"])
    # simulate a manifest reset / lost writer race: entry gone, blob left
    os.remove(store._manifest_path)
    old = os.path.getmtime(path) - 3600
    os.utime(path, (old, old))  # past the sweep's age floor
    store2 = ArtifactStore(str(tmp_path))
    assert store2.keys() == []
    assert not os.path.exists(path)  # orphan reclaimed, budget stays honest


def test_just_written_entry_survives_tiny_budget(tmp_path):
    store = ArtifactStore(str(tmp_path), byte_budget=10)
    store.put("big", bytes(100))
    assert store.get("big") is not None  # never evict the entry just put


# --- jax compile-cache GC (shared byte budget) -------------------------------

def _fake_jax_cache(root, sizes):
    """Files under <root>/jax_cache/<fp>/ with staged mtimes (oldest
    first), mirroring the per-machine-fingerprint layout."""
    import os
    import time as _time
    d = os.path.join(str(root), "jax_cache", "fp0")
    os.makedirs(d, exist_ok=True)
    now = _time.time()
    paths = []
    for i, size in enumerate(sizes):
        p = os.path.join(d, f"exe{i}.bin")
        with open(p, "wb") as f:
            f.write(bytes(size))
        os.utime(p, (now - 1000 + i, now - 1000 + i))
        paths.append(p)
    return paths


def test_jax_cache_counts_against_budget_oldest_first(tmp_path):
    import os
    metrics = Metrics()
    store = ArtifactStore(str(tmp_path), byte_budget=300,
                          metrics=metrics.scoped("store"))
    store.put("key", bytes(100))
    paths = _fake_jax_cache(tmp_path, [100, 100, 100])  # 100 + 300 > 300
    # stats() reports the last-gauged total (no walk on the poll path);
    # the explicit accessor walks and refreshes it
    assert store.jax_cache_bytes() == 300
    assert store.stats()["jax_cache_bytes"] == 300
    removed = store.sweep_jax_cache()
    # artifact bytes (100) leave 200 for the cache: the OLDEST file goes
    assert removed == 1
    assert not os.path.exists(paths[0])
    assert os.path.exists(paths[1]) and os.path.exists(paths[2])
    # the manifest entry is untouched — executables yield before keys
    assert store.get("key") is not None
    assert metrics.snapshot()["counters"]["store_jax_cache_evictions"] == 1


def test_jax_cache_swept_on_open_and_put(tmp_path):
    import os
    _fake_jax_cache(tmp_path, [200, 200])
    store = ArtifactStore(str(tmp_path), byte_budget=250)
    # open-time sweep already bounded the cache
    assert store.stats()["jax_cache_bytes"] <= 250
    # a put() past the throttle window re-sweeps: shrink the budget's
    # free share by writing artifacts, with the throttle disabled
    store._jax_sweep_interval = 0.0
    store.put("a", bytes(200))
    assert store.stats()["jax_cache_bytes"] <= 50
    assert store.get("a") is not None


def test_jax_cache_untouched_without_budget(tmp_path):
    import os
    paths = _fake_jax_cache(tmp_path, [1 << 20])
    store = ArtifactStore(str(tmp_path))  # no budget: GC disabled
    assert store.sweep_jax_cache() == 0
    assert os.path.exists(paths[0])


# --- warm start across processes ---------------------------------------------

def test_second_cache_instance_hits_disk_skips_build(tmp_path, monkeypatch):
    m1 = Metrics()
    cache1 = BucketCache(m1, store=ArtifactStore(str(tmp_path)))
    res1 = cache1.get(_spec(seed=1))
    assert m1.snapshot()["counters"]["bucket_misses"] == 1

    # "restarted server": fresh store handle + fresh cache over the same
    # root; a rebuild here would defeat the whole subsystem, so make any
    # build attempt an error
    def boom(spec, backend=None):
        raise AssertionError("warm path called build_bucket_keys")

    monkeypatch.setattr(scheduler_mod.J, "build_bucket_keys", boom)
    m2 = Metrics()
    cache2 = BucketCache(m2, store=ArtifactStore(str(tmp_path)))
    res2 = cache2.get(_spec(seed=2))
    snap = m2.snapshot()
    assert snap["counters"]["bucket_disk_hits"] == 1
    assert "bucket_misses" not in snap["counters"]
    assert res2.vk.selector_comms == res1.vk.selector_comms
    assert res2.pk.ck == res1.pk.ck

    # memory tier on the second touch
    cache2.get(_spec(seed=3))
    assert m2.snapshot()["counters"]["bucket_hits"] == 1


def test_aot_warmup_reports_a_refused_stage_as_failed():
    """A stage the compiler refuses to the AOT warmers is not a warm
    shape: aot_warmup says "failed" and aot_errors hands out what the
    compiler said (chip_smoke.py and scripts/warmup.py stop on it)."""
    from distributed_plonk_tpu.store import aot_errors, aot_warmup

    class Refusing:
        name = "fake"

        def warm_stages(self, domain_size, ck=None):
            return {"ntt": {domain_size: {"compiled": 7, "failed": 1,
                                          "errors": ["Mosaic said no"]}},
                    "msm": {"compiled": 3, "failed": 0, "errors": []}}

    class Compiling(Refusing):
        def warm_stages(self, domain_size, ck=None):
            return {"ntt": {domain_size: {"compiled": 8, "failed": 0,
                                          "errors": []}}}

    report = aot_warmup(Refusing(), 16)
    assert report["aot"] == "failed"
    assert aot_errors(report) == ["Mosaic said no"]
    assert aot_warmup(Compiling(), 16)["aot"] == "ok"
    assert aot_warmup(object(), 16)["aot"] == "unsupported"


# --- bounded in-memory tier --------------------------------------------------

def test_memory_tier_entry_cap_and_eviction_counter():
    metrics = Metrics()
    cache = BucketCache(metrics, max_entries=1)  # no store: build tier only
    a, b = _spec(), _spec(gates=12)
    cache.get(a)
    cache.get(b)          # evicts a
    cache.get(b)          # memory hit
    cache.get(a)          # rebuilt (a was evicted)
    snap = metrics.snapshot()
    assert snap["counters"]["bucket_misses"] == 3
    assert snap["counters"]["bucket_mem_evictions"] == 2
    assert snap["counters"]["bucket_hits"] == 1
    assert snap["gauges"]["buckets_resident"] == 1


def test_concurrent_writers_merge_not_clobber(tmp_path):
    """Two writer PROCESSES' worth of store objects on one root: each
    holds a stale in-memory manifest while the other writes; the
    file-locked merge-on-load must preserve BOTH writers' entries
    (pre-PR-4 behavior: last manifest save wins and drops the other's)."""
    root = str(tmp_path / "s")
    a = ArtifactStore(root)
    b = ArtifactStore(root)  # loaded an empty manifest: stale vs a's puts
    a.put("ka", b"alpha")
    b.put("kb", b"beta")     # without merge-on-load this would drop "ka"
    a.put("ka2", b"alpha2")  # and this would drop "kb"
    fresh = ArtifactStore(root)
    assert set(fresh.keys()) >= {"ka", "kb", "ka2"}
    assert fresh.get("ka") == b"alpha"
    assert fresh.get("kb") == b"beta"
    # deletes are honored across writers too: disk is authoritative
    assert b.delete("ka")
    a.put("ka3", b"alpha3")
    assert "ka" not in ArtifactStore(root).keys()
    assert ArtifactStore(root).get("ka3") == b"alpha3"


def test_concurrent_writer_threads_stress(tmp_path):
    """Interleaved writers on separate store objects over one root: all
    entries written by either survive, under real thread interleaving."""
    import threading as _t
    root = str(tmp_path / "s2")
    stores = [ArtifactStore(root) for _ in range(2)]
    errs = []

    def writer(i):
        try:
            for k in range(12):
                stores[i].put(f"w{i}-{k}", b"x%d-%d" % (i, k))
        except Exception as e:  # pragma: no cover - failure reporting
            errs.append(e)

    ts = [_t.Thread(target=writer, args=(i,)) for i in range(2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert not errs
    final = ArtifactStore(root)
    assert set(final.keys()) == {f"w{i}-{k}"
                                 for i in range(2) for k in range(12)}
