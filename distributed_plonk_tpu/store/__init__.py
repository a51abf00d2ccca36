"""Artifact store + warm start: the persistence layer under the service.

The layer between key setup and the serving path that turns every server
restart and repeat circuit shape into a warm hit (ROADMAP: cold-start is
the dominant serving cost at scale):

    artifacts.py    content-addressed on-disk store — SHA-256 integrity,
                    atomic writes, versioned manifest, LRU byte budget
    keycache.py     SRS/proving-key/verifying-key <-> blob serialization
                    (encoding/proof_io wire idioms; load == fresh build,
                    element for element)
    warmstart.py    store-owned JAX persistent-compile-cache dir + AOT
                    stage precompilation per shape bucket

Consumers: service.scheduler.BucketCache (memory -> disk -> build tiers),
the WARMUP wire tag (service/server.py), scripts/warmup.py,
bench.py's cold-vs-warm service round trip, tests/test_store.py.
"""

from .artifacts import ArtifactStore
from .keycache import (bucket_store_key, serialize_bucket,
                       deserialize_bucket, store_bucket, load_bucket,
                       proof_store_key, store_proof, load_proof,
                       trace_store_key, store_trace, load_trace,
                       profile_store_key, store_profile, load_profile)
from .warmstart import (set_jax_cache_env, aot_errors, aot_warmup,
                        warm_spec)
from .remote import FetchError, fetch_blob, fetch_into

__all__ = [
    "ArtifactStore", "bucket_store_key", "serialize_bucket",
    "deserialize_bucket", "store_bucket", "load_bucket",
    "proof_store_key", "store_proof", "load_proof",
    "trace_store_key", "store_trace", "load_trace",
    "profile_store_key", "store_profile", "load_profile",
    "set_jax_cache_env", "aot_errors", "aot_warmup", "warm_spec",
    "FetchError", "fetch_blob", "fetch_into",
]
