"""Quickstart: build a ChamVS index, search it, check recall — 60 seconds.

Uses the unified ``repro.serve`` surface: ``DatastoreBuilder`` owns the
train-quantizers/build-shards recipe, and searches go through the
``Retriever`` protocol that every serving deployment speaks.

    PYTHONPATH=src python examples/quickstart.py
"""
import sys
sys.path.insert(0, "src")

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.ivfpq import exact_search
from repro.serve import DatastoreBuilder

key = jax.random.PRNGKey(0)

# 1) a database: 16k vectors in 64-d, with cluster structure
centers = jax.random.normal(key, (64, 64))
assign = jax.random.randint(jax.random.PRNGKey(1), (16384,), 0, 64)
vecs = centers[assign] + 0.1 * jax.random.normal(jax.random.PRNGKey(2),
                                                 (16384, 64))

# 2) train IVF-PQ quantizers and build 4 "memory node" shards
#    (paper partition scheme 1: every IVF list striped across all shards)
builder = DatastoreBuilder(dim=64, nlist=64, m=16, list_cap=512,
                           num_shards=4, kmeans_iters=10, seed=0)
ds = builder.build(np.asarray(vecs), train_vectors=np.asarray(vecs[:8192]))
print(f"index: {ds.index_cfg.nlist} lists, {ds.num_shards} memory nodes, "
      f"{ds.index_cfg.db_bytes_per_vector():.0f} B/vector")

# 3) search: scan the IVF index, stream PQ codes, merge truncated top-k'
#    (through the Retriever protocol — same call the serving engine makes)
ccfg = ds.search_config(nprobe=16, k=32)
queries = vecs[:32] + 0.02
dists, ids = ds.retriever(ccfg).search(queries)

# 4) recall vs exact brute force: true top-10 found among the returned 32
_, true_ids = exact_search(vecs, queries, 10)
hits = float((ids[:, :, None] == true_ids[:, None, :]).any(1).mean())
print(f"search: k'={ccfg.k_prime(4)} per node (K={ccfg.k}); "
      f"R10@{ccfg.k}={hits:.3f}")
print("nearest ids[0]:", np.asarray(ids[0, :5]))

# 5) the same search through the Pallas near-memory kernel (interpreted on a
#    CPU host, compiled on a TPU)
ccfg_k = ds.search_config(nprobe=16, k=32, backend="pallas")
d2, i2 = ds.retriever(ccfg_k).search(queries)
print("pallas kernel agrees:", bool(jnp.allclose(dists, d2, rtol=1e-4)))
