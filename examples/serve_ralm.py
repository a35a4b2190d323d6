"""End-to-end RALM serving (paper Fig. 3 workflow) with batched requests,
through the unified ``repro.serve`` API.

Demonstrates the paper's central behavioural claim at desk scale: an
UNTRAINED tiny LM + a retrieval datastore reproduces memorized sequences,
because the knowledge lives in the database, not the weights (knowledge
editing without retraining, paper §1). The same ``RalmEngine`` runs
monolithic (one mesh) or disaggregated (LM pool + retrieval pool) —
identical tokens either way.

    PYTHONPATH=src python examples/serve_ralm.py [--disaggregate]

``--gateway`` instead serves the same engine over HTTP (OpenAI-style
``/v1/completions`` with SSE streaming; see docs/serving.md):

    PYTHONPATH=src python examples/serve_ralm.py --gateway --port 8000
    curl -N localhost:8000/v1/completions -H 'Content-Type: application/json' \
      -d '{"prompt": [17, 52, 31, 30, 27, 18, 55, 38],
           "max_tokens": 8, "stream": true}'
    # data: {"id": "cmpl-0", ..., "choices": [{"text": " 5", ...}]}
    # ...
    # data: [DONE]
"""
import argparse
import dataclasses
import sys
sys.path.insert(0, "src")

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_arch
from repro.models import transformer as tf
from repro.serve import (DatastoreBuilder, RagConfig, RalmEngine,
                         ServiceConfig)

ap = argparse.ArgumentParser()
ap.add_argument("--disaggregate", action="store_true")
ap.add_argument("--async-retrieval", action="store_true",
                help="serve searches through a RetrievalService (wave "
                     "coalescing + LRU result cache)")
ap.add_argument("--per-sequence", action="store_true",
                help="use the per-sequence oracle decode loop instead of "
                     "wave-batched decode over the KV-cache pool")
ap.add_argument("--kv-slots", type=int, default=None,
                help="fix the KV pool capacity in prompt rows (admission "
                     "defers when full); default grows on demand")
ap.add_argument("--gateway", action="store_true",
                help="serve the engine over HTTP instead of running the "
                     "batch demo: OpenAI-style /v1/completions with SSE "
                     "streaming, per-tenant admission, load shedding")
ap.add_argument("--port", type=int, default=8000,
                help="gateway listen port (with --gateway)")
args = ap.parse_args()
wave = not args.per_sequence

# tiny decoder RALM (paper Dec-S family, reduced)
cfg = dataclasses.replace(get_arch("dec_s").reduced, vocab_size=64)
params = tf.init_params(jax.random.PRNGKey(0), cfg)

# a corpus with deterministic structure: token t -> (3t+1) mod 64
rng = np.random.default_rng(0)
start = rng.integers(0, 64, size=(64,))
seqs = [start]
for _ in range(31):
    seqs.append((3 * seqs[-1] + 1) % 64)
corpus = np.stack(seqs, axis=1).astype(np.int32)

# deployment shape first: disaggregated needs one datastore shard per
# retrieval-pool device (memory node)
disaggregate = args.disaggregate and len(jax.devices()) >= 2
ret_devices = min(2, len(jax.devices()) - 1) if disaggregate else 1
num_shards = ret_devices if disaggregate else 2

# datastore: hidden state of every prefix -> next token (kNN-LM, interval 1)
ds = DatastoreBuilder(dim=cfg.d_model, nlist=8, m=8, list_cap=512,
                      num_shards=num_shards).from_corpus(params, cfg, corpus)
ccfg = ds.search_config(nprobe=4, k=8)
print(f"datastore: {ds.num_vectors} vectors, {ds.num_shards} memory nodes, "
      f"k'={ccfg.k_prime(ds.num_shards)}")

rag = RagConfig(mode="knnlm", interval=1, k=8, lam=0.999, temperature=1.0)

if disaggregate:
    if args.async_retrieval:
        import warnings
        warnings.warn("--async-retrieval is not wired into the "
                      "disaggregated path; using the synchronous "
                      "DistributedRetriever", RuntimeWarning)
    engine = RalmEngine.disaggregated(
        params, cfg, rag, ds.params, ds.shards, ccfg,
        payload_tokens=ds.payload_tokens, lm_devices=1,
        ret_devices=ret_devices, wave=wave, kv_slots=args.kv_slots)
    print(f"disaggregated pools: "
          f"LM={engine.backend.lm_mesh.devices.size} dev, "
          f"retrieval={engine.backend.ret_mesh.devices.size} dev")
elif args.async_retrieval:
    # searches coalesce per scheduler wave into one batched dispatch
    engine = RalmEngine.monolithic(
        params, cfg, rag,
        retriever=ds.async_retriever(ccfg,
                                     service_cfg=ServiceConfig(
                                         cache_entries=1024)),
        wave=wave, kv_slots=args.kv_slots)
else:
    engine = RalmEngine.monolithic(params, cfg, rag,
                                   retriever=ds.retriever(ccfg),
                                   wave=wave, kv_slots=args.kv_slots)

if args.gateway:
    # same engine, served over HTTP: streaming SSE completions, tenant
    # quotas + queue-depth backpressure, retrieval-quality degradation
    # under load (docs/serving.md, "The front door")
    from repro.serve import Gateway, GatewayConfig
    Gateway(engine, GatewayConfig(port=args.port)).serve_forever()
    sys.exit(0)

# two request batches in flight at once: the scheduler pipelines them
outs = engine.generate_batches([jnp.asarray(corpus[:4, :8]),
                                jnp.asarray(corpus[4:8, :8])], steps=8)
out = outs[0]

acc = (out[:, 8:16] == corpus[:4, 8:16]).mean()
print(f"retrieval-augmented continuation accuracy: {acc:.2f} "
      f"(untrained LM alone would be ~{1/64:.3f})")
print("generated :", out[0, 8:16].tolist())
print("ground tru:", corpus[0, 8:16].tolist())

if engine.pool is not None:   # wave mode: the whole batch rides one dispatch
    ps = engine.pool.stats
    print(f"kv pool: {engine.pool.capacity} slots "
          f"(high water {ps.high_water}), {ps.waves} waves of "
          f"{ps.mean_wave():.1f} rows avg in {engine.decode_dispatches} "
          f"LM dispatches, buckets {sorted(ps.buckets)}")
service = getattr(engine.retriever, "service", None)
if service is not None:   # async path only (--disaggregate has no service)
    st = service.stats
    print(f"retrieval service: {st.batched_rows} query rows coalesced "
          f"into {st.num_batches} kernel dispatches "
          f"({st.coalescing_factor():.1f} rows/dispatch, "
          f"{st.cache_hits} cache hits)")
