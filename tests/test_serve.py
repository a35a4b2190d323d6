"""The unified ``repro.serve`` API: Retriever protocol, scheduler,
datastore builder, and monolithic/disaggregated parity.

Parity is the load-bearing claim (paper §3): disaggregation is a systems
transform, not a model change, so the same engine on split pools must
emit token-identical greedy sequences. The parity test runs in a
subprocess with 8 fake CPU devices (the XLA device count must be fixed
before jax initializes; same pattern as tests/test_distributed.py).
"""
import dataclasses
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.core.generate import RetrievalEngine, generate
from repro.models import transformer as tf
from repro.serve import (AsyncRetriever, DatastoreBuilder, LocalRetriever,
                         RagConfig, RalmEngine, RalmRequest, Retriever,
                         ServiceConfig)

SRC = str(pathlib.Path(__file__).resolve().parents[1] / "src")


def run_sub(code: str) -> str:
    # generous timeout: the 8-fake-device parity subprocess compiles two
    # full engines and takes ~8min on this host; CI runners are slower
    env = dict(PYTHONPATH=SRC, PATH="/usr/bin:/bin",
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               HOME="/tmp")
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=1800, env=env)
    assert p.returncode == 0, f"stdout:\n{p.stdout}\nstderr:\n{p.stderr[-3000:]}"
    return p.stdout


@pytest.fixture(scope="module")
def tiny_ralm():
    """Tiny decoder LM + datastore over a deterministic-bigram corpus
    (token t -> (3t+1) mod 64), built through DatastoreBuilder."""
    cfg = dataclasses.replace(get_arch("dec_s").reduced, vocab_size=64)
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    start = rng.integers(0, 64, size=(64,))
    corpus = [start]
    for _ in range(31):
        corpus.append((3 * corpus[-1] + 1) % 64)
    corpus = np.stack(corpus, axis=1).astype(np.int32)
    ds = DatastoreBuilder(dim=cfg.d_model, nlist=8, m=8,
                          list_cap=512).from_corpus(params, cfg, corpus)
    ccfg = ds.search_config(nprobe=4, k=8, backend="ref")
    rag = RagConfig(mode="knnlm", interval=1, k=8, lam=0.999,
                    temperature=1.0)
    return cfg, params, corpus, ds, ccfg, rag


# ---------------------------------------------------------------------------
# DatastoreBuilder
# ---------------------------------------------------------------------------

def test_datastore_roundtrip():
    """build() -> search() finds the indexed vectors; resolve() returns
    their payloads with missing-id masking folded in."""
    rng = np.random.default_rng(0)
    vecs = rng.normal(size=(512, 32)).astype(np.float32)
    payload = np.arange(512, dtype=np.int32) * 7
    ds = DatastoreBuilder(dim=32, nlist=8, m=8, list_cap=256,
                          num_shards=2).build(vecs, payload_tokens=payload)
    assert ds.num_vectors == 512 and ds.num_shards == 2
    ret = ds.retriever(ds.search_config(nprobe=8, k=4))
    assert isinstance(ret, Retriever)           # protocol conformance
    dists, ids = ret.search(jnp.asarray(vecs[:16]))
    assert ids.shape == (16, 4)
    # a vector queried against itself must be its own nearest neighbor
    hit = (np.asarray(ids) == np.arange(16)[:, None]).any(axis=1)
    assert hit.mean() > 0.9, hit
    # resolve: payload of the found ids, and -1 exactly where ids are -1
    toks = np.asarray(ret.resolve(ids))
    valid = np.asarray(ids) >= 0
    assert (toks[valid] == payload[np.asarray(ids)[valid]]).all()
    masked = ret.resolve(jnp.asarray([[0, -1, 3, -1]], jnp.int32))
    assert np.asarray(masked).tolist() == [[0, -1, 21, -1]]


def test_datastore_from_corpus_matches_manual(tiny_ralm):
    cfg, params, corpus, ds, ccfg, rag = tiny_ralm
    keys, nxt = DatastoreBuilder.corpus_keys(params, cfg, corpus)
    assert keys.shape == (64 * 31, cfg.d_model)
    assert ds.num_vectors == keys.shape[0]
    assert (np.asarray(ds.payload_tokens) == nxt).all()


def test_corpus_keys_batched_equals_one_forward(tiny_ralm, monkeypatch):
    """The batched, logit-free corpus pass (last batch padded) yields
    exactly the hidden states of one whole-corpus forward."""
    from repro.serve import datastore

    cfg, params, corpus, _, _, _ = tiny_ralm
    monkeypatch.setattr(datastore, "CORPUS_BATCH_TOKENS",
                        24 * corpus.shape[1])        # 3 batches of 24 docs
    keys, nxt = DatastoreBuilder.corpus_keys(params, cfg, corpus)
    _, _, hidden = tf.forward(params, cfg, tokens=jnp.asarray(corpus),
                              mode="train", return_hidden=True)
    want = np.asarray(hidden[:, :-1].astype(jnp.float32)).reshape(
        -1, cfg.d_model)
    np.testing.assert_allclose(keys, want, rtol=1e-5, atol=1e-5)
    assert (nxt == corpus[:, 1:].reshape(-1)).all()


def test_datastore_list_cap_from_data():
    """list_cap=None sizes the padded lists to the longest per-shard
    list slice, rounded up to 128 rows, and every vector is indexed."""
    rng = np.random.default_rng(1)
    vecs = rng.normal(size=(1500, 32)).astype(np.float32)
    ds = DatastoreBuilder(dim=32, nlist=4, m=8, list_cap=None,
                          num_shards=2).build(vecs)
    cap = ds.index_cfg.list_cap
    lens = np.stack([np.asarray(s.list_len) for s in ds.shards])
    assert cap % 128 == 0 and lens.max() <= cap < lens.max() + 128
    assert lens.sum() == 1500


def test_from_config_disaggregate_without_devices_raises(tiny_ralm):
    """A disaggregated config on a host with too few devices is an
    error, not a silent monolithic engine."""
    from repro.serve import EngineConfig

    cfg, params, _, ds, ccfg, rag = tiny_ralm
    with pytest.raises(ValueError, match="needs lm_devices"):
        RalmEngine.from_config(
            EngineConfig(model=cfg, rag=rag, disaggregate=True,
                         lm_devices=1, ret_devices=1), params, ds, ccfg)


# ---------------------------------------------------------------------------
# scheduler: continuous batching semantics
# ---------------------------------------------------------------------------

def test_scheduler_interleaved_submit_step(tiny_ralm):
    """submit() between step()s joins the running loop; sequences finish
    independently; interleaving never changes anyone's tokens."""
    cfg, params, corpus, ds, ccfg, rag = tiny_ralm
    ret = ds.retriever(ccfg)

    # reference: each request run alone
    ref_a = np.asarray(RalmEngine.monolithic(params, cfg, rag, ret)
                       .generate(jnp.asarray(corpus[:2, :8]), steps=6))
    ref_b = np.asarray(RalmEngine.monolithic(params, cfg, rag, ret)
                       .generate(jnp.asarray(corpus[2:4, :8]), steps=2))

    eng = RalmEngine.monolithic(params, cfg, rag, ret)
    rid_a = eng.submit(RalmRequest(prompt=jnp.asarray(corpus[:2, :8]),
                                   steps=6))
    done = eng.step() + eng.step()              # A advances 2 tokens
    assert done == [] and eng.scheduler.num_active == 1
    rid_b = eng.submit(RalmRequest(prompt=jnp.asarray(corpus[2:4, :8]),
                                   steps=2))    # B joins mid-flight
    completions = []
    while eng.scheduler.has_work:
        completions.extend(eng.step())
    # continuous batching: B asked for 2 steps, so it completes two
    # global steps after joining — while A (6 steps) is still decoding.
    # The later-submitted request finishes first.
    order = [r.request_id for r in completions]
    assert order == [rid_b, rid_a], order
    by_id = {r.request_id: r for r in completions}
    assert by_id[rid_a].steps == 6 and by_id[rid_b].steps == 2
    assert (by_id[rid_a].tokens == ref_a).all()
    assert (by_id[rid_b].tokens == ref_b).all()


def test_scheduler_rejects_duplicate_request_id(tiny_ralm):
    cfg, params, corpus, ds, ccfg, rag = tiny_ralm
    eng = RalmEngine.monolithic(params, cfg, rag, ds.retriever(ccfg))
    rid = eng.submit(RalmRequest(prompt=jnp.asarray(corpus[:1, :8]),
                                 steps=1))
    with pytest.raises(ValueError, match="already issued"):
        eng.submit(RalmRequest(prompt=jnp.asarray(corpus[:1, :8]),
                               steps=1, request_id=rid))


def test_generate_keeps_other_inflight_responses(tiny_ralm):
    """generate() drains the shared scheduler but must not discard other
    requests' completions — they surface on the next run()."""
    cfg, params, corpus, ds, ccfg, rag = tiny_ralm
    eng = RalmEngine.monolithic(params, cfg, rag, ds.retriever(ccfg))
    rid_a = eng.submit(RalmRequest(prompt=jnp.asarray(corpus[:1, :8]),
                                   steps=2))
    out_b = eng.generate(jnp.asarray(corpus[1:2, :8]), steps=4)
    assert out_b.shape == (1, 12)
    (resp_a,) = eng.run()               # A completed during generate()
    assert resp_a.request_id == rid_a and resp_a.tokens.shape == (1, 10)


def test_scheduler_zero_step_request(tiny_ralm):
    """steps=0 completes at admission with the prompt only (regression:
    the done-check must precede the decode, not follow it)."""
    cfg, params, corpus, ds, ccfg, rag = tiny_ralm
    eng = RalmEngine.monolithic(params, cfg, rag, ds.retriever(ccfg))
    eng.submit(RalmRequest(prompt=jnp.asarray(corpus[:1, :8]), steps=0))
    (resp,) = eng.run()
    assert resp.tokens.shape == (1, 8) and resp.steps == 0


def test_scheduler_admission_control(tiny_ralm):
    """max_active bounds in-flight sequences; queued work still drains."""
    cfg, params, corpus, ds, ccfg, rag = tiny_ralm
    eng = RalmEngine.monolithic(params, cfg, rag, ds.retriever(ccfg))
    eng.scheduler.max_active = 1
    for i in range(3):
        eng.submit(RalmRequest(prompt=jnp.asarray(corpus[i:i+1, :8]),
                               steps=2))
    seen_active = []
    completions = []
    while eng.scheduler.has_work:
        completions.extend(eng.step())
        seen_active.append(eng.scheduler.num_active)
    assert max(seen_active) <= 1
    assert [r.request_id for r in completions] == [0, 1, 2]


def test_scheduler_empty_queue_step(tiny_ralm):
    """step() with nothing queued or active is a no-op, not an error."""
    cfg, params, corpus, ds, ccfg, rag = tiny_ralm
    eng = RalmEngine.monolithic(params, cfg, rag, ds.retriever(ccfg))
    assert not eng.scheduler.has_work
    assert eng.step() == []
    assert eng.scheduler.num_active == 0
    assert eng.run() == []              # draining nothing is also fine


def test_scheduler_all_sequences_finish_same_step(tiny_ralm):
    """Every active sequence completing on one step() empties the
    scheduler in that call and reports all completions at once."""
    cfg, params, corpus, ds, ccfg, rag = tiny_ralm
    eng = RalmEngine.monolithic(params, cfg, rag, ds.retriever(ccfg))
    rids = [eng.submit(RalmRequest(prompt=jnp.asarray(corpus[i:i+1, :8]),
                                   steps=1)) for i in range(3)]
    done = eng.step()                   # one decode step finishes all 3
    assert sorted(r.request_id for r in done) == sorted(rids)
    assert not eng.scheduler.has_work and eng.scheduler.num_active == 0


def test_scheduler_max_active_reached_blocks_admission(tiny_ralm):
    """While max_active sequences are in flight, later submissions wait
    in the queue (they are admitted only as slots free up)."""
    cfg, params, corpus, ds, ccfg, rag = tiny_ralm
    eng = RalmEngine.monolithic(params, cfg, rag, ds.retriever(ccfg))
    eng.scheduler.max_active = 2
    for i in range(4):
        eng.submit(RalmRequest(prompt=jnp.asarray(corpus[i:i+1, :8]),
                               steps=3))
    eng.step()
    assert eng.scheduler.num_active == 2         # admission capped
    assert len(eng.scheduler.queue) == 2         # rest still queued
    completions = eng.run()
    assert [r.request_id for r in completions] == [0, 1, 2, 3]


# ---------------------------------------------------------------------------
# AsyncRetriever + RetrievalService: parity, coalescing, cache fast-path
# ---------------------------------------------------------------------------

def test_async_retriever_parity(tiny_ralm):
    """Acceptance criterion: greedy outputs via AsyncRetriever +
    RetrievalService are token-identical to the synchronous
    LocalRetriever path, under pipelined multi-request serving."""
    cfg, params, corpus, ds, ccfg, rag = tiny_ralm
    prompts = [jnp.asarray(corpus[:2, :8]), jnp.asarray(corpus[2:4, :8])]
    sync_eng = RalmEngine.monolithic(params, cfg, rag, ds.retriever(ccfg))
    out_sync = sync_eng.generate_batches(prompts, steps=6)
    aret = ds.async_retriever(ccfg)
    assert isinstance(aret, AsyncRetriever) and isinstance(aret, Retriever)
    async_eng = RalmEngine.monolithic(params, cfg, rag, aret)
    out_async = async_eng.generate_batches(prompts, steps=6)
    for a, b in zip(out_sync, out_async):
        assert (np.asarray(a) == np.asarray(b)).all()


def test_async_overlap_coalesces_waves(tiny_ralm):
    """Acceptance criterion: >= 2 concurrent sequences' queries coalesce
    into a single batched kernel dispatch per scheduler wave."""
    cfg, params, corpus, ds, ccfg, rag = tiny_ralm
    aret = ds.async_retriever(ccfg)
    eng = RalmEngine.monolithic(params, cfg, rag, aret)
    eng.submit(RalmRequest(prompt=jnp.asarray(corpus[:2, :8]), steps=4))
    eng.submit(RalmRequest(prompt=jnp.asarray(corpus[2:4, :8]), steps=4))
    eng.run()
    st = aret.service.stats
    assert st.num_queries == 16                  # 2 req x 2 rows x 4 steps
    assert st.num_batches == 4                   # ONE dispatch per wave
    assert st.max_coalesced == 4                 # both sequences' rows
    assert st.coalescing_factor() == pytest.approx(4.0)


def test_async_cache_hit_skips_kernel(tiny_ralm):
    """Acceptance criterion: a repeated prompt is answered from the
    result cache — zero new kernel dispatches — with identical tokens."""
    cfg, params, corpus, ds, ccfg, rag = tiny_ralm
    aret = ds.async_retriever(
        ccfg, service_cfg=ServiceConfig(cache_entries=256,
                                        cache_quant=1e-5))
    eng = RalmEngine.monolithic(params, cfg, rag, aret)
    out1 = np.asarray(eng.generate(jnp.asarray(corpus[:2, :8]), steps=4))
    n_dispatch = aret.service.stats.num_batches
    assert n_dispatch > 0 and aret.service.stats.cache_hits == 0
    out2 = np.asarray(eng.generate(jnp.asarray(corpus[:2, :8]), steps=4))
    assert (out1 == out2).all()
    assert aret.service.stats.num_batches == n_dispatch   # kernel skipped
    assert aret.service.stats.cache_hits == 8             # 2 rows x 4 steps


# ---------------------------------------------------------------------------
# the compatibility shims ride the same loop
# ---------------------------------------------------------------------------

def test_generate_shim_matches_engine(tiny_ralm):
    cfg, params, corpus, ds, ccfg, rag = tiny_ralm
    retr = RetrievalEngine(params=ds.params, shards=ds.shards, cfg=ccfg,
                           payload_tokens=ds.payload_tokens)
    assert isinstance(retr, LocalRetriever)
    out_shim = np.asarray(generate(params, cfg, rag,
                                   jnp.asarray(corpus[:2, :8]), steps=4,
                                   engine=retr))
    out_api = np.asarray(RalmEngine.monolithic(params, cfg, rag, retr)
                         .generate(jnp.asarray(corpus[:2, :8]), steps=4))
    assert (out_shim == out_api).all()


# ---------------------------------------------------------------------------
# monolithic == disaggregated (greedy parity, 8 fake devices)
# ---------------------------------------------------------------------------

def test_monolithic_disaggregated_parity():
    """Same seed, same prompts: the disaggregated engine (1 LM device +
    2 retrieval devices, DistributedRetriever) must emit exactly the
    monolithic engine's greedy tokens, for fresh and memorized prompts,
    while pipelining two request batches."""
    out = run_sub("""
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_arch
from repro.models import transformer as tf
from repro.serve import DatastoreBuilder, RagConfig, RalmEngine

cfg = dataclasses.replace(get_arch("dec_s").reduced, vocab_size=64)
params = tf.init_params(jax.random.PRNGKey(0), cfg)
rng = np.random.default_rng(0)
start = rng.integers(0, 64, size=(64,))
seqs = [start]
for _ in range(31):
    seqs.append((3 * seqs[-1] + 1) % 64)
corpus = np.stack(seqs, axis=1).astype(np.int32)

ds = DatastoreBuilder(dim=cfg.d_model, nlist=8, m=8,
                      list_cap=512).from_corpus(params, cfg, corpus)
ccfg = ds.search_config(nprobe=4, k=8, backend="ref")
rag = RagConfig(mode="knnlm", interval=1, k=8, lam=0.999, temperature=1.0)
prompts = [jnp.asarray(corpus[:4, :8]),
           jnp.asarray(rng.integers(0, 64, size=(2, 8), dtype=np.int32))]

mono = RalmEngine.monolithic(params, cfg, rag, retriever=ds.retriever(ccfg))
out_m = mono.generate_batches(prompts, steps=8)

dis = RalmEngine.disaggregated(params, cfg, rag, ds.params, ds.shards, ccfg,
                               payload_tokens=ds.payload_tokens,
                               lm_devices=1, ret_devices=2)
assert dis.backend.lm_mesh.devices.size == 1
assert dis.backend.ret_mesh.devices.size == 2
out_d = dis.generate_batches(prompts, steps=8)

for a, b in zip(out_m, out_d):
    assert (a == b).all(), (a, b)
assert (out_m[0][:, 8:] == corpus[:4, 8:16]).mean() > 0.8   # still a RALM
assert len(dis.times.decode_s) > 0 and len(dis.times.search_s) > 0
print("PARITY_OK ratio=%.2f" % dis.times.optimal_ratio())
""")
    assert "PARITY_OK" in out
