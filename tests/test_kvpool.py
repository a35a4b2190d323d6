"""Wave-batched decode over the slotted KV-cache pool.

The load-bearing claims (ISSUE 3 acceptance criteria):

  * ``RalmScheduler.step`` issues exactly ONE LM decode dispatch per
    wave, however many sequences are active (dispatch counter);
  * greedy outputs are token-identical to the per-sequence oracle
    (``wave=False``) under mixed prompt lengths, mid-run admission,
    early finishers freeing slots, and slot reuse;
  * a fixed-capacity pool defers admission until completions free slots
    (continuous batching in units of KV slot rows).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.models import transformer as tf
from repro.serve import (DatastoreBuilder, KVCachePool, RagConfig,
                         RalmEngine, RalmRequest)


@pytest.fixture(scope="module")
def tiny_ralm():
    """Tiny decoder LM + kNN-LM datastore over a deterministic-bigram
    corpus (token t -> (3t+1) mod 64) — the serving fixture."""
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


def oracle_tokens(tiny, prompt, steps):
    """Per-sequence reference path (one dispatch per sequence)."""
    cfg, params, corpus, ds, ccfg, rag = tiny
    eng = RalmEngine.monolithic(params, cfg, rag, ds.retriever(ccfg),
                                wave=False)
    return np.asarray(eng.generate(jnp.asarray(prompt), steps=steps))


# ---------------------------------------------------------------------------
# KVCachePool unit behavior
# ---------------------------------------------------------------------------

def test_pool_slot_lifecycle():
    cfg = dataclasses.replace(get_arch("dec_s").reduced, vocab_size=64)
    pool = KVCachePool(cfg, capacity=4, max_seq=16)
    assert pool.num_free == 4 and pool.scratch == 4
    a = pool.alloc(2)
    b = pool.alloc(1)
    assert a.tolist() == [0, 1] and b.tolist() == [2]
    assert pool.num_used == 3
    pool.release(a)
    # lowest ids first -> deterministic slot reuse
    assert pool.alloc(2).tolist() == [0, 1]
    with pytest.raises(RuntimeError, match="exhausted"):
        pool.alloc(2)
    assert pool.bucket(3) == 4 and pool.bucket(4) == 4 and pool.bucket(5) == 8


def test_oversized_request_rejected_at_submit(tiny_ralm):
    """A request that can NEVER fit the fixed pool fails in submit()
    instead of wedging the FIFO queue when admission reaches it."""
    cfg, params, corpus, ds, ccfg, rag = tiny_ralm
    eng = RalmEngine.monolithic(params, cfg, rag, ds.retriever(ccfg),
                                kv_slots=2)
    with pytest.raises(ValueError, match="never fit"):
        eng.submit(RalmRequest(prompt=jnp.asarray(corpus[:3, :8]), steps=2))
    # the queue stays clean: valid work still flows
    out = eng.generate(jnp.asarray(corpus[:2, :8]), steps=2)
    assert out.shape == (2, 10)


def test_pool_fixed_capacity_cannot_grow():
    cfg = dataclasses.replace(get_arch("dec_s").reduced, vocab_size=64)
    pool = KVCachePool(cfg, capacity=2, max_seq=8, fixed=True)
    with pytest.raises(RuntimeError, match="fixed"):
        pool.grow_slots(4)


def test_pool_growth_preserves_written_rows():
    """Slot and sequence growth pad the pool without disturbing rows that
    prefill already wrote."""
    cfg = dataclasses.replace(get_arch("dec_s").reduced, vocab_size=64)
    pool = KVCachePool(cfg, capacity=2, max_seq=8)
    caches = tf.init_cache(cfg, 1, max_seq=8)
    marked = jax.tree.map(lambda a: jnp.ones_like(a), caches)
    slots = pool.alloc(1)
    pool.write_prefill(slots, marked)
    pool.grow_slots(4)
    pool.grow_seq(12)
    assert pool.capacity == 4 and pool.max_seq == 12
    cls = cfg.layer_pattern[0]
    k = pool.caches["classes"][cls]["k"]
    assert k.shape[2] == 12
    assert bool((k[:, slots[0], :8] == 1).all())      # written prefix intact
    assert bool((k[:, slots[0], 8:] == 0).all())      # extension zeroed
    assert pool.num_free == 3                          # old scratch + growth


# ---------------------------------------------------------------------------
# acceptance: one dispatch per wave
# ---------------------------------------------------------------------------

def test_one_decode_dispatch_per_wave(tiny_ralm):
    """Three concurrent requests, one LM dispatch per scheduler wave —
    versus one per sequence on the oracle path."""
    cfg, params, corpus, ds, ccfg, rag = tiny_ralm
    eng = RalmEngine.monolithic(params, cfg, rag, ds.retriever(ccfg))
    for i in range(3):
        eng.submit(RalmRequest(prompt=jnp.asarray(corpus[i:i + 1, :8]),
                               steps=4))
    assert eng.step() == [] and eng.decode_dispatches == 0   # all at step 0
    before = eng.decode_dispatches
    eng.step()
    assert eng.decode_dispatches == before + 1               # ONE for 3 seqs
    eng.run()
    # steps 1..3 decode (step 0 consumes prefill logits): 3 waves total
    assert eng.decode_dispatches == 3
    assert eng.pool.stats.mean_wave() == pytest.approx(3.0)

    oracle = RalmEngine.monolithic(params, cfg, rag, ds.retriever(ccfg),
                                   wave=False)
    for i in range(3):
        oracle.submit(RalmRequest(prompt=jnp.asarray(corpus[i:i + 1, :8]),
                                  steps=4))
    oracle.run()
    assert oracle.decode_dispatches == 9                     # 3 seqs x 3


# ---------------------------------------------------------------------------
# acceptance: wave == oracle, token for token
# ---------------------------------------------------------------------------

def test_wave_parity_mixed_prompt_lengths(tiny_ralm):
    """Ragged prompts (5/8/11 tokens) share the pool; every request's
    greedy tokens must match its solo per-sequence run."""
    cfg, params, corpus, ds, ccfg, rag = tiny_ralm
    specs = [(corpus[:2, :5], 6), (corpus[2:4, :8], 6), (corpus[4:5, :11], 4)]
    eng = RalmEngine.monolithic(params, cfg, rag, ds.retriever(ccfg))
    rids = [eng.submit(RalmRequest(prompt=jnp.asarray(p), steps=s))
            for p, s in specs]
    by_id = {r.request_id: r.tokens for r in eng.run()}
    for rid, (p, s) in zip(rids, specs):
        assert (by_id[rid] == oracle_tokens(tiny_ralm, p, s)).all()


def test_wave_parity_prompts_straddle_prefill_bucket(tiny_ralm):
    """Prompts of 16 and 17 tokens prefill in different buckets (16 and
    32, the latter padded at its tail) and share the pool; greedy tokens
    still match the per-sequence runs."""
    cfg, params, corpus, ds, ccfg, rag = tiny_ralm
    specs = [(corpus[:2, :16], 5), (corpus[2:3, :17], 5)]
    eng = RalmEngine.monolithic(params, cfg, rag, ds.retriever(ccfg))
    rids = [eng.submit(RalmRequest(prompt=jnp.asarray(p), steps=s))
            for p, s in specs]
    by_id = {r.request_id: r.tokens for r in eng.run()}
    assert eng.prefill_stats.pad_tokens == 32 - 17
    for rid, (p, s) in zip(rids, specs):
        assert (by_id[rid] == oracle_tokens(tiny_ralm, p, s)).all()


def test_wave_parity_mid_run_admission_and_early_finishers(tiny_ralm):
    """A request admitted mid-run joins the wave; a short request
    finishes early, frees its slots, and a queued request reuses them —
    all without perturbing anyone's tokens."""
    cfg, params, corpus, ds, ccfg, rag = tiny_ralm
    eng = RalmEngine.monolithic(params, cfg, rag, ds.retriever(ccfg),
                                kv_slots=4)
    ra = eng.submit(RalmRequest(prompt=jnp.asarray(corpus[:2, :8]), steps=6))
    eng.step(); eng.step()                      # A is 2 tokens in
    rb = eng.submit(RalmRequest(prompt=jnp.asarray(corpus[2:4, :5]),
                                steps=2))       # joins mid-run (ragged)
    rc = eng.submit(RalmRequest(prompt=jnp.asarray(corpus[4:6, :6]),
                                steps=3))       # must wait for B's slots
    completions = []
    deferred = False
    while eng.scheduler.has_work:
        completions.extend(eng.step())
        deferred |= len(eng.scheduler.queue) > 0
    assert deferred                             # C actually queued on slots
    # B (2 steps) finishes first; A (6 steps, 2-step head start) beats C
    # (3 steps, admitted only once B freed its slots)
    assert [r.request_id for r in completions] == [rb, ra, rc]
    by_id = {r.request_id: r.tokens for r in completions}
    assert (by_id[ra] == oracle_tokens(tiny_ralm, corpus[:2, :8], 6)).all()
    assert (by_id[rb] == oracle_tokens(tiny_ralm, corpus[2:4, :5], 2)).all()
    assert (by_id[rc] == oracle_tokens(tiny_ralm, corpus[4:6, :6], 3)).all()
    assert eng.pool.num_free == 4               # everything released
    assert eng.pool.stats.high_water == 4       # B+C reused A-era rows
    assert eng.pool.stats.slot_grows == 0       # fixed pool never grew


def test_wave_parity_slot_reuse_back_to_back(tiny_ralm):
    """Slots freed by one request are re-prefilled by the next; stale
    cache contents from the previous occupant must not leak."""
    cfg, params, corpus, ds, ccfg, rag = tiny_ralm
    eng = RalmEngine.monolithic(params, cfg, rag, ds.retriever(ccfg),
                                kv_slots=2)
    out1 = np.asarray(eng.generate(jnp.asarray(corpus[:2, :8]), steps=5))
    out2 = np.asarray(eng.generate(jnp.asarray(corpus[6:8, :7]), steps=5))
    assert eng.pool.stats.allocs == 4 and eng.pool.stats.releases == 4
    assert (out1 == oracle_tokens(tiny_ralm, corpus[:2, :8], 5)).all()
    assert (out2 == oracle_tokens(tiny_ralm, corpus[6:8, :7], 5)).all()


def test_wave_pool_autogrow_parity(tiny_ralm):
    """Without ``kv_slots`` the pool doubles its rows and extends its
    sequence axis on demand; outputs stay oracle-identical."""
    cfg, params, corpus, ds, ccfg, rag = tiny_ralm
    eng = RalmEngine.monolithic(params, cfg, rag, ds.retriever(ccfg))
    ra = eng.submit(RalmRequest(prompt=jnp.asarray(corpus[:2, :6]), steps=3))
    eng.step()
    big = corpus[2:12, :12]                     # 10 rows, longer horizon
    rb = eng.submit(RalmRequest(prompt=jnp.asarray(big), steps=6))
    by_id = {r.request_id: r.tokens for r in eng.run()}
    assert eng.pool.stats.slot_grows >= 1 and eng.pool.stats.seq_grows >= 1
    assert (by_id[ra] == oracle_tokens(tiny_ralm, corpus[:2, :6], 3)).all()
    assert (by_id[rb] == oracle_tokens(tiny_ralm, big, 6)).all()


def test_wave_buckets_are_pow2(tiny_ralm):
    """Continuous batching sweeps the active row count; compiled wave
    shapes stay on pow2 buckets (bounded jit recompiles)."""
    cfg, params, corpus, ds, ccfg, rag = tiny_ralm
    eng = RalmEngine.monolithic(params, cfg, rag, ds.retriever(ccfg))
    for i, steps in enumerate([5, 4, 3, 2, 1]):  # 5 rows, one drops per wave
        eng.submit(RalmRequest(prompt=jnp.asarray(corpus[i:i + 1, :8]),
                               steps=steps))
    eng.run()
    buckets = eng.pool.stats.buckets
    assert all(b & (b - 1) == 0 for b in buckets), buckets
    assert buckets <= {1, 2, 4, 8}


def test_wave_async_retriever_coalesces(tiny_ralm):
    """Wave decode composes with the async retrieval service: one LM
    dispatch AND one search dispatch per wave."""
    cfg, params, corpus, ds, ccfg, rag = tiny_ralm
    aret = ds.async_retriever(ccfg)
    eng = RalmEngine.monolithic(params, cfg, rag, aret)
    eng.submit(RalmRequest(prompt=jnp.asarray(corpus[:2, :8]), steps=4))
    eng.submit(RalmRequest(prompt=jnp.asarray(corpus[2:4, :8]), steps=4))
    eng.run()
    assert eng.decode_dispatches == 3            # steps 1..3 (step 0 free)
    st = aret.service.stats
    assert st.num_batches == 4                   # one search per wave
    assert st.max_coalesced == 4
