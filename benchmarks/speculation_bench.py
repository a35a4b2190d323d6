"""Speculative-retrieval sweep: how much of the per-step retrieval
block does verify-and-rollback speculation hide?

Run via ``python -m benchmarks.run --mode speculation``; merges a
``speculation`` section into ``BENCH_serve.json``.

Method. Two engines per (speculate_k, interval, wave) cell over ONE
model + datastore:

  * baseline — ``speculate_k=0``, ``ServiceConfig.measure=True``: every
    due step sits behind the real search, and the service's blocking
    stage timers report exactly what it waited for. The denominator is
    the per-flush ``queue_wait + scan`` time — the retrieval block the
    baseline pays on the decode path.
  * speculating — ``speculate_k=k``, ``measure=False`` (blocking stage
    timers would serialize the flush and destroy the overlap being
    measured): due steps decode ahead on stale neighbors; the residual
    block is ``spec_wait`` (forcing the in-flight results at harvest —
    XLA drains its queue in enqueue order, so this wait covers only the
    scan, not the decode wave dispatched after it) plus ``spec_replay``
    (rollback re-decodes). The numerator is their sum.

``hidden_fraction = 1 - (spec_wait + spec_replay) / (queue_wait +
scan)`` over whole runs — the NET fraction of the baseline's retrieval
block the speculating engine no longer pays, rollback cost included
(``hidden_fraction_gross`` excludes replay for the decomposition).
``landed_fraction`` is the direct observation backing it: the share of
harvested points whose result arrays were ALREADY materialized
(``jax.Array.is_ready``) before the harvest forced them — those points
paid zero residual wait, the search ran entirely under the decode.

Corpus choice is load-bearing and reported, not hidden: acceptance is
workload-dependent. Queries one step apart retrieve the same payload
token only when the local context repeats, so the corpus here is
RUN-STRUCTURED (tokens repeat in runs of ``RUN_LEN=8``): consecutive
retrievals agree ~7/8 of the time, the regime speculation targets
(RaLMSpec §4 reports the same corpus sensitivity). A bigram corpus
(every step a new token) drives acceptance to ~0 and turns speculation
into pure rollback churn — that regime is covered by the parity tests,
not claimed as a speedup.

Greedy parity (base tokens == spec tokens) is asserted per cell and
recorded in each row: the hiding claim only counts if the output is
token-identical.
"""
from __future__ import annotations

import json
import time
from typing import Dict, List, Sequence

RUN_LEN = 8
STEPS = 24
PROMPT_LEN = 4


def _build_world():
    import dataclasses

    import jax
    import numpy as np

    from repro.configs import get_arch
    from repro.models import transformer as tf
    from repro.serve import DatastoreBuilder, RagConfig

    cfg = dataclasses.replace(get_arch("dec_s").reduced, vocab_size=64)
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    # run-structured corpus: each row is 32/RUN_LEN runs of RUN_LEN
    # repeated tokens — consecutive-step retrievals agree inside a run
    runs = rng.integers(0, 64, size=(64, 32 // RUN_LEN))
    corpus = np.repeat(runs, RUN_LEN, axis=1).astype(np.int32)
    ds = DatastoreBuilder(dim=cfg.d_model, nlist=8, m=8,
                          list_cap=512).from_corpus(params, cfg, corpus)
    ccfg = ds.search_config(nprobe=4, k=8)
    rag = RagConfig(mode="knnlm", interval=1, k=8, lam=0.999,
                    temperature=1.0)
    return cfg, params, corpus, ds, ccfg, rag


def _make_engine(world, spec_k: int, interval: int, measure: bool):
    import dataclasses

    from repro.serve import RalmEngine, ServiceConfig

    cfg, params, _, ds, ccfg, rag = world
    rag = dataclasses.replace(rag, interval=interval)
    ret = ds.async_retriever(ccfg, service_cfg=ServiceConfig(
        measure=measure, cache_entries=0))
    return RalmEngine.monolithic(params, cfg, rag, retriever=ret,
                                 speculate_k=spec_k)


def _run_once(world, eng, wave: int, steps: int = STEPS):
    """One request of ``wave`` rows decoded to completion; returns
    (tokens, wall_s)."""
    import jax.numpy as jnp

    from repro.serve import RalmRequest

    corpus = world[2]
    prompt = jnp.asarray(corpus[0:wave, :PROMPT_LEN])
    t0 = time.perf_counter()
    eng.submit(RalmRequest(prompt=prompt, steps=steps))
    resp = eng.run()[0]
    return resp.tokens, time.perf_counter() - t0


def run_sweep(spec_ks: Sequence[int] = (1, 2),
              intervals: Sequence[int] = (1, 2),
              waves: Sequence[int] = (1, 2, 4, 8)) -> List[Dict]:
    import numpy as np

    world = _build_world()
    rows: List[Dict] = []
    for interval in intervals:
        for wave in waves:
            base = _make_engine(world, 0, interval, measure=True)
            # warm at FULL length: kv_len buckets grow with position, so
            # a short warmup leaves decode graphs uncompiled and the
            # measured window absorbs backend_compile time
            _run_once(world, base, wave, steps=STEPS)
            base.retriever.service.stats.reset()
            base_toks, base_s = _run_once(world, base, wave)
            bst = base.retriever.service.stats
            base_block_s = bst.queue_wait.total_s + bst.scan.total_s
            base_flushes = max(bst.num_batches, 1)
            for spec_k in spec_ks:
                spec = _make_engine(world, spec_k, interval,
                                    measure=False)
                _run_once(world, spec, wave, steps=STEPS)
                spec.retriever.service.stats.reset()
                spec_toks, spec_s = _run_once(world, spec, wave)
                sst = spec.retriever.service.stats
                resid_s = sst.spec_wait.total_s + sst.spec_replay.total_s
                parity = bool(np.array_equal(np.asarray(base_toks),
                                             np.asarray(spec_toks)))
                ntok = wave * STEPS
                rows.append(dict(
                    speculate_k=spec_k, interval=interval, wave=wave,
                    spec_issued=sst.spec_issued,
                    spec_verified=sst.spec_verified,
                    spec_landed=sst.spec_landed,
                    landed_fraction=round(
                        sst.spec_landed
                        / max(sst.spec_verified + sst.spec_discarded, 1),
                        4),
                    spec_accepted=sst.spec_accepted,
                    spec_rollbacks=sst.spec_rollbacks,
                    spec_replayed_steps=sst.spec_replayed_steps,
                    acceptance_rate=round(sst.spec_acceptance_rate(), 4),
                    base_block_us_per_flush=round(
                        base_block_s / base_flushes * 1e6, 1),
                    spec_wait_us_total=round(
                        sst.spec_wait.total_s * 1e6, 1),
                    spec_replay_us_total=round(
                        sst.spec_replay.total_s * 1e6, 1),
                    hidden_fraction=round(
                        1.0 - resid_s / base_block_s, 4)
                    if base_block_s > 0 else None,
                    hidden_fraction_gross=round(
                        1.0 - sst.spec_wait.total_s / base_block_s, 4)
                    if base_block_s > 0 else None,
                    base_tokens_per_s=round(ntok / base_s, 1),
                    spec_tokens_per_s=round(ntok / spec_s, 1),
                    parity=parity,
                ))
                r = rows[-1]
                print(f"k={spec_k} interval={interval} wave={wave}: "
                      f"accept={r['acceptance_rate']:.0%} "
                      f"rollbacks={r['spec_rollbacks']} "
                      f"hidden={r['hidden_fraction']} "
                      f"landed={r['landed_fraction']:.0%} "
                      f"parity={parity}")
    return rows


def main(out_path: str = "BENCH_serve.json") -> None:
    rows = run_sweep()
    meta = dict(
        run_len=RUN_LEN, steps=STEPS, prompt_len=PROMPT_LEN,
        note="hidden_fraction = 1 - (spec_wait + spec_replay) / "
             "(queue_wait + scan), whole-run totals: the NET share of "
             "the baseline's per-step retrieval block that speculation "
             "removed from the decode path, rollback replay cost "
             "included. spec_wait times ONLY the forcing of the "
             "in-flight result arrays at harvest (XLA executes its "
             "queue in enqueue order, so the wait excludes the decode "
             "wave dispatched after the scan); the verification math "
             "is excluded because the baseline pays the same "
             "interpolate in its finish phase. landed_fraction is the "
             "model-free cross-check: the share of harvested points "
             "whose results were already materialized (is_ready) "
             "before forcing — those searches ran entirely under the "
             "decode wave(s). Denominator from a speculation-off "
             "engine with blocking stage timers (measure=True); "
             "numerator from the speculating engine with measure=False "
             "(blocking timers would serialize the flush being "
             "overlapped). Corpus is "
             "run-structured (runs of run_len repeated tokens) so "
             "consecutive retrievals agree ~(run_len-1)/run_len of the "
             "time — acceptance is WORKLOAD-DEPENDENT and this file "
             "reports the favorable regime speculation targets; "
             "adversarial (bigram) corpora drive acceptance to ~0 and "
             "are covered by the parity tests instead. parity = greedy "
             "token-identity of the speculating run vs its baseline. "
             "Caveat: on a single-core host the overlapped scan still "
             "consumes serialized CPU time, so base/spec tokens_per_s "
             "stay comparable — hidden_fraction measures the decode-"
             "path BLOCK removed, which converts to wall-clock speedup "
             "only where the search runs on spare cores or a separate "
             "accelerator (the paper's disaggregated setting).")
    section = dict(meta=meta, rows=rows)
    try:
        with open(out_path) as f:
            doc = json.load(f)
    except (OSError, json.JSONDecodeError):
        doc = {}
    doc["speculation"] = section
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=2)

    parity_ok = all(r["parity"] for r in rows)
    big = [r for r in rows if r["wave"] >= 4
           and r["hidden_fraction"] is not None]
    claim = all(r["hidden_fraction_gross"] >= 0.70 for r in big)
    net_min = min(r["hidden_fraction"] for r in big) if big else None
    print(f"wrote {out_path} (speculation section, {len(rows)} rows); "
          f"greedy parity everywhere: {parity_ok}; "
          f">=70% of queue_wait+scan hidden at wave>=4: {claim} "
          f"(worst-case net, rollback replay charged: {net_min})")


if __name__ == "__main__":
    main()
