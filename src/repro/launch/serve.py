"""Serving launcher: ``python -m repro.launch.serve --arch <id> [...]``.

Builds a RALM deployment end-to-end on the local devices through the
unified ``repro.serve`` API: a ``DatastoreBuilder`` indexes a synthetic
datastore (full width by default, ``--reduced`` for the toy config), an
``EngineConfig`` picks monolithic (one mesh) or disaggregated (LM pool +
retrieval pool) deployment, and the engine's scheduler pipelines the
request batches. On an accelerator the serving path runs the compiled
Pallas kernels; on a CPU host the reference paths.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_arch
from repro.launch.cache import setup_compile_cache
from repro.serve import (DatastoreBuilder, EngineConfig, RalmEngine,
                         RalmRequest)


#: the full-width datastore: about 2^20 kNN-LM keys (8192 documents of
#: 129 tokens) under paper Table 3's SYN-512 code shape — 512-d keys,
#: m = 32 sub-quantizers of ksub = 256, 1024 IVF lists, nprobe 32
FULL_DOCS, FULL_DOC_LEN, FULL_NLIST, FULL_M, FULL_NPROBE = \
    8192, 129, 1024, 32, 32
FULL_TRAIN = 1 << 16          # keys the quantizers are trained on


def build_datastore(params, cfg, rag, *, seed: int, reduced: bool,
                    num_shards: int):
    """kNN-LM datastore over a synthetic corpus drawn from ``seed``, and
    the search config that serves it. Returns ``(Datastore,
    ChamVSConfig)`` (the build recipe itself lives in
    ``DatastoreBuilder``).

    ``reduced`` builds a toy index for the reduced model; otherwise the
    full-width SYN-512 datastore above, with list capacity taken from
    the data and the quantizers trained on ``FULL_TRAIN`` keys."""
    rng = np.random.default_rng(seed)
    if reduced:
        corpus = rng.integers(0, cfg.vocab_size, size=(256, 32),
                              dtype=np.int32)
        builder = DatastoreBuilder(dim=cfg.d_model, nlist=128,
                                   num_shards=num_shards)
        ds = builder.from_corpus(params, cfg, corpus)
        return ds, ds.search_config(nprobe=4, k=min(rag.k, 8))
    corpus = rng.integers(0, cfg.vocab_size, size=(FULL_DOCS, FULL_DOC_LEN),
                          dtype=np.int32)
    builder = DatastoreBuilder(dim=cfg.d_model, nlist=FULL_NLIST, m=FULL_M,
                               list_cap=None, num_shards=num_shards)
    keys, nxt = builder.corpus_keys(params, cfg, corpus)
    train = keys[rng.choice(keys.shape[0], FULL_TRAIN, replace=False)]
    ds = builder.build(keys, payload_tokens=jnp.asarray(nxt),
                       train_vectors=train)
    return ds, ds.search_config(nprobe=FULL_NPROBE, k=rag.k)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="dec_s")
    ap.add_argument("--reduced", action="store_true",
                    help="serve the arch's reduced-width config with a toy "
                         "datastore (default: the published widths and the "
                         "full SYN-512 datastore)")
    ap.add_argument("--steps", type=int, default=16)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--requests", type=int, default=2,
                    help="concurrent request batches (pipelined)")
    ap.add_argument("--disaggregate", action="store_true",
                    help="split devices into LM + retrieval pools: one LM "
                         "device, every other device a retrieval shard")
    ap.add_argument("--async-retrieval", action="store_true",
                    help="route searches through a RetrievalService "
                         "(wave coalescing + result cache)")
    ap.add_argument("--retrieval-cache", type=int, default=0,
                    help="RetrievalService LRU cache entries (0 = off)")
    ap.add_argument("--speculate-k", type=int, default=0,
                    help="speculative retrieval depth: due steps decode "
                         "ahead on stale neighbors while the real search "
                         "runs async; verified (and rolled back on "
                         "mismatch) k waves later. Requires "
                         "--async-retrieval. 0 = off")
    ap.add_argument("--no-speculate-verify", action="store_true",
                    help="skip verify-and-rollback: trust stale "
                         "neighbors outright (bounded quality drift, "
                         "zero rollback cost)")
    ap.add_argument("--retrieval-deadline-ms", type=float, default=0.0,
                    help="per-dispatch retrieval latency budget in ms: a "
                         "fault domain still unresolved past it is dropped "
                         "and the flush serves exact top-k over the "
                         "survivors (0 = wait indefinitely). Arms the "
                         "fault-tolerant dispatch layer; requires "
                         "--async-retrieval")
    ap.add_argument("--hedge-quantile", type=float, default=0.95,
                    help="latency quantile after which a hung retrieval "
                         "dispatch is hedged to another replica")
    ap.add_argument("--shard-replicas", type=int, default=1,
                    help="dispatch-target replicas per retrieval fault "
                         "domain (>1 arms replica failover; requires "
                         "--async-retrieval)")
    ap.add_argument("--chaos", default=None, metavar="PLAN.json",
                    help="arm a deterministic FaultPlan (JSON) at the "
                         "retrieval scan boundary: injected hangs / "
                         "crashes / errors / slowdowns exercise failover, "
                         "hedging, and partial results (docs/retrieval.md)")
    ap.add_argument("--no-retrieval-measure", action="store_true",
                    help="drop the per-flush stage-timing host blocks "
                         "(maximum decode/search overlap; the stats line "
                         "then reports counters only)")
    ap.add_argument("--per-sequence", action="store_true",
                    help="per-sequence oracle decode (one LM dispatch per "
                         "sequence) instead of wave-batched decode over "
                         "the KV-cache pool")
    ap.add_argument("--kv-slots", type=int, default=None,
                    help="fix the KV pool capacity in prompt rows; "
                         "default grows on demand")
    ap.add_argument("--kernel-backend", choices=["ref", "pallas"],
                    default=None,
                    help="override the ChamVS scan kernel backend "
                         "(default: pallas on an accelerator, ref on CPU)")
    ap.add_argument("--staged-scan", action="store_true",
                    help="per-shard staged scan pipeline (one chamvs "
                         "dispatch per shard; the parity oracle) instead "
                         "of the fused single-dispatch chamvs_scan")
    ap.add_argument("--attn-kernel", choices=["ref", "pallas", "einsum"],
                    default=None,
                    help="wave decode-attention kernel (default: pallas "
                         "= the streaming decode_attn kernel on an "
                         "accelerator, ref = the grouped einsum on CPU); "
                         "einsum = the legacy full-materialization oracle")
    ap.add_argument("--attn-seq-block", type=int, default=16,
                    help="KV-pool seq-axis alignment quantum: per-wave "
                         "attention reads crop to this multiple of the "
                         "valid prefix instead of the padded max_seq")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="enable the observability tracer and write the "
                         "Chrome trace-event JSON here on exit (open at "
                         "https://ui.perfetto.dev; docs/observability.md)")
    ap.add_argument("--metrics", action="store_true",
                    help="print the Prometheus text exposition of the "
                         "run's metrics registry after the demo batches "
                         "(with --gateway the same data is live at "
                         "GET /metricsz)")
    ap.add_argument("--gateway", action="store_true",
                    help="serve over HTTP instead of running the demo "
                         "batches: OpenAI-style /v1/completions with SSE "
                         "streaming, per-tenant admission + backpressure, "
                         "load-shedding degradation (docs/serving.md)")
    ap.add_argument("--port", type=int, default=8000,
                    help="gateway listen port (with --gateway)")
    ap.add_argument("--host", default="127.0.0.1",
                    help="gateway bind address (with --gateway)")
    args = ap.parse_args()

    setup_compile_cache()
    from repro.models import transformer as tf
    spec = get_arch(args.arch)
    cfg = spec.reduced if args.reduced else spec.model
    rag = spec.rag
    rng = np.random.default_rng(0)
    params = tf.init_params(jax.random.PRNGKey(0), cfg)

    disaggregate = args.disaggregate
    ret_devices = len(jax.devices()) - 1 if disaggregate else 1
    ds, ccfg = build_datastore(params, cfg, rag, seed=0,
                               reduced=args.reduced,
                               num_shards=ret_devices if disaggregate else 2)

    econfig = EngineConfig(model=cfg, rag=rag, disaggregate=disaggregate,
                           lm_devices=1, ret_devices=ret_devices,
                           async_retrieval=args.async_retrieval,
                           retrieval_cache=args.retrieval_cache,
                           retrieval_measure=not args.no_retrieval_measure,
                           speculate_k=args.speculate_k,
                           speculate_verify=not args.no_speculate_verify,
                           wave_decode=not args.per_sequence,
                           kv_slots=args.kv_slots,
                           kernel_backend=args.kernel_backend,
                           kernel_fused=(False if args.staged_scan
                                         else None),
                           attn_backend=args.attn_kernel,
                           attn_seq_block=args.attn_seq_block,
                           retrieval_deadline_s=(
                               args.retrieval_deadline_ms / 1e3),
                           hedge_quantile=args.hedge_quantile,
                           shard_replicas=args.shard_replicas,
                           chaos_plan=args.chaos,
                           trace=args.trace is not None,
                           trace_path=args.trace)
    engine = RalmEngine.from_config(econfig, params, ds, ccfg)

    if args.gateway:
        from repro.serve import Gateway, GatewayConfig
        Gateway(engine, GatewayConfig(host=args.host,
                                      port=args.port)).serve_forever()
        if args.trace:
            print(f"[serve] trace written to {engine.write_trace()}")
        return

    prompts = [jnp.asarray(rng.integers(0, cfg.vocab_size,
                                        size=(args.batch, 8), dtype=np.int32))
               for _ in range(args.requests)]
    t0 = time.time()
    for prompt in prompts:
        engine.submit(RalmRequest(prompt=prompt, steps=args.steps))
    responses = engine.run()
    dt = time.time() - t0

    mode = engine.backend.name
    for resp in responses:
        print(f"[serve] {mode} request {resp.request_id}: "
              f"{resp.tokens.shape} last tokens "
              f"{resp.tokens[:, -4:].tolist()}")
    ntok = sum(r.tokens.shape[0] * r.steps for r in responses)
    line = f"[serve] {mode}: {len(responses)} batches, {ntok} tokens in " \
           f"{dt:.2f}s ({ntok/dt:.1f} tok/s)"
    if engine.times is not None:
        line += (f"; optimal LM:retrieval ratio estimate "
                 f"{engine.times.optimal_ratio():.2f}")
    print(line)
    if engine.pool is not None:
        ps = engine.pool.stats
        print(f"[serve] kv pool: {engine.pool.capacity} slots "
              f"(high water {ps.high_water}), {ps.waves} waves avg "
              f"{ps.mean_wave():.1f} rows -> {engine.decode_dispatches} "
              f"LM dispatches, buckets {sorted(ps.buckets)}")
        print(f"[serve] decode attn [{engine.attn_spec.backend}]: "
              f"{ps.blocks_skipped}/{ps.blocks_total} seq blocks skipped "
              f"({ps.skip_fraction():.0%} of pool padding), "
              f"{ps.decode_compiles} decode graphs "
              f"(seq block {engine.pool.seq_block})")
    service = getattr(engine.retriever, "service", None)
    if service is not None:
        st = service.stats
        line = (f"[serve] retrieval service: {st.batched_rows} rows in "
                f"{st.num_batches} waves / {st.scan_dispatches} scan "
                f"dispatches "
                f"(coalescing {st.coalescing_factor():.1f}x, "
                f"cache {st.cache_hits} hit / {st.cache_misses} miss)")
        if service.config.measure:
            line += (f"; queue-wait {st.queue_wait.mean_s * 1e6:.0f}us "
                     f"scan {st.scan.mean_s * 1e6:.0f}us "
                     f"merge {st.merge.mean_s * 1e6:.0f}us")
        print(line)
        if service.replicas is not None:
            states = service.replicas.state_counts()
            print(f"[serve] fault tolerance: {st.ft_timeouts} timeouts, "
                  f"{st.ft_hedges} hedges, {st.ft_retries} retries, "
                  f"{st.ft_crashes} crashes -> {st.ft_ejections} "
                  f"ejections / {st.ft_recoveries} recoveries; "
                  f"{st.ft_partial_flushes} partial flushes "
                  f"({st.ft_partial_rows} rows); replicas "
                  + " ".join(f"{k}={v}" for k, v in states.items() if v))
        if st.spec_issued:
            print(f"[serve] speculation: {st.spec_issued} issued, "
                  f"{st.spec_accepted}/{st.spec_verified} accepted "
                  f"({st.spec_acceptance_rate():.0%}), "
                  f"{st.spec_rollbacks} rollbacks "
                  f"({st.spec_replayed_steps} steps replayed), "
                  f"residual wait {st.spec_wait.mean_s * 1e6:.0f}us/wave")

    if args.trace:
        print(f"[serve] trace written to {engine.write_trace()} "
              f"({len(engine.tracer.events())} events — open at "
              "https://ui.perfetto.dev)")
    if args.metrics:
        from repro.obs import MetricsRegistry, bind_engine_metrics
        reg = MetricsRegistry()
        bind_engine_metrics(reg, engine)
        print(reg.render(), end="")


if __name__ == "__main__":
    main()
