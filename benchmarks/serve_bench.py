"""Serving throughput sweep: tokens/s vs. active wave size over the
wave-batched ``RalmEngine`` (one LM dispatch + one retrieval dispatch
per scheduler wave), with the per-pool step breakdown — LM decode time
from a blocking timer around ``decode_wave``, retrieval stage times from
``repro.retrieval.stats``.

Run via ``python -m benchmarks.run --mode serve``; emits
``BENCH_serve.json`` with one row per (pool provisioning, wave size).
Two acceptance claims:
tokens/s improves monotonically-or-flat from wave size 1 to the max
bucket (the whole wave rides one dispatch, so adding rows amortizes the
per-step dispatch + kernel fixed costs — paper §5, Fig. 9/12 batch
sweeps), and the length-aware decode-attention path beats the legacy
full-pool einsum path per LM step (``lm_speedup``: adjacent
paired-window A/B against a second, legacy-configured engine in the
same process — the only comparison that survives this host's
multi-second noise epochs).
"""
from __future__ import annotations

import json
import time
from typing import Dict, List, Optional, Sequence


class _TimedWave:
    """Blocking timer around a backend's ``decode_wave`` (the LM-pool
    side of the per-pool breakdown; retrieval stages come from the
    service stats, which block per flush the same way)."""

    def __init__(self, backend):
        self.backend = backend
        self.times_s: List[float] = []
        self._orig = backend.decode_wave

    def __enter__(self):
        def timed(caches, token, slots, position, enc_states=None, **kw):
            import jax
            t0 = time.perf_counter()
            out = self._orig(caches, token, slots, position,
                             enc_states=enc_states, **kw)
            jax.block_until_ready(out[0])
            self.times_s.append(time.perf_counter() - t0)
            return out
        self.backend.decode_wave = timed
        return self

    def __exit__(self, *exc):
        self.backend.decode_wave = self._orig
        return False


def _build_engines(kv_slots: int, max_seq: int):
    """Two engines over ONE model + datastore: ``kernel`` — the default
    decode-attention path (grouped ref + per-wave ``kv_len`` crop) —
    and ``legacy`` — the pre-kernel shapes (``attn_backend="einsum"``
    with ``attn_seq_block=max_seq``, i.e. full-pool attention reads).
    Measuring both in adjacent paired windows inside one process is the
    only comparison that survives this host's multi-second noise
    epochs; cross-run deltas against an old committed file do not."""
    import dataclasses

    import jax
    import numpy as np

    from repro.configs import get_arch
    from repro.models import transformer as tf
    from repro.serve import (DatastoreBuilder, RagConfig, RalmEngine,
                             ServiceConfig)

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
    ccfg = ds.search_config(nprobe=4, k=8)
    rag = RagConfig(mode="knnlm", interval=1, k=8, lam=0.999,
                    temperature=1.0)
    engines, arets = {}, {}
    for mode, attn_kw in (("kernel", {}),
                          ("legacy", dict(attn_backend="einsum",
                                          attn_seq_block=max_seq))):
        aret = ds.async_retriever(ccfg,
                                  service_cfg=ServiceConfig(measure=True))
        engines[mode] = RalmEngine.monolithic(params, cfg, rag, aret,
                                              max_seq=max_seq,
                                              kv_slots=kv_slots, **attn_kw)
        arets[mode] = aret
    return engines, corpus, arets


def run_sweep(wave_sizes: Sequence[int] = (1, 2, 4, 8),
              steps: int = 48, prompt_len: int = 8,
              repeats: int = 7,
              pool_seqs: Sequence[Optional[int]] = (None, 512)
              ) -> List[Dict[str, object]]:
    """One row per (pool provisioning, wave size). Points of one
    provisioning share one engine pair (fixed pool shape + jit cache);
    each point submits ``w`` single-row requests decoded in lockstep,
    best-of-``repeats`` wall clock.

    ``pool_seqs`` sweeps the pool's provisioned context budget:
    ``None`` = tight (``max_seq = prompt + steps``, zero padding
    headroom — the configuration where length-aware attention cannot
    help by construction) and a provisioned value (the continuous-
    batching steady state: the pool sized for the deployment's longest
    request, live rows much shorter — where the legacy path pays the
    full padded axis every step and the crop wins).

    The timed window is the steady-state decode loop: admission
    (prefill + the free step-0 token) runs before the clock starts, so
    tokens/s isolates the wave-batching lever — ``steps - 1`` decode
    waves over ``w`` rows — from the per-request prefill cost."""
    import numpy as np

    import jax.numpy as jnp

    from repro.serve import RalmRequest

    max_wave = max(wave_sizes)

    rows: List[Dict[str, object]] = []
    for pool_seq in pool_seqs:
        max_seq = pool_seq if pool_seq is not None else prompt_len + steps
        # pre-align to the kernel engine's seq block (16) so BOTH A/B
        # engines run the same pool shape — otherwise the kernel side
        # alone pays the alignment padding and the pair is biased
        max_seq = -(-max_seq // 16) * 16
        engines, corpus, arets = _build_engines(
            kv_slots=max_wave, max_seq=max_seq)

        def run_once(engine, w: int) -> float:
            for i in range(w):
                engine.submit(RalmRequest(
                    prompt=jnp.asarray(corpus[i:i + 1, :prompt_len]),
                    steps=steps))
            engine.step()                # admission + step 0 (untimed)
            t0 = time.perf_counter()
            engine.run()
            return time.perf_counter() - t0

        rows.extend(_sweep_waves(engines, arets, run_once, wave_sizes,
                                 steps, prompt_len, max_seq, repeats, np))
    return rows


def _sweep_waves(engines, arets, run_once, wave_sizes, steps, prompt_len,
                 max_seq, repeats, np) -> List[Dict[str, object]]:
    rows: List[Dict[str, object]] = []
    for w in wave_sizes:
        engine = engines["kernel"]
        pre_buckets = set(engine.pool.stats.buckets) if engine.pool else set()
        pre_graphs = (set(engine.pool.stats.compiled) if engine.pool
                      else set())
        pre_blocks = ((engine.pool.stats.blocks_total,
                       engine.pool.stats.blocks_skipped)
                      if engine.pool else (0, 0))
        for mode in ("legacy", "kernel"):
            run_once(engines[mode], w)   # warmup: compile this bucket
        best = {}
        lm_samples = {"legacy": [], "kernel": []}
        for _ in range(repeats):
            # adjacent alternating windows, legacy then kernel, so both
            # modes sample the same host noise epochs; the reported
            # speedup is the ratio of per-mode MEDIANS (a single run
            # spans an appreciable fraction of an epoch, so per-pair
            # ratios are noisier than the medians themselves)
            for mode in ("legacy", "kernel"):
                eng = engines[mode]
                arets[mode].service.stats.reset()
                base_dispatch = eng.decode_dispatches
                with _TimedWave(eng.backend) as t:
                    wall = run_once(eng, w)
                lm_us = (sum(t.times_s) / len(t.times_s) * 1e6
                         if t.times_s else 0.0)
                lm_samples[mode].append(lm_us)
                if mode not in best or wall < best[mode][0]:
                    # keep the retrieval-stage snapshot of the SAME
                    # repeat the wall-clock/LM numbers come from, so
                    # each row's per-pool breakdown is consistent
                    best[mode] = (wall, eng.decode_dispatches -
                                  base_dispatch, lm_us,
                                  arets[mode].service.stats.snapshot())
        wall, dispatches, lm_us, snap = best["kernel"]
        ntok = w * (steps - 1)
        rows.append(dict(
            wave=w, steps=steps, prompt_len=prompt_len, pool_seq=max_seq,
            tokens_per_s=ntok / wall,
            us_per_token=wall / ntok * 1e6,
            wall_s=wall,
            decode_dispatches=dispatches,
            lm_step_us=float(np.median(lm_samples["kernel"])),
            lm_step_us_legacy=float(np.median(lm_samples["legacy"])),
            lm_step_us_best=lm_us,
            tokens_per_s_legacy=ntok / best["legacy"][0],
            # the honest decode-attn claim: per-mode lm-step medians
            # over adjacent alternating windows, legacy / kernel
            lm_speedup=float(np.median(lm_samples["legacy"])
                             / np.median(lm_samples["kernel"])),
            queue_wait_us=snap["queue_wait"]["mean_us"],
            scan_us=snap["scan"]["mean_us"],
            merge_us=snap["merge"]["mean_us"],
            search_batches=snap["num_batches"],
            coalescing_factor=snap["coalescing_factor"],
            # buckets this point compiled/used (pool stats are
            # cumulative across the sweep, so report the delta)
            buckets=sorted(set(engine.pool.stats.buckets) - pre_buckets),
            # length-aware decode attention: seq blocks skipped vs a
            # full-pool read, and the decode graphs this point added
            attn_skip_fraction=(
                (engine.pool.stats.blocks_skipped - pre_blocks[1])
                / max(engine.pool.stats.blocks_total - pre_blocks[0], 1)),
            decode_graphs=sorted(
                set(engine.pool.stats.compiled) - pre_graphs),
        ))
    return rows


def main(out_path: str = "BENCH_serve.json") -> None:
    rows = run_sweep()
    meta = dict(
        note="kernel rows (the headline fields) run the default decode-"
             "attention path: grouped-ref flavor + per-wave kv_len crop "
             "(attn_seq_block 16). lm_step_us_legacy / lm_speedup come "
             "from a second engine with attn_backend='einsum' and "
             "attn_seq_block=max_seq — the exact pre-kernel shapes — "
             "measured in ADJACENT ALTERNATING windows in the same "
             "process; lm_speedup is the ratio of per-mode lm-step "
             "MEDIANS (cross-run deltas on this host are noise-epoch-"
             "dominated and not comparable). pool_seq sweeps the "
             "provisioned "
             "context budget: the tight pool (prompt+steps, zero "
             "padding headroom) is where length-aware attention cannot "
             "help by construction — expect lm_speedup ~1.0 there; the "
             "provisioned pool is the continuous-batching steady state "
             "the crop targets.")
    with open(out_path, "w") as f:
        json.dump(dict(meta=meta, rows=rows), f, indent=2)
    print("pool_seq,wave,tokens_per_s,lm_step_us,lm_step_us_legacy,"
          "lm_speedup,scan_us,dispatches,attn_skip")
    for r in rows:
        print(f"{r['pool_seq']},{r['wave']},{r['tokens_per_s']:.1f},"
              f"{r['lm_step_us']:.1f},"
              f"{r['lm_step_us_legacy']:.1f},{r['lm_speedup']:.2f},"
              f"{r['scan_us']:.1f},{r['decode_dispatches']},"
              f"{r['attn_skip_fraction']:.2f}")
    pools = sorted(set(r["pool_seq"] for r in rows))
    mono = True
    for p in pools:
        tps = [r["tokens_per_s"] for r in rows if r["pool_seq"] == p]
        mono &= all(b >= a * 0.98 for a, b in zip(tps, tps[1:]))
    lm_faster = all(r["lm_speedup"] >= 1.0 for r in rows
                    if r["wave"] >= 4 and r["pool_seq"] == max(pools))
    print(f"wrote {out_path} ({len(rows)} rows); "
          f"monotonic-or-flat per pool: {mono}; lm_step reduced at "
          f"wave>=4 on the provisioned pool: {lm_faster}")


if __name__ == "__main__":
    import sys
    sys.path.insert(0, "src")
    main()
