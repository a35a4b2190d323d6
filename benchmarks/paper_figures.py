"""One benchmark per paper table/figure. Each returns a list of row-dicts;
benchmarks/run.py prints them as CSV (name,us_per_call,derived)."""
from __future__ import annotations

import pathlib
import time
from typing import Dict, List

import numpy as np

from benchmarks import hwmodel as hw

RESULTS = pathlib.Path(__file__).resolve().parent / "results"


# ---------------------------------------------------------------------------
# Fig. 7 — probability a level-one queue holds k of the top-K
# ---------------------------------------------------------------------------

def fig7_queue_probability() -> List[Dict]:
    from repro.core.approx_topk_math import binom_pmf
    K, nq = 100, 16
    rng = np.random.default_rng(0)
    mc = np.zeros(K + 1)
    trials = 20000
    for _ in range(trials):
        mc[(rng.integers(0, nq, size=K) == 0).sum()] += 1
    mc /= trials
    rows = []
    cum = 0.0
    for k in range(0, 26):
        p = binom_pmf(K, 1 / nq, k)
        cum += p
        rows.append(dict(name=f"fig7/k={k}", us_per_call=0.0,
                         derived=f"p={p:.5f};P={cum:.5f};mc={mc[k]:.5f}"))
    return rows


# ---------------------------------------------------------------------------
# Fig. 8 — resource saving from truncated queues
# ---------------------------------------------------------------------------

def fig8_resource_saving() -> List[Dict]:
    from repro.core.approx_topk_math import (resource_saving,
                                             truncated_queue_len)
    rows = []
    for nq in (2, 4, 8, 16, 32, 64, 128):
        kp = truncated_queue_len(100, nq, 0.01)
        rows.append(dict(
            name=f"fig8/queues={nq}", us_per_call=0.0,
            derived=f"k_prime={kp};saving={resource_saving(100, nq):.1f}x"))
    return rows


# ---------------------------------------------------------------------------
# Fig. 9 — vector search latency: CPU baseline vs ChamVS (modeled at paper
# scale + measured small-scale gather-ADC wall time for grounding)
# ---------------------------------------------------------------------------

def fig9_search_latency() -> List[Dict]:
    rows = []
    for ds in hw.DATASETS:
        for batch in (1, 4, 16, 64):
            t_cpu = hw.cpu_search_latency(ds, batch)
            t_chv = hw.chamvs_search_latency(ds, batch, nodes=1)
            rows.append(dict(
                name=f"fig9/{ds.name}/b={batch}",
                us_per_call=t_chv * 1e6,
                derived=(f"modeled;cpu_ms={t_cpu*1e3:.2f};"
                         f"chamvs_ms={t_chv*1e3:.2f};"
                         f"speedup={t_cpu/t_chv:.1f}x")))
    # measured grounding: small-scale ref ADC scan wall time on this host
    import jax
    import jax.numpy as jnp
    from repro.kernels.pq_adc.ops import pq_adc_topk
    from repro.kernels.registry import REF
    B, n, m = 8, 4096, 16
    luts = jax.random.normal(jax.random.PRNGKey(0), (B, m, 256))
    codes = jax.random.randint(jax.random.PRNGKey(1), (B, n, m), 0, 256,
                               jnp.uint8)
    lens = jnp.full((B,), n, jnp.int32)
    f = lambda: pq_adc_topk(luts, codes, lens, 10, spec=REF)[0]
    f()[0].block_until_ready()
    t0 = time.perf_counter()
    for _ in range(5):
        f()[0].block_until_ready()
    dt = (time.perf_counter() - t0) / 5
    bps = B * n * m / dt
    rows.append(dict(name="fig9/measured_host_gather_adc",
                     us_per_call=dt * 1e6,
                     derived=f"measured;host_scan_GBps={bps/1e9:.2f}"))
    return rows


# ---------------------------------------------------------------------------
# Fig. 10 — scale-out latency (LogGP model, paper methodology)
# ---------------------------------------------------------------------------

def fig10_scaleout() -> List[Dict]:
    ds = hw.DATASETS[2]  # SYN-512 (paper's choice)
    rng = np.random.default_rng(1)
    rows = []
    for batch in (1, 16, 64):
        base = None
        for nodes in (1, 2, 4, 8, 16):
            s = hw.scaleout_latency_samples(ds, nodes, batch, rng)
            med, p99 = np.median(s), np.percentile(s, 99)
            if nodes == 1:
                base = med
            rows.append(dict(
                name=f"fig10/b={batch}/nodes={nodes}",
                us_per_call=med * 1e6,
                derived=(f"modeled;p99_us={p99*1e6:.1f};"
                         f"median_vs_1node={med/base:.3f}")))
    return rows


# ---------------------------------------------------------------------------
# Table 5 — energy per query (modeled)
# ---------------------------------------------------------------------------

def table5_energy() -> List[Dict]:
    rows = []
    for ds in hw.DATASETS:
        for batch in (1, 4, 16):
            t_cpu = hw.cpu_search_latency(ds, batch)
            t_chv = hw.chamvs_search_latency(ds, batch)
            e_cpu = t_cpu * hw.CPU_TDP_W / batch * 1e3      # mJ/query
            e_chv = t_chv * hw.TPU_V5E_W / batch * 1e3
            rows.append(dict(
                name=f"table5/{ds.name}/b={batch}",
                us_per_call=0.0,
                derived=(f"modeled;cpu_mJ={e_cpu:.1f};chamvs_mJ={e_chv:.1f};"
                         f"ratio={e_cpu/e_chv:.1f}x")))
    return rows


# ---------------------------------------------------------------------------
# Figs. 11/12 — end-to-end RALM latency / throughput
# (paper Table 2 models x retrieval interval; retrieval engine: CPU vs ChamVS)
# ---------------------------------------------------------------------------

def _lm_unit_step_time(arch: str, batch: int) -> float:
    """Per-token decode latency of ONE LM accelerator unit (the paper's
    single-GPU setup, §6.3): weight-streaming-bound on one chip + KV reads."""
    from repro.configs import get_arch
    cfg = get_arch(arch).model
    w_bytes = cfg.active_param_count() * 2
    kv_bytes = (cfg.n_layers * 2 * cfg.n_kv_heads * cfg.d_head * 512 *
                batch * 2)          # 512-token contexts, bf16
    return (w_bytes + kv_bytes) / hw.HBM_BW


def fig11_fig12_ralm() -> List[Dict]:
    """End-to-end RALM latency (Fig. 11) / throughput (Fig. 12): one LM
    unit + one retrieval engine, CPU-engine baseline vs ChamVS."""
    rows = []
    seq = 512  # paper: 512-token generations
    for arch, ds, interval_list in [
            ("dec_s", hw.DATASETS[2], [1]),
            ("dec_l", hw.DATASETS[3], [1]),
            ("encdec_s", hw.DATASETS[2], [8, 64, 512]),
            ("encdec_l", hw.DATASETS[3], [8, 64, 512])]:
        for interval in interval_list:
            n_ret = seq // interval
            # latency: batch 1 (paper disables batching for latency runs)
            step1 = _lm_unit_step_time(arch, 1)
            speedups = {}
            for engine, tfun in (("cpu", hw.cpu_search_latency),
                                 ("chamvs", hw.chamvs_search_latency)):
                t_ret = tfun(ds, batch=1)
                total = seq * step1 + n_ret * t_ret
                speedups[engine] = total
                rows.append(dict(
                    name=f"fig11/{arch}/iv={interval}/{engine}",
                    us_per_call=total / seq * 1e6,
                    derived=(f"modeled;seq_s={total:.3f};"
                             f"retrieval_share={n_ret*t_ret/total:.2f}")))
            rows.append(dict(
                name=f"fig11/{arch}/iv={interval}/speedup",
                us_per_call=0.0,
                derived=(f"modeled;chamvs_vs_cpu="
                         f"{speedups['cpu']/speedups['chamvs']:.2f}x")))
            # throughput: max batch per memory (paper: 64 small / 8 large)
            batch = 64 if arch.endswith("_s") else 8
            stepB = _lm_unit_step_time(arch, batch)
            tputs = {}
            for engine, tfun in (("cpu", hw.cpu_search_latency),
                                 ("chamvs", hw.chamvs_search_latency)):
                t_ret = tfun(ds, batch=batch)
                total = seq * stepB + n_ret * t_ret
                tputs[engine] = batch * seq / total
                rows.append(dict(
                    name=f"fig12/{arch}/iv={interval}/{engine}",
                    us_per_call=0.0,
                    derived=f"modeled;tokens_per_s={tputs[engine]:.0f}"))
            rows.append(dict(
                name=f"fig12/{arch}/iv={interval}/speedup",
                us_per_call=0.0,
                derived=(f"modeled;chamvs_vs_cpu="
                         f"{tputs['chamvs']/tputs['cpu']:.2f}x")))
    return rows


# ---------------------------------------------------------------------------
# Fig. 12 (measured) — end-to-end serving throughput on this host through
# the unified repro.serve engine (desk scale; grounds the modeled rows)
# ---------------------------------------------------------------------------

def fig12_measured_serving() -> List[Dict]:
    """Serve pipelined request batches through ``RalmEngine`` (monolithic
    on this host's devices) and report measured tokens/s, with and
    without retrieval — the measured counterpart of the Fig. 12 model."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from repro.configs import get_arch
    from repro.models import transformer as tf
    from repro.serve import DatastoreBuilder, RagConfig, RalmEngine

    cfg = dataclasses.replace(get_arch("dec_s").reduced, vocab_size=64)
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    corpus = rng.integers(0, 64, size=(64, 32), dtype=np.int32)
    ds = DatastoreBuilder(dim=cfg.d_model, nlist=8, m=8,
                          list_cap=512).from_corpus(params, cfg, corpus)
    ccfg = ds.search_config(nprobe=4, k=8)

    rows = []
    steps, batch, n_req = 16, 4, 2
    prompts = [jnp.asarray(rng.integers(0, 64, size=(batch, 8),
                                        dtype=np.int32))
               for _ in range(n_req)]
    for tag, rag in (("norag", RagConfig(mode="none")),
                     ("knnlm_iv1", RagConfig(mode="knnlm", interval=1,
                                             k=8, lam=0.25))):
        # pin max_seq so the KV-cache shape (and thus the compiled
        # programs) is identical between warmup and the timed run
        engine = RalmEngine.monolithic(params, cfg, rag,
                                       retriever=ds.retriever(ccfg),
                                       max_seq=8 + steps)
        engine.generate_batches(prompts, steps=2)       # compile warmup
        t0 = time.perf_counter()
        engine.generate_batches(prompts, steps=steps)
        dt = time.perf_counter() - t0
        ntok = n_req * batch * steps
        rows.append(dict(
            name=f"fig12_measured/dec_s/{tag}",
            us_per_call=dt / ntok * 1e6,
            derived=(f"measured;tokens_per_s={ntok/dt:.1f};"
                     f"requests={n_req};batch={batch}")))
    return rows


# ---------------------------------------------------------------------------
# Fig. 13 — optimal LM:retrieval accelerator ratio
# ---------------------------------------------------------------------------

def fig13_accelerator_ratio() -> List[Dict]:
    """LM units needed to saturate ONE ChamVS engine =
    engine_qps / (queries generated per second by one LM unit)."""
    rows = []
    span = []
    for arch, ds, intervals, batch in [
            ("dec_s", hw.DATASETS[2], [1], 64),
            ("dec_l", hw.DATASETS[3], [1], 8),
            ("encdec_s", hw.DATASETS[2], [8, 64, 512], 64),
            ("encdec_l", hw.DATASETS[3], [8, 64, 512], 8)]:
        step = _lm_unit_step_time(arch, batch)
        for iv in intervals:
            unit_qps = batch / (step * iv)
            engine_qps = batch / hw.chamvs_search_latency(ds, batch=batch)
            ratio = engine_qps / unit_qps
            span.append(ratio)
            rows.append(dict(
                name=f"fig13/{arch}/iv={iv}", us_per_call=0.0,
                derived=f"modeled;lm_units_per_engine={ratio:.2f}"))
    rows.append(dict(
        name="fig13/span", us_per_call=0.0,
        derived=(f"modeled;min={min(span):.2f};max={max(span):.1f};"
                 f"orders_of_magnitude={math_log10(max(span)/min(span)):.1f}")))
    return rows


def math_log10(x: float) -> float:
    import math
    return math.log10(x)
