"""Fused vs staged ChamVS scan sweep (``--mode kernels``).

One row per (batch B, database size n, nprobe, shard count): the same
retrieval-service flush served by

  * **staged** — the per-shard pipeline (one chamvs dispatch per shard,
    materialized per-shard candidates, separate top-k per shard), the
    parity oracle; vs
  * **fused**  — ONE ``chamvs_scan`` dispatch over the
    ``stack_shards``-packed stack.

Both run the same kernel backend — default "pallas", the backend the
fusion claim is actually about: staged lowers to S separate
``pq_adc`` ``pallas_call``s plus per-shard top-k passes, fused lowers
to ONE ``chamvs_scan`` ``pallas_call`` with the running top-k' in its
grid. (With ``backend="ref"`` both modes already compile to a single
XLA executable per flush — there is no dispatch structure left to
measure, only XLA fusion luck — so the ref sweep is not the committed
artifact.) On a CPU host the Pallas kernels run in interpret mode;
relative cost there tracks grid-step count and per-step work, which is
exactly what the fusion changes — on a real accelerator they compile
(interpret mode is derived from the platform).

Methodology notes (documented in the JSON meta):
  * batch sizes start at the service's wave scale (B >= 8) — sub-wave
    flushes are dispatch-overhead-dominated and the whole point of the
    retrieval service is that B=1 submits coalesce into waves;
  * the two modes are measured in adjacent paired windows and the
    reported speedup is the MEDIAN of per-pair ratios: sandbox/container
    noise on this host comes in multi-second epochs (a window can run
    1.5x slower than its neighbor), so per-mode minima can sample
    different epochs and fabricate regressions — the paired ratio
    cancels the epoch, the median rejects the stragglers. Reported
    walls are per-mode medians;
  * XLA runs single-threaded-eigen (set before jax imports) — on the
    2-vCPU sandbox this removes thread-pool jitter that otherwise
    swamps the structural difference;
  * the index uses nlist >= PALLAS_MIN_NLIST so the probe stage really
    runs the Pallas centroid scan; ``pallas_fallbacks`` per row proves
    no reference path leaked into a "pallas" number.

Emits ``BENCH_kernels.json`` via ``python -m benchmarks.run --mode
kernels``.
"""
from __future__ import annotations

import json
import os
import time
from typing import Dict, List, Sequence

# must happen before jax initializes its CPU client (benchmarks.run only
# imports this module for --mode kernels, before any jax import)
os.environ.setdefault("XLA_FLAGS", "--xla_cpu_multi_thread_eigen=false")

import jax
import jax.numpy as jnp
import numpy as np


def _build(dim: int, n_vecs: int, nlist: int, num_shards: int):
    from repro.core.ivfpq import IVFPQConfig, build_shards, train_ivfpq
    # cap at 4x the mean list load: k-means cluster sizes are skewed and
    # the padded layout must hold the largest per-shard list slice
    icfg = IVFPQConfig(dim=dim, nlist=nlist, m=max(dim // 8, 4),
                       list_cap=max(4 * n_vecs // (nlist * num_shards), 64))
    key = jax.random.PRNGKey(0)
    vecs = jax.random.normal(key, (n_vecs, dim))
    params = train_ivfpq(key, vecs[:min(n_vecs, 4096)], icfg,
                         kmeans_iters=6)
    shards = build_shards(params, np.asarray(vecs), icfg,
                          num_shards=num_shards)
    return icfg, params, shards


def _make_service(params, shards, cfg, max_batch: int):
    from repro.retrieval.service import RetrievalService, ServiceConfig
    return RetrievalService.local(
        params, shards, cfg, ServiceConfig(max_batch=max_batch,
                                           measure=True))


def _window(svc, queries, iters: int) -> float:
    svc.stats.reset()
    t0 = time.perf_counter()
    for it in range(1, iters + 1):
        svc.search(queries[it])
    return time.perf_counter() - t0


def run_sweep(
    batch_sizes: Sequence[int] = (8, 16),
    n_vecs_sweep: Sequence[int] = (4096, 8192),
    nprobes: Sequence[int] = (4, 16),
    shard_counts: Sequence[int] = (1, 4, 8),
    dim: int = 64,
    nlist: int = 128,
    k: int = 10,
    iters: int = 3,
    windows: int = 5,
    backend: str = "pallas",
) -> List[Dict[str, object]]:
    """One row per (B, n, nprobe, shards) with the fused and staged
    wall/stage breakdown side by side."""
    from repro.core.chamvs import ChamVSConfig
    from repro.kernels import registry

    rng = np.random.default_rng(0)
    rows: List[Dict[str, object]] = []
    for n_vecs in n_vecs_sweep:
        for num_shards in shard_counts:
            icfg, params, shards = _build(dim, n_vecs, nlist, num_shards)
            for nprobe in nprobes:
                for batch in batch_sizes:
                    queries = jnp.asarray(
                        rng.normal(size=(iters + 1, batch, dim)),
                        jnp.float32)
                    registry.reset_warnings()
                    svcs, walls = {}, {"fused": [], "staged": []}
                    for mode, fused in (("fused", True), ("staged", False)):
                        cfg = ChamVSConfig(ivfpq=icfg, nprobe=nprobe, k=k,
                                           backend=backend, fused=fused)
                        svcs[mode] = _make_service(params, shards, cfg,
                                                   batch)
                        svcs[mode].search(queries[0])   # warmup/compile
                    # adjacent paired windows: host noise epochs hit both
                    # modes of a pair, so the per-pair ratio cancels them
                    for _ in range(windows):
                        walls["staged"].append(
                            _window(svcs["staged"], queries, iters))
                        walls["fused"].append(
                            _window(svcs["fused"], queries, iters))
                    speedup = float(np.median(
                        [s / f for s, f in zip(walls["staged"],
                                               walls["fused"])]))
                    res = {}
                    for mode in ("fused", "staged"):
                        snap = svcs[mode].stats.snapshot()
                        res[mode] = dict(
                            wall_us_per_flush=float(
                                np.median(walls[mode])) / iters * 1e6,
                            scan_us=snap["scan"]["mean_us"],
                            merge_us=snap["merge"]["mean_us"],
                            scan_dispatches_per_flush=snap[
                                "scan_dispatches"] / snap["num_batches"],
                        )
                    row = dict(
                        batch=batch, n_vecs=n_vecs, nprobe=nprobe,
                        num_shards=num_shards, backend=backend,
                        pallas_fallbacks=registry.fallback_count(),
                        fused=res["fused"], staged=res["staged"],
                        speedup=speedup,
                    )
                    rows.append(row)
                    print(f"B={batch} n={n_vecs} nprobe={nprobe} "
                          f"S={num_shards}: fused "
                          f"{res['fused']['wall_us_per_flush']:.0f}us vs "
                          f"staged "
                          f"{res['staged']['wall_us_per_flush']:.0f}us "
                          f"({row['speedup']:.2f}x)")
    return rows


def main(out_path: str = "BENCH_kernels.json") -> None:
    rows = run_sweep()
    worse = [r for r in rows if r["speedup"] < 1.0]
    meta = dict(
        backend=rows[0]["backend"] if rows else "ref",
        note="fused = ONE chamvs_scan pallas_call over all shards; "
             "staged = per-shard pq_adc pallas_calls + per-shard top-k "
             "(parity oracle). Same backend both sides (pallas, "
             "interpret mode on this CPU host). speedup = median of "
             "adjacent paired-window ratios (cancels host noise "
             "epochs); walls are per-mode medians; single-threaded-"
             "eigen XLA; B >= 8 (wave scale — the service coalesces "
             "B=1 submits); nlist >= PALLAS_MIN_NLIST so the probe "
             "stage is genuinely Pallas (pallas_fallbacks per row).",
        points=len(rows),
        fused_never_slower=not worse,
    )
    with open(out_path, "w") as f:
        json.dump(dict(meta=meta, rows=rows), f, indent=2)
    print(f"wrote {out_path} ({len(rows)} rows; "
          f"fused_never_slower={not worse})")
    if worse:
        for r in worse:
            print(f"  REGRESSION: B={r['batch']} n={r['n_vecs']} "
                  f"nprobe={r['nprobe']} S={r['num_shards']} "
                  f"speedup={r['speedup']:.2f}")


if __name__ == "__main__":
    import sys
    sys.path.insert(0, "src")
    main()
