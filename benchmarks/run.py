"""Benchmark driver — one function per paper table/figure + the roofline.

Prints ``name,us_per_call,derived`` CSV. Modeled rows are tagged `modeled`
inside `derived`; wall-clock rows on this host are tagged `measured`.

``--mode retrieval`` instead sweeps batch size x nprobe against the
``RetrievalService`` and writes ``BENCH_retrieval.json`` with the
queue-wait / scan / merge breakdown (see benchmarks/retrieval_bench.py).

``--mode serve`` sweeps tokens/s vs. active wave size over the
wave-batched serving engine and writes ``BENCH_serve.json`` with the
per-pool step breakdown (see benchmarks/serve_bench.py).

``--mode kernels`` sweeps the fused single-dispatch ``chamvs_scan``
against the staged per-shard pipeline over (batch, db size, nprobe,
shards) and writes ``BENCH_kernels.json`` with the per-stage breakdown
(see benchmarks/kernels_bench.py).

``--mode decode-attn`` sweeps the length-aware decode-attention path
against the legacy full-seq einsum over (batch, pool seq, window, GQA
ratio) and writes ``BENCH_decode_attn.json`` (see
benchmarks/decode_attn_bench.py).

``--mode speculation`` sweeps speculative retrieval (speculate_k x
interval x wave size) against a speculation-off baseline over a
run-structured corpus and merges a ``speculation`` section — acceptance
rate, rollback counts, net hidden fraction of the per-step retrieval
block — into ``BENCH_serve.json`` (see benchmarks/speculation_bench.py).

``--mode chaos`` serves request streams against seeded fault plans
(replica crash / hang / slowdown / whole-shard outage at the retrieval
scan boundary) and merges a ``chaos`` section — availability, settled
p99 TTFT vs the fault-free baseline, partial-result accounting,
ejection/recovery counts, plus the FT-armed-but-fault-free inertness
parity — into ``BENCH_serve.json`` (see benchmarks/chaos_bench.py).

``--mode traffic`` drives the HTTP serving gateway with a closed-loop
capacity calibration plus an open-loop Poisson sweep (heavy-tailed
lengths, multi-tenant, up to 2x overload) and merges a ``traffic``
section — p50/p99 TTFT/TPOT, shed + degrade counts, greedy-parity
replay — into ``BENCH_serve.json`` (see benchmarks/loadgen.py).
"""
from __future__ import annotations

import argparse
import sys


def main() -> None:
    # allow running as `python -m benchmarks.run` from the repo root
    sys.path.insert(0, "src")
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode",
                    choices=["figures", "retrieval", "serve", "kernels",
                             "decode-attn", "traffic", "speculation",
                             "chaos"],
                    default="figures")
    ap.add_argument("--out", default=None,
                    help="output path for the sweep modes")
    args = ap.parse_args()
    from repro.launch.cache import setup_compile_cache
    setup_compile_cache()

    if args.mode == "retrieval":
        from benchmarks import retrieval_bench
        retrieval_bench.main(args.out or "BENCH_retrieval.json")
        return

    if args.mode == "decode-attn":
        from benchmarks import decode_attn_bench
        decode_attn_bench.main(args.out or "BENCH_decode_attn.json")
        return

    if args.mode == "kernels":
        from benchmarks import kernels_bench
        kernels_bench.main(args.out or "BENCH_kernels.json")
        return

    if args.mode == "serve":
        from benchmarks import serve_bench
        serve_bench.main(args.out or "BENCH_serve.json")
        return

    if args.mode == "speculation":
        from benchmarks import speculation_bench
        speculation_bench.main(args.out or "BENCH_serve.json")
        return

    if args.mode == "chaos":
        from benchmarks import chaos_bench
        chaos_bench.main(args.out or "BENCH_serve.json")
        return

    if args.mode == "traffic":
        from benchmarks import loadgen
        loadgen.main(args.out or "BENCH_serve.json")
        return

    from benchmarks import paper_figures as pf
    from benchmarks import roofline

    sections = [
        ("fig7", pf.fig7_queue_probability),
        ("fig8", pf.fig8_resource_saving),
        ("fig9", pf.fig9_search_latency),
        ("fig10", pf.fig10_scaleout),
        ("table5", pf.table5_energy),
        ("fig11_fig12", pf.fig11_fig12_ralm),
        ("fig12_measured", pf.fig12_measured_serving),
        ("fig13", pf.fig13_accelerator_ratio),
        ("roofline", roofline.roofline_rows),
    ]
    print("name,us_per_call,derived")
    for _, fn in sections:
        try:
            rows = fn()
        except Exception as e:  # keep the suite running; report the failure
            rows = [dict(name=f"{fn.__name__}/ERROR", us_per_call=0.0,
                         derived=str(e)[:120].replace(",", ";"))]
        for r in rows:
            print(f"{r['name']},{r['us_per_call']:.2f},{r['derived']}")


if __name__ == "__main__":
    main()
