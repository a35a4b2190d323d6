#!/usr/bin/env python3
"""Rate sweep of a cell, to find the knee once, when the cell
is defined (the cell's traffic file then fixes its rate).

    python bench/sweep.py --workload <name> --seed <n> --seconds <s> \\
        --rates 1,2,3

One process and one server; for each rate, one window of the cell's
requests (the sizes a run of ``--seconds`` sends at the cell's rate, so
the warm-up covers every rate) due at that rate. Per rate it prints the
completed requests per second, the tails of time to first token and of
the gap between tokens, and the median time to first token of the
window's first and last thirds: a backlog that grows through the
window shows as the last third waiting longer.
"""
from __future__ import annotations

import argparse
import json
import sys

import numpy as np

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    _, cell, cfg, traffic, lm = run.load_cell(run.ROOT, args.workload)
    try:
        run.require_chips(cell["chips"])
    except run.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return run.NO_CHIP
    run.compile_cache()
    import loadgen
    vocab = cfg["model"]["vocab_size"]
    base = loadgen.plan(traffic, args.seconds, args.seed, vocab)
    server = run.Server(cfg, lm, traffic, args.seed, run.CompileCounter(),
                        base)
    for rate in [float(r) for r in args.rates.split(",")]:
        seconds = len(base) / rate
        w = server.window(loadgen.plan(dict(traffic, rate_per_s=rate),
                                       seconds, args.seed, vocab), seconds)
        res = w["results"]
        ttft = np.array([(r.token_times[0] - r.due) * 1e3 if r.token_times
                         else np.inf for r in res])
        gaps = np.concatenate([np.diff(r.token_times) * 1e3 for r in res
                               if len(r.token_times) > 1] or [[np.nan]])
        third = max(1, len(res) // 3)
        done = [r for r in res if r.complete]
        print(json.dumps(dict(
            rate=rate, requests=len(res), failed=len(res) - len(done),
            tokens_per_s=sum(len(r.tokens) for r in done)
            / (w["t_end"] - w["t0"]),
            drain_s=w["t_end"] - w["t1"],
            programs_built=w["programs_built"],
            ttft_p50_ms=float(np.median(ttft)),
            ttft_p95_ms=float(np.percentile(ttft, 95)),
            itl_p50_ms=float(np.median(gaps)),
            itl_p95_ms=float(np.percentile(gaps, 95)),
            ttft_first_third_ms=float(np.median(ttft[:third])),
            ttft_last_third_ms=float(np.median(ttft[-third:])))),
            flush=True)
    server.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
