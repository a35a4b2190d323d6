#!/usr/bin/env python3
"""Readings that set a cell's limits: the compared numbers of the program
on many seeds, and of the lower-precision control on some of them.

    python bench/control.py --workload <name> --seconds <s> \\
        --seeds 1,2,3,... [--control-seeds 1,2,3]

One process: for each seed it makes that seed's weights and datastore,
serves one window of the cell's own traffic through the gateway, and
reads ``check.readings`` of what was served. For a control seed it also
reads ``check.control_readings``: the reference computed a precision
lower than the configuration states, in the program's place, on the same
prompts and tokens. The benchmark's own runs never run the control. The
last line is the largest program reading and the smallest control
reading of each number.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    _, cell, cfg, traffic, lm = run.load_cell(run.ROOT, args.workload)
    try:
        run.require_chips(cell["chips"])
    except run.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return run.NO_CHIP
    run.compile_cache()
    compiles = run.CompileCounter()
    import check
    seeds = [int(s) for s in args.seeds.split(",")]
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    program, control = {}, {}
    import loadgen
    for seed in seeds:
        plan = loadgen.plan(traffic, args.seconds, seed,
                            cfg["model"]["vocab_size"])
        server = run.Server(cfg, lm, traffic, seed, compiles, plan)
        w = server.window(plan, args.seconds)
        server.close()
        failed = sum(not r.complete for r in w["results"])
        got = server.readings(w["results"], w["rows"])
        line = dict(seed=seed, failed=failed, program=got)
        for k, v in got.items():
            program[k] = max(program.get(k, v), v)
        if seed in controls:
            line["control"] = check.control_readings(server.reference)
            for k, v in line["control"].items():
                control[k] = min(control.get(k, v), v)
        print(json.dumps(line), flush=True)
        del server, w
        gc.collect()
    print(json.dumps(dict(seeds=len(seeds), controls=len(controls),
                          program_max=program, control_min=control)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
