"""Traffic: one general generator driven by a traffic file.

A traffic file (``bench/traffic/<name>.json``) holds parameters only:

    {"rate_per_s": 0.75,
     "prompt_len": {"median": 128, "sigma": 0.8, "min": 16, "max": 512},
     "output_len": {"median": 64, "sigma": 0.8, "min": 8, "max": 256},
     "sample_requests": 6}

The loop is open: requests are due on a fixed schedule at
``rate_per_s``, sent whether or not earlier ones have finished. Lengths
are lognormal and clipped, to the token, as users send them.

Every seed gets the same schedule: quantiles of the distributions,
paired, ordered and spaced by one fixed draw. The seed draws the prompt
tokens (and the cell's weights and datastore), not the amount of work
or when it comes: an order drawn from the seed moved the tail of the
gaps between tokens by a third from seed to seed. The warm-up builds
the programs of exactly the lengths a run sends (``warmup``,
``decode_shapes``, ``length_pairs`` take the run's plan).
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Dict, List, Tuple

import numpy as np

@dataclasses.dataclass
class Request:
    index: int
    prompt: List[int]
    max_tokens: int
    due_s: float                      # offset from the window's start


def lognormal_quantiles(spec: Dict, n: int) -> np.ndarray:
    """``n`` evenly spaced quantiles of the clipped lognormal ``spec``."""
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def plan(traffic: Dict, seconds: float, seed: int, vocab: int
         ) -> List[Request]:
    """The requests of one run, every one due in ``[0, seconds)``."""
    fixed = np.random.default_rng(0)
    n = max(1, round(traffic["rate_per_s"] * seconds))
    prompts = lognormal_quantiles(traffic["prompt_len"], n)
    outputs = lognormal_quantiles(traffic["output_len"], n)
    # one pairing of the two lengths, one order and one spacing for
    # every seed
    outputs = fixed.permutation(outputs)
    order = fixed.permutation(n)
    prompts, outputs = prompts[order], outputs[order]
    rate = traffic["rate_per_s"]
    gaps = fixed.permutation([-math.log(1 - (i + 0.5) / n) / rate
                              for i in range(n)])
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    due = due * (seconds / max(seconds, float(np.sum(gaps))))
    rng = np.random.default_rng(seed)
    return [Request(index=i,
                    prompt=rng.integers(0, vocab, int(prompts[i])).tolist(),
                    max_tokens=int(outputs[i]), due_s=float(due[i]))
            for i in range(n)]


def warmup(plan: List[Request], kv_slots: int, max_seq: int, vocab: int
           ) -> List[List[Request]]:
    """Rounds of requests, served one round at a time before the window,
    that make the server build the programs ``plan`` uses:

    * one request of each prompt length of the plan (a prefill program
      each), one token each;
    * ``kv_slots`` requests of the shortest prompt and 1..``kv_slots``
      tokens, so the wave shrinks through every size from ``kv_slots`` to
      one (the per-wave work sized to the exact number of rows).
    """
    rng = np.random.default_rng(0)
    lengths = sorted({len(r.prompt) for r in plan})
    if lengths[0] + kv_slots > max_seq:
        raise ValueError("the shortest prompt and kv_slots tokens exceed "
                         "the configuration's max_seq")
    return [[Request(i, rng.integers(0, vocab, g).tolist(), 1, 0.0)
             for i, g in enumerate(lengths)],
            [Request(i, rng.integers(0, vocab, lengths[0]).tolist(), i + 1,
                     0.0) for i in range(kv_slots)]]


def decode_shapes(plan: List[Request], kv_slots: int, max_seq: int,
                  seq_block: int) -> List[Tuple[int, int]]:
    """Every (pow2 wave bucket, attention length) a decode wave of the
    plan (or of its warm-up) can have: attention reads are cropped to the
    block-aligned valid prefix, which runs from just past the shortest
    prompt to the longest prompt plus the longest output."""
    top = min(max_seq, max(len(r.prompt) + r.max_tokens for r in plan))
    lo = -(-(min(len(r.prompt) for r in plan) + 1) // seq_block) * seq_block
    kv_lens = range(min(lo, max_seq), min(max_seq, -(-top // seq_block)
                                          * seq_block) + 1, seq_block)
    buckets = [1 << b for b in range(int(math.log2(kv_slots)) + 1)]
    return [(b, k) for b in buckets for k in kv_lens]


def length_pairs(plan: List[Request]) -> List[Tuple[int, int]]:
    """Every (prompt length, output length) pair of the plan."""
    return sorted({(len(r.prompt), r.max_tokens) for r in plan})
