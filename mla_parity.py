#!/usr/bin/env python3
"""Agreement of the served DeepSeek-V2-Lite with its plain reference, at
the published widths, on one TPU.

    python mla_parity.py [--seed N] [--steps 64] [--trace DIR]

One process. It makes the weights of the benchmark configuration
``dsv2_lite-syn2048`` from ``--seed`` with the configuration's LM module
(``bench/lm/deepseek_v2.py``), stands the engine up with the
configuration's pool (32 slots of 4096 positions, attention read in
512-position blocks), prefills four prompts of 300, 1024, 2000 and 3000
tokens through the padded-bucket prefill, and decodes ``--steps`` tokens
of each through the latent pool in one wave per step (tokens forced from
the seed, so the positions do not depend on the argmax of near-tied
random logits). Every step's logits and kNN-LM query (the last hidden
state) are kept.

The reference runs the module's non-absorbed forward pass over each
prompt with its forced tokens, in blocks of positions: float32 at
highest (``f32``), and the same with scaled fp8 matmuls (``fp8``, the
lower-precision control). The last line prints, for the program against
the float32 reference and for the fp8 control against it, the largest
relative L2 distance of a row of logits and of a query, over every
served position, and whether they agree: the program within
``TOLERANCE`` and the control beyond it (the exit code is 1 otherwise).

With ``--trace DIR`` the decode steps run under the profiler, and the
last line also gives the device time of the expert matmuls (``gmm``) and
of the latent attention (``decode_attn_latent``) over those steps, and
each one's share of its roofline: for ``gmm`` the larger of the routed
experts' operations over the bf16 peak and their weight bytes over the
HBM peak (from the engine's routing counters, ``engine.moe_stats()``,
and the LM module's ``expert_flops`` / ``expert_bytes``), for the latent
attention the cached rows' bytes (``latent_attn_bytes``) over the HBM
peak, each divided by the kernel's time.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "bench"))
sys.path.insert(0, str(ROOT / "src"))

CELL = "dsv2_lite.syn2048.poisson_long"
PROMPTS = (300, 1024, 2000, 3000)
#: largest relative L2 distance of a row of logits or of a query from the
#: float32 reference: bf16 activations through six layers, with a router
#: tie now and then choosing another sixth expert, read about 3% on a
#: v5e; the fp8 control reads about 20%
TOLERANCE = 0.08


def log(**fields) -> None:
    print(json.dumps(fields, default=float), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=2300000017)
    ap.add_argument("--steps", type=int, default=64)
    ap.add_argument("--trace", default=None, metavar="DIR")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    import reference as ref
    import run
    from repro.core.rag import RagConfig
    from repro.launch.cache import setup_compile_cache
    from repro.serve import RalmEngine, RalmRequest

    t0 = time.perf_counter()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"mla_parity: needs a TPU, found {dev.platform!r}",
              file=sys.stderr)
        return 3
    setup_compile_cache()
    _, _, cfg, _, lm = run.load_cell(ROOT, CELL)
    m = lm.Model.from_config(cfg["model"])
    eng_cfg = cfg["engine"]
    params = lm.make_weights(ref.seed_key(args.seed), m)
    engine = RalmEngine.monolithic(
        params, run.model_config(cfg), RagConfig(mode="none"),
        max_seq=eng_cfg["max_seq"], kv_slots=eng_cfg["kv_slots"],
        attn_seq_block=eng_cfg["attn_seq_block"])
    rng = np.random.default_rng(args.seed)
    seqs, served = [], []
    for T0 in PROMPTS:
        prompt = rng.integers(1, m.vocab_size, (1, T0)).astype(np.int32)
        forced = rng.integers(1, m.vocab_size, args.steps).astype(np.int32)
        seqs.append(engine.start(RalmRequest(prompt=jnp.asarray(prompt),
                                             steps=args.steps + 1)))
        served.append((prompt[0], forced, [], []))
    log(phase="prefill", s=time.perf_counter() - t0)
    jax.block_until_ready([s.hidden0 for s in seqs])
    moe0 = engine.moe_stats() or dict(assignments=0, experts_touched=0)
    if args.trace:
        jax.profiler.start_trace(args.trace)
    for t in range(args.steps + 1):
        outs = engine.dispatch_wave(seqs)
        for seq, (logits, hidden), (_, forced, lg, hd) in zip(
                seqs, outs, served):
            lg.append(logits[0].astype(jnp.float32))
            hd.append(hidden[0].astype(jnp.float32))
            if t < args.steps:
                seq.cur = jnp.asarray(forced[t:t + 1])[None]
                seq.step += 1
    jax.block_until_ready([x for s in served for x in s[2] + s[3]])
    kernels = None
    if args.trace:
        jax.profiler.stop_trace()
        kernels = kernel_shares(args.trace, cfg["model"], lm, engine, moe0,
                                [len(p) for p, _, _, _ in served],
                                args.steps, dev.device_kind)
    log(phase="decode", s=time.perf_counter() - t0,
        fallbacks=__import__("repro.kernels.registry",
                             fromlist=["x"]).fallback_count())

    width = -(-(max(PROMPTS) + args.steps) // 512) * 512
    toks = np.zeros((len(PROMPTS), width), np.int32)
    for i, (prompt, forced, _, _) in enumerate(served):
        toks[i, :len(prompt) + args.steps] = np.concatenate([prompt, forced])
    got_l = jnp.stack([jnp.stack(s[2]) for s in served])    # [R, S+1, V]
    got_h = jnp.stack([jnp.stack(s[3]) for s in served])    # [R, S+1, d]
    out = {}
    for prec in (ref.REFERENCE, ref.CONTROL):
        h = lm.forward_hidden(params, jnp.asarray(toks), m, prec)
        rows = jnp.stack([h[i, len(p) - 1:len(p) + args.steps]
                          for i, (p, _, _, _) in enumerate(served)])
        lg = jnp.stack([lm.lm_logits(params, r, m, prec) for r in rows])
        out[prec] = (rows, lg)
    log(phase="reference", s=time.perf_counter() - t0)

    def rel(a, b):
        return float(jnp.max(jnp.linalg.norm(a - b, axis=-1)
                             / jnp.linalg.norm(b, axis=-1)))
    h_ref, l_ref = out[ref.REFERENCE]
    h_ctl, l_ctl = out[ref.CONTROL]
    program = {"logits": rel(got_l, l_ref), "query": rel(got_h, h_ref)}
    control = {"logits": rel(l_ctl, l_ref), "query": rel(h_ctl, h_ref)}
    agree = (max(program.values()) <= TOLERANCE
             and min(control.values()) > TOLERANCE)
    log(device=dev.device_kind, positions=int(got_l.shape[0] * got_l.shape[1]),
        program=program, control=control, tolerance=TOLERANCE, agree=agree,
        moe=engine.moe_stats(), kernels=kernels,
        s=time.perf_counter() - t0)
    return 0 if agree else 1


def kernel_shares(trace_dir, model, lm, engine, moe0, prompts, steps, kind):
    """Device time of ``gmm`` and ``decode_attn_latent`` in the traced
    decode steps, and each one's share of its roofline."""
    import devtrace
    import spans
    from peaks import lookup
    trace = devtrace.load(trace_dir)
    planes = devtrace.devices(trace)
    if not planes:
        return None
    peak = lookup(kind)
    ops = trace[planes[0]][devtrace.OPS]

    def busy(name):
        return 1e-9 * sum(b - a for a, b in devtrace._union(
            [(s, s + d) for n, s, d in ops if spans.kernel(n) == name]))
    moe = engine.moe_stats()
    assigned = moe["assignments"] - moe0["assignments"]
    touched = moe["experts_touched"] - moe0["experts_touched"]
    t_gmm, t_lat = busy("gmm"), busy("decode_attn_latent")
    flops = lm.expert_flops(model, assigned)
    gmm_bytes = lm.expert_bytes(model, touched)
    lat_bytes = sum(lm.latent_attn_bytes(model, t0 + j)
                    for t0 in prompts for j in range(1, steps + 1))
    return dict(
        gmm_s=t_gmm, assignments=assigned, experts_touched=touched,
        gmm_roofline_pct=100.0 * max(flops / peak["bf16_flops"],
                                     gmm_bytes / peak["hbm_bytes_per_s"])
        / t_gmm if t_gmm else None,
        gmm_bound="bytes" if gmm_bytes / peak["hbm_bytes_per_s"]
        > flops / peak["bf16_flops"] else "flops",
        latent_s=t_lat,
        latent_attn_roofline_pct=100.0 * lat_bytes
        / peak["hbm_bytes_per_s"] / t_lat if t_lat else None)


if __name__ == "__main__":
    sys.exit(main())
