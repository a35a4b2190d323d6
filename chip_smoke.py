#!/usr/bin/env python3
"""Chip smoke test: serve full-width Dec-S RALM on one TPU.

    python chip_smoke.py               # one chip: the serving path
    python chip_smoke.py --four-chips  # four chips: disaggregated vs monolithic

One process, no child that touches JAX. The default run builds the
paper's Dec-S model (Table 2: 24 layers, d_model 512, 8 heads, vocab
50000, bf16; random weights from ``--seed``) and a kNN-LM datastore of
about 2^20 of its own hidden states under the SYN-512 code shape (paper
Table 3: m = 32, ksub = 256, 1024 IVF lists, nprobe 32), stands the
engine up the way ``repro.launch.serve`` does, and serves requests
through the HTTP ``Gateway`` on loopback. It then checks, on the chip:

  * every request is answered with the tokens it asked for;
  * the compiled ``chamvs_scan`` returns the reference scan's top-k on
    the queries the engine served;
  * the compiled ``decode_attn`` matches the reference at Dec-S widths
    within bf16 tolerance;
  * no kernel call was routed to a reference path
    (``registry.fallback_count() == 0``).

``--four-chips`` runs only the disaggregated engine — one LM chip and a
three-shard retrieval pool on the other three chips — and compares its
greedy tokens and retrieved ids with the monolithic engine on one chip.

Any failed check raises, so the exit code is non-zero and no result
line is printed; so does a run where JAX finds no TPU. On success the
last line of standard output is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import concurrent.futures
import json
import pathlib
import sys
import time
import urllib.request

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

from repro.launch.cache import setup_compile_cache  # noqa: E402

N_REQUESTS = 4          # requests per round through the gateway
PROMPT_LEN = 16
NEW_TOKENS = 16
SCAN_CHECK_ROWS = 16    # served queries per kernel-vs-ref scan comparison


def log(**fields) -> None:
    print(json.dumps(fields, default=float), flush=True)


class RecordingRetriever:
    """``Retriever`` proxy that keeps every query batch the engine
    searched, so the scan check runs on the queries actually served."""

    def __init__(self, inner):
        self.inner = inner
        self.queries = []

    def search(self, queries):
        self.queries.append(queries)
        return self.inner.search(queries)

    def resolve(self, ids, kind: str = "tokens"):
        return self.inner.resolve(ids, kind)


def setup(seed: int, reduced: bool, num_shards: int):
    """Model params from ``seed`` plus the launcher's datastore."""
    import jax

    from repro.configs import get_arch
    from repro.launch.serve import build_datastore
    from repro.models import transformer as tf

    spec = get_arch("dec_s")
    cfg = spec.reduced if reduced else spec.model
    params = tf.init_params(jax.random.PRNGKey(seed), cfg)
    t0 = time.perf_counter()
    ds, ccfg = build_datastore(params, cfg, spec.rag, seed=seed,
                               reduced=reduced, num_shards=num_shards)
    log(phase="datastore", keys=ds.num_vectors, shards=ds.num_shards,
        nlist=ds.index_cfg.nlist, m=ds.index_cfg.m,
        ksub=ds.index_cfg.ksub, list_cap=ds.index_cfg.list_cap,
        nprobe=ccfg.nprobe, k=ccfg.k,
        build_s=time.perf_counter() - t0)
    return cfg, spec.rag, params, ds, ccfg


def prompts_from(seed: int, vocab: int, n: int):
    import numpy as np
    rng = np.random.default_rng(seed + 1)
    return [rng.integers(0, vocab, size=PROMPT_LEN).tolist()
            for _ in range(n)]


def post_completion(url: str, prompt, max_tokens: int) -> dict:
    body = json.dumps({"prompt": prompt, "max_tokens": max_tokens}).encode()
    req = urllib.request.Request(f"{url}/v1/completions", data=body,
                                 headers={"Content-Type": "application/json"})
    t0 = time.perf_counter()
    with urllib.request.urlopen(req, timeout=900) as resp:
        out = json.loads(resp.read())
    out["client_s"] = time.perf_counter() - t0
    return out


def serve_round(url: str, prompts) -> list:
    """All prompts in flight at once; returns the completion tokens."""
    with concurrent.futures.ThreadPoolExecutor(len(prompts)) as pool:
        outs = list(pool.map(
            lambda p: post_completion(url, p, NEW_TOKENS), prompts))
    tokens = []
    for out in outs:
        toks = [int(t) for t in out["choices"][0]["text"].split()]
        if len(toks) != NEW_TOKENS or out["usage"]["completion_tokens"] \
                != NEW_TOKENS:
            raise AssertionError(f"request answered with {len(toks)} of "
                                 f"{NEW_TOKENS} tokens: {out}")
        tokens.append(toks)
    return tokens, [o["client_s"] for o in outs], \
        [o["ralm"].get("ttft_ms") for o in outs]


def agreement(a, b) -> float:
    """Share of positions where two lists of token lists agree."""
    pairs = [(x, y) for ra, rb in zip(a, b) for x, y in zip(ra, rb)]
    return sum(x == y for x, y in pairs) / max(1, len(pairs))


def topk_agreement(d_k, i_k, d_r, i_r, rtol: float = 1e-4) -> dict:
    """Compare a kernel's top-k with the reference's. Distances must
    agree to ``rtol``; ids must agree as a set per row, except where a
    candidate's distance ties (within ``rtol``) the row's k-th — a
    near-tie at the boundary may fall either way under f32 rounding."""
    import numpy as np
    d_k, d_r = np.asarray(d_k), np.asarray(d_r)
    i_k, i_r = np.asarray(i_k), np.asarray(i_r)
    finite = np.isfinite(d_r)
    if not (np.isfinite(d_k) == finite).all():
        raise AssertionError("kernel and ref disagree on empty slots")
    np.testing.assert_allclose(d_k[finite], d_r[finite], rtol=rtol,
                               atol=rtol)
    bad_rows = 0
    for dk, ik, dr, ir in zip(d_k.reshape(-1, d_k.shape[-1]),
                              i_k.reshape(-1, i_k.shape[-1]),
                              d_r.reshape(-1, d_r.shape[-1]),
                              i_r.reshape(-1, i_r.shape[-1])):
        diff = set(ik.tolist()) ^ set(ir.tolist())
        if not diff:
            continue
        kth = dr[np.isfinite(dr)].max() if np.isfinite(dr).any() else 0.0
        edge = np.concatenate([dk[np.isin(ik, list(diff))],
                               dr[np.isin(ir, list(diff))]])
        if not np.all(np.abs(edge - kth) <= rtol * max(1.0, abs(kth))):
            bad_rows += 1
    if bad_rows:
        raise AssertionError(f"{bad_rows} rows of top-k ids differ from "
                             "the reference beyond boundary ties")
    return dict(rows=int(np.prod(d_k.shape[:-1])),
                ids_equal_frac=float((i_k == i_r).mean()))


def check_scan(ds, ccfg, queries, backend) -> dict:
    """The compiled ``chamvs_scan`` vs its reference on served queries."""
    import jax.numpy as jnp

    from repro.core.chamvs import probe_lists, stack_shards
    from repro.kernels import registry
    from repro.kernels.chamvs_scan.ops import chamvs_scan, probed_operands

    q = jnp.concatenate([jnp.asarray(x, jnp.float32) for x in queries])
    stacked = stack_shards(ds.shards)
    kk = ccfg.k_prime(ds.num_shards)
    rows, equal = 0, 0.0
    for s in range(0, q.shape[0], SCAN_CHECK_ROWS):  # bounds the ref's HBM
        qs = q[s:s + SCAN_CHECK_ROWS]
        probe_ids = probe_lists(ds.params, qs, ccfg)
        ops = probed_operands(ds.params, stacked, qs, probe_ids, ccfg)
        got = chamvs_scan(*ops, kk, spec=registry.serving_spec(backend))
        want = chamvs_scan(*ops, kk, spec=registry.REF)
        out = topk_agreement(*got, *want)
        rows += out["rows"]
        equal += out["ids_equal_frac"] * out["rows"]
    return dict(queries=int(q.shape[0]), kk=kk, ids_equal_frac=equal / rows)


def check_decode_attn(cfg, seed: int, backend) -> dict:
    """The compiled ``decode_attn`` vs its reference at the model's
    widths, on waves of 8 and 5 rows over a 2048-slot cache."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import registry
    from repro.kernels.decode_attn.ops import pallas_decode_attention
    from repro.kernels.decode_attn.ref import ref_decode_attention

    key = jax.random.PRNGKey(seed + 2)
    S, H, KV, D = 2048, cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    worst = 0.0
    for B in (8, 5):
        kq, kk, kv, kp, key = jax.random.split(key, 5)
        q = jax.random.normal(kq, (B, 1, H, D), jnp.bfloat16)
        k = jax.random.normal(kk, (B, S, KV, D), jnp.bfloat16)
        v = jax.random.normal(kv, (B, S, KV, D), jnp.bfloat16)
        pos = jax.random.randint(kp, (B,), 0, S, jnp.int32)
        got = pallas_decode_attention(q, k, v, pos,
                                      spec=registry.serving_spec(backend))
        with jax.default_matmul_precision("highest"):
            want = ref_decode_attention(q.astype(jnp.float32),
                                        k.astype(jnp.float32),
                                        v.astype(jnp.float32), pos)
        got = np.asarray(got.astype(jnp.float32))
        want = np.asarray(want)
        np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)
        worst = max(worst, float(np.abs(got - want).max()))
    return dict(waves=[8, 5], seq=S, max_abs_err=worst)


def serve_phase(seed: int, reduced: bool = False, backend=None) -> None:
    """One chip: build, serve through the gateway, check the kernels."""
    import numpy as np

    from repro.kernels import registry
    from repro.serve import EngineConfig, Gateway, GatewayConfig, RalmEngine

    registry.reset_warnings()
    cfg, rag, params, ds, ccfg = setup(seed, reduced, num_shards=1)
    engine = RalmEngine.from_config(
        EngineConfig(model=cfg, rag=rag, kernel_backend=backend,
                     attn_backend=backend), params, ds, ccfg)
    scan_spec = registry.serving_spec(backend)
    log(phase="engine", backend=engine.backend.name,
        scan=scan_spec.backend, attn=engine.attn_spec.backend,
        interpret=scan_spec.use_interpret(), fallback=scan_spec.fallback)
    if not reduced and (scan_spec.backend != "pallas" or
                        engine.attn_spec.backend != "pallas" or
                        scan_spec.use_interpret() or
                        scan_spec.fallback != "error"):
        raise AssertionError("the serving path is not the compiled Pallas "
                             "path with fallback='error'")
    recorder = RecordingRetriever(engine.retriever)
    engine.retriever = recorder

    gateway = Gateway(engine, GatewayConfig(port=0, degrade=None))
    url = gateway.start_background()
    try:
        prompts = prompts_from(seed, cfg.vocab_size, N_REQUESTS)
        t0 = time.perf_counter()
        served, _, _ = serve_round(url, prompts)
        log(phase="serve", round="cold (compiles)", requests=len(served),
            tokens_each=NEW_TOKENS, wall_s=time.perf_counter() - t0)
        again, client_s, ttft_ms = serve_round(url, prompts)
        # informational: rounds batch the requests into different waves,
        # and a random-weight model's near-tied logits may then argmax
        # differently under bf16 rounding
        log(phase="serve", round="warm", requests=len(again),
            client_latency_s=client_s, ttft_ms=ttft_ms,
            token_agreement_with_cold=agreement(again, served))
    finally:
        gateway.shutdown()

    log(phase="scan_check", **check_scan(ds, ccfg, recorder.queries,
                                         backend))
    log(phase="decode_attn_check", **check_decode_attn(cfg, seed, backend))

    # informational: the same prompts through an engine on the reference
    # paths (gather-ADC scan, grouped-einsum attention)
    ref_engine = RalmEngine.from_config(
        EngineConfig(model=cfg, rag=rag, kernel_backend="ref",
                     attn_backend="ref"), params, ds, ccfg)
    ref_out = ref_engine.generate_batches(
        [np.asarray([p], np.int32) for p in prompts], steps=NEW_TOKENS)
    ref_tokens = [o[0, PROMPT_LEN:].tolist() for o in ref_out]
    log(phase="ref_engine_agreement",
        token_agreement=agreement(served, ref_tokens))

    fallbacks = registry.fallback_count()
    log(phase="fallbacks", count=fallbacks, per_op=registry.fallback_counts())
    if fallbacks:
        raise AssertionError(f"{fallbacks} kernel calls fell back to a "
                             "reference path")


def four_chip_phase(seed: int, reduced: bool = False, backend=None) -> None:
    """Disaggregated (1 LM chip + 3 retrieval chips) vs monolithic on
    one chip: the same greedy tokens and the same retrieved ids."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import registry
    from repro.serve import EngineConfig, RalmEngine, RalmRequest

    if len(jax.devices()) != 4:
        raise AssertionError(f"--four-chips needs 4 devices, found "
                             f"{len(jax.devices())}")
    registry.reset_warnings()
    cfg, rag, params, ds, ccfg = setup(seed, reduced, num_shards=3)
    prompts = prompts_from(seed, cfg.vocab_size, N_REQUESTS)

    def run(engine):
        traces = [[] for _ in prompts]
        rids = [engine.submit(RalmRequest(
            prompt=jnp.asarray([p], jnp.int32), steps=NEW_TOKENS,
            trace=tr)) for p, tr in zip(prompts, traces)]
        t0 = time.perf_counter()
        by_id = {r.request_id: r.tokens for r in engine.run()}
        wall = time.perf_counter() - t0
        tokens = [np.asarray(by_id[r])[0, PROMPT_LEN:] for r in rids]
        ids = [np.stack([np.asarray(e["ids"]) for e in
                         sorted(tr, key=lambda e: e["step"])])
               for tr in traces]
        return tokens, ids, wall

    mono = RalmEngine.from_config(
        EngineConfig(model=cfg, rag=rag, kernel_backend=backend,
                     attn_backend=backend), params, ds, ccfg)
    mono_tok, mono_ids, mono_s = run(mono)
    dis = RalmEngine.from_config(
        EngineConfig(model=cfg, rag=rag, kernel_backend=backend,
                     attn_backend=backend, disaggregate=True,
                     lm_devices=1, ret_devices=3), params, ds, ccfg)
    lm = [d.id for d in dis.backend.lm_mesh.devices.flat]
    ret = [d.id for d in dis.backend.ret_mesh.devices.flat]
    placed = sorted({d.id for d in dis.retriever.db_shard.codes.devices()})
    log(phase="disaggregated", lm_devices=lm, retrieval_devices=ret,
        shard_devices=placed, mono_wall_s=mono_s)
    if sorted(lm + ret) != [d.id for d in jax.devices()] or placed != ret:
        raise AssertionError("the disaggregated engine does not use all "
                             "four chips as 1 LM + 3 retrieval shards")
    dis_tok, dis_ids, dis_s = run(dis)
    tok_equal = all((a == b).all() for a, b in zip(mono_tok, dis_tok))
    ids_equal = all(a.shape == b.shape and (a == b).all()
                    for a, b in zip(mono_ids, dis_ids))
    log(phase="parity", requests=len(prompts), tokens_each=NEW_TOKENS,
        tokens_equal=tok_equal, retrieved_ids_equal=ids_equal,
        retrieval_steps=int(sum(len(x) for x in dis_ids)),
        dis_wall_s=dis_s,
        pool_ratio=(dis.times.optimal_ratio() if dis.times else None))
    if not (tok_equal and ids_equal):
        raise AssertionError("disaggregated tokens/ids differ from the "
                             "monolithic engine")
    fallbacks = registry.fallback_count()
    if fallbacks:
        raise AssertionError(f"{fallbacks} kernel calls fell back to a "
                             "reference path")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the disaggregated 1 LM + 3 retrieval "
                         "chip engine against the monolithic one")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    setup_compile_cache()
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2
    t0 = time.perf_counter()
    if args.four_chips:
        four_chip_phase(args.seed)
    else:
        serve_phase(args.seed)
    log(phase="done", total_s=time.perf_counter() - t0)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
