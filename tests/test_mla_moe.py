"""DeepSeek-V2's block through the serving path: multi-head latent
attention over a latent KV pool and dropless routed + shared experts.

  * (a) the engine's prefill-then-decode logits and retrieval queries
    match the benchmark's plain float32 reference
    (``bench/lm/deepseek_v2.py``) on its seeded weights;
  * (b) the latent mode of ``decode_attn`` (interpret mode) matches the
    non-absorbed per-head einsum over ragged positions and slots;
  * (c) dropless MoE matches an all-experts reference, the megablox
    ``gmm`` path (interpret mode) matches the CPU path, and a token's
    output is the same alone and inside a wave of 32;
  * (d) a padded-bucket prefill equals the exact-length prefill (latent
    rows and logits);
  * (e) YaRN by hand at DeepSeek-V2-Lite's settings;
  * the routing counters reach /metricsz, and a dense model does no such
    work; parameter counts of the published model by hand.
"""
import dataclasses
import importlib.util
import math
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import registry
from repro.kernels.decode_attn.kernel import fused_latent_decode_attention
from repro.kernels.decode_attn.ref import ref_latent_decode_attention
from repro.models import moe as moe_lib
from repro.models import transformer as tf
from repro.models.config import ModelConfig
from repro.models.layers import yarn_correction_range, yarn_inv_freq
from repro.serve import RagConfig, RalmEngine, RalmRequest
from repro.serve.engine import pads_unread, prefill_bucket

BENCH = pathlib.Path(__file__).resolve().parents[1] / "bench"

#: DeepSeek-V2's block at CPU widths: three layers, the first dense; 8
#: experts top-2 unrenormalised and 1 shared; latent 32, rope 16
TINY = dict(n_layers=3, d_model=64, n_heads=4, n_kv_heads=4, d_ff=96,
            vocab_size=256, block="moe", n_experts=8, top_k=2, d_expert=32,
            n_shared_experts=1, first_k_dense=1, norm_topk_prob=False,
            routed_scaling_factor=1.0, kv_lora_rank=32, qk_nope_head_dim=16,
            qk_rope_head_dim=16, v_head_dim=16, rope_theta=10000.0,
            yarn_factor=40.0, yarn_original_max_position=4096,
            yarn_beta_fast=32.0, yarn_beta_slow=1.0, yarn_mscale=0.707,
            yarn_mscale_all_dim=0.707, norm_eps=1e-6, tie_embeddings=False)
CFG = ModelConfig(name="tiny-dsv2", **TINY)

#: DeepSeek-V2-Lite's published config, all 27 layers
LITE = ModelConfig(
    name="dsv2-lite", n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16,
    d_ff=10944, vocab_size=102400, block="moe", n_experts=64, top_k=6,
    d_expert=1408, n_shared_experts=2, first_k_dense=1,
    norm_topk_prob=False, kv_lora_rank=512, qk_nope_head_dim=128,
    qk_rope_head_dim=64, v_head_dim=128, yarn_factor=40.0,
    yarn_original_max_position=4096, yarn_mscale=0.707,
    yarn_mscale_all_dim=0.707, norm_eps=1e-6)


@pytest.fixture(scope="module")
def lm():
    """The benchmark's LM module, loaded by path with its ``reference``."""
    sys.path.insert(0, str(BENCH))
    spec = importlib.util.spec_from_file_location(
        "bench_lm_deepseek_v2", BENCH / "lm" / "deepseek_v2.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def weights(lm):
    m = lm.Model.from_config(dict(TINY, init_scale=0.05))
    return m, lm.make_weights(jax.random.PRNGKey(7), m)


def _rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return np.linalg.norm(a - b, axis=-1) / np.linalg.norm(b, axis=-1)


# ---------------------------------------------------------------------------
# (a) the serving path against the plain reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("attn", ["ref", "pallas"])
def test_engine_prefill_then_decode_matches_reference(lm, weights, attn):
    """Two prompts of 11 and 6 tokens prefilled (bucket 16) then decoded
    12 steps through the pool, on the reference's own bf16 weights.
    Tolerance: 3% relative L2 per row of logits and of hidden states.
    The program keeps bf16 activations through three layers where the
    reference is float32 at highest; the rows read under 1% here, and a
    softmax scale without YaRN's mscale, or rotary positions over halves
    instead of the published channel pairs, fails it."""
    m, params = weights
    import reference as ref
    eng = RalmEngine.monolithic(params, CFG, RagConfig(mode="none"),
                                max_seq=32, kv_slots=4, attn_backend=attn)
    rng = np.random.default_rng(0)
    for T0 in (11, 6):
        prompt = rng.integers(1, CFG.vocab_size, (1, T0)).astype(np.int32)
        forced = rng.integers(1, CFG.vocab_size, 12).astype(np.int32)
        seq = eng.start(RalmRequest(prompt=jnp.asarray(prompt), steps=13))
        got_l, got_h = [], []
        for t in range(13):
            logits, hidden = eng.dispatch_wave([seq])[0]
            got_l.append(logits[0])
            got_h.append(hidden[0])
            if t < 12:
                seq.cur = jnp.asarray(forced[t:t + 1])[None]
                seq.step += 1
        toks = jnp.asarray(np.concatenate([prompt[0], forced])[None])
        h = lm.forward_hidden(params, toks, m, ref.REFERENCE)[0, T0 - 1:]
        want_l = lm.lm_logits(params, h, m, ref.REFERENCE)
        assert _rel(jnp.stack(got_h), h).max() < 0.03
        assert _rel(jnp.stack(got_l), want_l).max() < 0.03
        eng.release(seq)


# ---------------------------------------------------------------------------
# (b) the latent mode of decode_attn
# ---------------------------------------------------------------------------

def test_latent_kernel_matches_non_absorbed_attention():
    """Per-head keys ``c W_UK`` (+ the shared roped key) and values
    ``c W_UV``, scored against per-head queries, equal the absorbed
    query against the latent rows, weighted latents times W_UV: ragged
    positions, slots out of order, rows read up to ``kv_len``."""
    H, r, nope, rope, v, S = 4, 64, 16, 16, 16, 256
    ks = jax.random.split(jax.random.PRNGKey(3), 6)
    pool = jax.random.normal(ks[0], (6, S, r + rope), jnp.float32)
    q_nope = jax.random.normal(ks[1], (4, H, nope), jnp.float32)
    q_pe = jax.random.normal(ks[2], (4, H, rope), jnp.float32)
    w_uk = jax.random.normal(ks[3], (r, H, nope), jnp.float32) * 0.1
    w_uv = jax.random.normal(ks[4], (r, H, v), jnp.float32) * 0.1
    slots = jnp.asarray([4, 0, 2, 5], jnp.int32)
    pos = jnp.asarray([0, 130, 255, 37], jnp.int32)
    scale = 0.3
    # non-absorbed, per head, over every slot row <= pos
    c = pool[slots]
    k = jnp.concatenate([jnp.einsum("bsr,rhn->bshn", c[..., :r], w_uk),
                         jnp.broadcast_to(c[:, :, None, r:],
                                          (4, S, H, rope))], -1)
    val = jnp.einsum("bsr,rhv->bshv", c[..., :r], w_uv)
    q = jnp.concatenate([q_nope, q_pe], -1)
    s = jnp.einsum("bhd,bshd->bhs", q, k,
                   precision=jax.lax.Precision.HIGHEST) * scale
    s = jnp.where(jnp.arange(S)[None, None] <= pos[:, None, None], s,
                  -jnp.inf)
    want = jnp.einsum("bhs,bshv->bhv", jax.nn.softmax(s, -1), val,
                      precision=jax.lax.Precision.HIGHEST)
    q_abs = jnp.concatenate(
        [jnp.einsum("bhn,rhn->bhr", q_nope, w_uk,
                    precision=jax.lax.Precision.HIGHEST), q_pe], -1)
    for fn in (lambda: fused_latent_decode_attention(
                   q_abs, pool, slots, pos, kv_len=S, scale=scale, v_dim=r,
                   blk=64, interpret=True),
               lambda: ref_latent_decode_attention(
                   q_abs, pool, slots, pos, S, scale, r)):
        o = fn()
        got = jnp.einsum("bhr,rhv->bhv", o, w_uv,
                         precision=jax.lax.Precision.HIGHEST)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)
    # reading only the first 192 rows changes nothing for positions < 192
    short = fused_latent_decode_attention(
        q_abs[:2], pool, slots[:2], pos[:2], kv_len=192, scale=scale,
        v_dim=r, blk=64, interpret=True)
    full = fused_latent_decode_attention(
        q_abs[:2], pool, slots[:2], pos[:2], kv_len=S, scale=scale,
        v_dim=r, blk=64, interpret=True)
    np.testing.assert_allclose(np.asarray(short), np.asarray(full),
                               rtol=1e-6, atol=1e-6)


def test_latent_kernel_is_one_named_call_in_the_wave():
    """A decode wave of the MLA model calls the latent kernel under its
    own name, once in the dense first layer and once in the scanned body
    of the MoE layers, and the dense kernel never."""
    params = tf.init_params(jax.random.PRNGKey(0), CFG)
    caches = tf.init_cache(CFG, 5, max_seq=32)
    spec = registry.KernelSpec(backend="pallas", interpret=True)
    text = str(jax.make_jaxpr(lambda c: tf.decode_wave(
        params, CFG, c, jnp.zeros((4, 1), jnp.int32),
        jnp.arange(4, dtype=jnp.int32), jnp.asarray([3, 5, 7, 9]),
        kv_len=16, attn_spec=spec))(caches))
    assert text.count("decode_attn_latent") == 2
    assert "name=decode_attn " not in text


# ---------------------------------------------------------------------------
# (c) dropless MoE
# ---------------------------------------------------------------------------

def _experts(seed, N=40, d=16, f=32, E=8):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    return (jax.random.normal(ks[0], (N, d), jnp.float32),
            jax.random.normal(ks[1], (d, E), jnp.float32),
            jax.random.normal(ks[2], (E, d, f), jnp.float32) * 0.1,
            jax.random.normal(ks[3], (E, d, f), jnp.float32) * 0.1,
            jax.random.normal(ks[4], (E, f, d), jnp.float32) * 0.1)


def _all_experts(x, rw, wg, wu, wd, k, norm, scale):
    """Every expert on every token, weighted by its gate (zero where not
    chosen): the benchmark reference's form."""
    p = jax.nn.softmax(jnp.matmul(x, rw, precision="highest"), -1)
    top, idx = jax.lax.top_k(p, k)
    top = top / top.sum(-1, keepdims=True) if norm else top * scale
    gate = jnp.sum(jax.nn.one_hot(idx, rw.shape[1]) * top[..., None], -2)
    y = jnp.einsum("nef,efd->ned", jax.nn.silu(
        jnp.einsum("nd,edf->nef", x, wg, precision="highest"))
        * jnp.einsum("nd,edf->nef", x, wu, precision="highest"), wd,
        precision="highest")
    return jnp.einsum("ne,ned->nd", gate, y)


@pytest.mark.parametrize("k,norm,scale", [(2, True, 1.0), (3, False, 1.0),
                                          (2, False, 2.5)])
def test_dropless_moe_matches_all_experts(k, norm, scale):
    x, rw, wg, wu, wd = _experts(k)
    want = _all_experts(x, rw, wg, wu, wd, k, norm, scale)
    with jax.default_matmul_precision("highest"):
        got, stats = moe_lib.moe_dropless(x, rw, wg, wu, wd, k, norm=norm,
                                          scale=scale)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    sizes = np.bincount(np.asarray(jax.lax.top_k(x @ rw, k)[1]).ravel(),
                        minlength=8)
    assert stats.tolist() == [40 * k, int((sizes > 0).sum()),
                              int(sizes.max())]


def test_gmm_path_matches_cpu_path(monkeypatch):
    """The accelerator's grouped matmul (megablox ``gmm``, steered here
    and interpreted) gives what ``lax.ragged_dot`` gives, padding rows
    included."""
    import functools
    x, rw, wg, wu, wd = _experts(5, N=21)
    x, wg, wu, wd = (a.astype(jnp.bfloat16) for a in (x, wg, wu, wd))
    cpu, s1 = moe_lib.moe_dropless(x, rw, wg, wu, wd, 3, norm=False)
    monkeypatch.setattr(registry, "on_cpu", lambda: False)
    monkeypatch.setattr(moe_lib, "gmm",
                        functools.partial(moe_lib.gmm, interpret=True))
    tpu, s2 = moe_lib.moe_dropless(x, rw, wg, wu, wd, 3, norm=False)
    np.testing.assert_allclose(np.asarray(tpu), np.asarray(cpu), rtol=1e-5,
                               atol=1e-6)
    assert s1.tolist() == s2.tolist()


def test_a_token_is_served_alike_alone_and_in_a_wave():
    """No capacity, no drop: each token of a wave of 32 gets exactly what
    it gets alone."""
    x, rw, wg, wu, wd = _experts(9, N=32)
    wave, _ = moe_lib.moe_dropless(x, rw, wg, wu, wd, 2, norm=False)
    for i in (0, 13, 31):
        alone, _ = moe_lib.moe_dropless(x[i:i + 1], rw, wg, wu, wd, 2,
                                        norm=False)
        np.testing.assert_allclose(np.asarray(alone[0]),
                                   np.asarray(wave[i]), rtol=1e-6,
                                   atol=1e-7)


# ---------------------------------------------------------------------------
# (d) bucketed prefill
# ---------------------------------------------------------------------------

def test_padded_bucket_prefill_equals_exact_length():
    """A prompt of 11 tokens runs at bucket 16: its logits, its step-0
    query and its latent rows below 11 equal the exact-length prefill's
    (dropless experts over a linear latent cache read no pad). In
    float32, so that the two shapes agree to float32 rounding: in bf16
    they differ by an ulp where XLA orders a sum differently."""
    cfg = dataclasses.replace(CFG, dtype="float32")
    params = tf.init_params(jax.random.PRNGKey(7), cfg)
    assert pads_unread(cfg) and prefill_bucket(cfg, 11, 32) == 16
    prompt = jnp.asarray(np.random.default_rng(1).integers(
        1, cfg.vocab_size, (2, 11)), jnp.int32)
    eng = RalmEngine.monolithic(params, cfg, RagConfig(mode="none"),
                                max_seq=32, wave=False)
    (caches, _, logits, hidden), _ = eng._prefill(
        RalmRequest(prompt=prompt, steps=1), 32, None)
    assert eng.prefill_stats.programs == {(16, 2, 32)}
    exact = tf.init_cache(cfg, 2, max_seq=32)
    r_logits, exact, r_hidden = tf.prefill(params, cfg, exact, prompt, 11)
    close = dict(rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(r_logits),
                               **close)
    np.testing.assert_allclose(np.asarray(hidden), np.asarray(r_hidden),
                               **close)
    for cls in cfg.pattern_classes():
        got = caches["classes"][cls]["latent"][:, :, :11]
        want = exact["classes"][cls]["latent"][:, :, :11]
        assert got.shape[-1] == cfg.latent_width
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   **close)


# ---------------------------------------------------------------------------
# (e) YaRN by hand; counts of the published model
# ---------------------------------------------------------------------------

def test_yarn_by_hand():
    mscale = 0.1 * 0.707 * math.log(40) + 1
    assert LITE.mla_softmax_scale() == pytest.approx(192 ** -0.5
                                                     * mscale ** 2)
    assert LITE.mla_softmax_scale() == pytest.approx(0.114721, abs=1e-6)
    assert yarn_correction_range(64, 1e4, 4096, 32, 1) == (10, 23)
    inv = np.asarray(yarn_inv_freq(LITE, 64))
    base = 1e4 ** (-np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(inv[:11], base[:11], rtol=1e-6)  # kept
    np.testing.assert_allclose(inv[23:], base[23:] / 40, rtol=1e-6)
    ramp = (16 - 10) / (23 - 10)
    assert inv[16] == pytest.approx(base[16] * (1 - ramp)
                                    + base[16] / 40 * ramp, rel=1e-6)


def test_published_parameter_counts():
    """15.7B parameters, 2.66B active per token (2.45B without the input
    embedding, which is looked up, not multiplied), and 1152 bytes of
    latent cache a position a layer."""
    d, fe, V = 2048, 1408, 102400
    attn = d * 16 * 192 + d * 576 + 512 + 512 * 16 * 256 + 16 * 128 * d
    moe = d * 64 + 64 * 3 * d * fe + 2 * 3 * d * fe
    total = 2 * V * d + 27 * attn + 3 * d * 10944 + 26 * moe
    assert LITE.param_count() == total
    assert round(total / 1e9, 1) == 15.7
    active = total - 26 * 58 * 3 * d * fe
    assert LITE.active_param_count() == active
    assert round((active - V * d) / 1e9, 2) == 2.45
    assert LITE.latent_width * 2 == 1152


# ---------------------------------------------------------------------------
# routing counters; pool growth
# ---------------------------------------------------------------------------

def test_routing_counters_reach_metricsz(weights):
    from repro.obs import MetricsRegistry, bind_engine_metrics
    _, params = weights
    eng = RalmEngine.monolithic(params, CFG, RagConfig(mode="none"),
                                max_seq=32, kv_slots=4)
    assert eng.moe_stats() == dict(assignments=0, experts_touched=0,
                                   max_load=0)
    rng = np.random.default_rng(2)
    for _ in range(3):
        eng.submit(RalmRequest(prompt=jnp.asarray(
            rng.integers(1, 256, (1, 9)), jnp.int32), steps=5))
    eng.run()
    st = eng.moe_stats()
    waves = eng.pool.stats.waves
    # every wave routes each of its (one or more) bucket rows to top_k
    # experts in each of the 2 MoE layers
    assert st["assignments"] >= waves * 2 * 2
    assert 0 < st["experts_touched"] <= waves * 2 * 8
    assert 1 <= st["max_load"] <= 4 * 2
    reg = MetricsRegistry()
    bind_engine_metrics(reg, eng)
    text = reg.render()
    assert f"ralm_moe_assignments_total {st['assignments']}" in text
    assert "ralm_moe_experts_touched_total" in text
    assert "ralm_moe_max_expert_load" in text


def test_a_dense_model_does_no_routing_work(monkeypatch):
    """A dense model's waves pass no routing totals, so its decode-wave
    program computes no counter, and no counter exists."""
    from repro.configs import get_arch
    from repro.serve import engine as engine_mod
    cfg = dataclasses.replace(get_arch("dec_s").reduced, vocab_size=64)
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    totals, stats = [], []
    wave = engine_mod._jit_decode_wave
    decode_wave = tf.decode_wave

    def spy_wave(*a, **k):
        totals.append(a[7])
        return wave(*a, **k)

    def spy_decode(*a, **k):
        stats.append(k.get("return_stats", False))
        return decode_wave(*a, **k)

    monkeypatch.setattr(engine_mod, "_jit_decode_wave", spy_wave)
    monkeypatch.setattr(tf, "decode_wave", spy_decode)
    jax.clear_caches()
    eng = RalmEngine.monolithic(params, cfg, RagConfig(mode="none"),
                                max_seq=32, kv_slots=2)
    eng.generate(jnp.ones((1, 5), jnp.int32), 4)
    assert totals and all(t is None for t in totals)
    assert stats and not any(stats)
    assert eng.moe_stats() is None


def test_latent_pool_grows_and_keeps_rows():
    from repro.serve.kvpool import KVCachePool
    pool = KVCachePool(CFG, 2, 16, seq_block=8)
    lat = pool.caches["classes"]["global"]["latent"]
    assert lat.shape == (2, 3, 16, CFG.latent_width)
    rows = jax.tree.map(lambda a: jnp.ones_like(a[:, :1]), pool.caches)
    pool.write_prefill(np.asarray([1], np.int32), rows)
    pool.grow_seq(24)
    pool.grow_slots(4)
    lat = pool.caches["classes"]["global"]["latent"]
    assert lat.shape == (2, 5, 24, CFG.latent_width)
    assert float(lat[:, 1, :16].min()) == 1.0
    assert float(jnp.abs(lat[:, 1, 16:]).max()) == 0.0


def test_parity_script_at_cpu_widths(monkeypatch, capsys):
    """``mla_parity.py`` end to end on the tiny block (its chip-only
    guards steered here): the served rows agree with the float32
    reference far closer than the fp8 control does."""
    import json

    sys.path.insert(0, str(BENCH.parent))
    sys.path.insert(0, str(BENCH))
    import mla_parity
    import run

    import repro.launch.cache
    lm = run.lm_module(BENCH.parent, "deepseek_v2")
    cfg = {"model": dict(TINY, lm="deepseek_v2", init_scale=0.02),
           "engine": {"kv_slots": 4, "max_seq": 64, "attn_seq_block": 16},
           "name": "tiny-dsv2"}

    class Chip:
        platform, device_kind = "tpu", "cpu"
    monkeypatch.setattr(run, "load_cell", lambda root, name: (
        None, None, cfg, None, lm))
    monkeypatch.setattr(mla_parity, "PROMPTS", (5, 9, 20, 30))
    monkeypatch.setattr(jax, "devices", lambda *a: [Chip()])
    monkeypatch.setattr(repro.launch.cache, "setup_compile_cache",
                        lambda: None)
    monkeypatch.setattr(mla_parity, "TOLERANCE", 0.03)
    assert mla_parity.main(["--steps", "6"]) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["positions"] == 4 * 7 and out["agree"] is True
    for part in ("logits", "query"):
        assert out["program"][part] < 0.03
        assert out["control"][part] > 3 * out["program"][part]
    assert out["moe"]["assignments"] > 0
