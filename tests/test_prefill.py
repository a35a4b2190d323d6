"""The admission prefill runs one compiled program per prompt-length bucket.

  * A dense, global-attention model pads the prompt at its tail to a
    power-of-two bucket; the step-0 logits, the step-0 retrieval query
    and every cache row below the prompt length match an eager prefill
    at the exact length.
  * Ring (sliding-window), RWKV, hybrid and MoE models are never padded
    (their pads would be read), and match the same reference.
  * Admitting three lengths of one bucket builds one program: the first
    admission compiles it, the other two compile nothing.

The ``ralm_prefill_*`` counters on /statsz and /metricsz, and the
``bucket`` arg of the ``prefill`` span, are checked in tests/test_obs.py.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.models import transformer as tf
from repro.serve import RagConfig, RalmEngine, RalmRequest
from repro.serve.engine import pads_unread, prefill_bucket

MAX_SEQ = 64


def eager_prefill(params, cfg, prompt, max_seq, enc_len=0):
    """The prefill as it ran before buckets: eager, at the exact prompt
    length, every position unembedded and the last one kept."""
    B, T0 = prompt.shape
    caches = tf.init_cache(cfg, B, max_seq=max_seq)
    enc_states = None
    if enc_len:
        neutral = jnp.zeros((B, enc_len), jnp.int32)
        enc_states = tf.encode(params, cfg, tf.embed_tokens(params, neutral))
    pos = jnp.broadcast_to(jnp.arange(T0)[None], (B, T0))
    if cfg.rope_mode == "mrope":
        pos = jnp.broadcast_to(pos[None], (3, B, T0))
    logits, caches, hidden = tf.forward(
        params, cfg, tokens=prompt, positions=pos, mode="prefill",
        caches=caches, enc_states=enc_states, return_hidden=True)
    return caches, enc_states, logits[:, -1], hidden[:, -1]


def _f32(x):
    return np.asarray(x, np.float32)


def _close(x, ref, **kw):
    """Equal to bf16 rounding (one ulp at the logits' magnitude)."""
    np.testing.assert_allclose(_f32(x), _f32(ref), rtol=1e-2, atol=4e-3,
                               **kw)


def _check_parity(arch, T0, bucket):
    cfg = get_arch(arch).reduced
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(T0), (2, T0), 1,
                                cfg.vocab_size)
    rag = RagConfig(mode="none")
    eng = RalmEngine.monolithic(params, cfg, rag, max_seq=MAX_SEQ,
                                wave=False)
    (caches, enc, logits, hidden), cur = eng._prefill(
        RalmRequest(prompt=prompt, steps=1), MAX_SEQ, None)
    enc_len = 8 if cfg.arch == "encdec" else 0
    r_caches, r_enc, r_logits, r_hidden = eager_prefill(
        params, cfg, prompt, MAX_SEQ, enc_len)

    assert prefill_bucket(cfg, T0, MAX_SEQ) == bucket
    st = eng.prefill_stats
    assert (st.calls, st.prompt_tokens, st.pad_tokens) == \
        (1, 2 * T0, 2 * (bucket - T0))
    assert st.programs == {(bucket, 2, MAX_SEQ)}
    assert (np.asarray(cur) == np.asarray(prompt[:, -1:])).all()
    _close(hidden, r_hidden)
    _close(logits, r_logits)
    if enc is not None:
        _close(enc, r_enc)
    for cls, c in caches["classes"].items():
        for key, leaf in c.items():
            ref = r_caches["classes"][cls][key]
            assert leaf.shape == ref.shape, (cls, key)
            if key in ("k", "v") and leaf.shape[2] == MAX_SEQ:
                leaf, ref = leaf[:, :, :T0], ref[:, :, :T0]
            _close(leaf, ref, err_msg=f"{cls}.{key}")


@pytest.mark.parametrize("arch,T0,bucket", [
    ("dec_s", 17, 32), ("dec_s", 24, 32), ("dec_s", 32, 32),  # one bucket
    ("dec_s", 5, 16),                        # below the smallest bucket
    ("encdec_s", 20, 32),                    # neutral encoder
    ("qwen2_0_5b", 40, 64),                  # qkv bias
])
def test_bucketed_prefill_matches_exact(arch, T0, bucket):
    """A dense, global model pads to its bucket; logits0, hidden0 and
    the cache rows below T0 match the eager exact-length prefill."""
    assert pads_unread(get_arch(arch).reduced)
    _check_parity(arch, T0, bucket)


@pytest.mark.parametrize("arch", ["gemma3_4b", "rwkv6_3b", "hymba_1_5b",
                                  "dbrx_132b"])
def test_unpaddable_prefill_runs_at_exact_length(arch):
    """Ring caches, recurrent state and MoE capacity would read the pads:
    these configurations prefill at the exact length, with the same
    results as the eager reference."""
    assert not pads_unread(get_arch(arch).reduced)
    _check_parity(arch, 20, 20)


def test_bucket_sizes():
    cfg = get_arch("dec_s").reduced
    assert [prefill_bucket(cfg, t, 1024) for t in
            (1, 16, 17, 100, 128, 129, 512, 513, 1024)] == \
        [16, 16, 32, 128, 128, 256, 512, 1024, 1024]
    # the bucket never passes the cache length, nor falls under the prompt
    assert prefill_bucket(cfg, 40, 48) == 48
    assert prefill_bucket(cfg, 60, 48) == 60
    ring = get_arch("gemma3_4b").reduced
    assert prefill_bucket(ring, 17, 1024) == 17


# ---------------------------------------------------------------------------
# one program per bucket, counted by JAX's own compile events
# ---------------------------------------------------------------------------

_COMPILES = []


def _on_compile(event, duration, **kw):
    if event == "/jax/core/compile/backend_compile_duration":
        _COMPILES.append(kw.get("fun_name", "?"))


jax.monitoring.register_event_duration_secs_listener(_on_compile)


def test_one_compile_per_bucket():
    """Admissions of 17, 24 and 30 tokens share bucket 32: the first
    builds its program, the other two build nothing."""
    # a configuration of its own: no other test has built its programs
    cfg = dataclasses.replace(get_arch("dec_s").reduced,
                              name="dec_s-prefill-buckets")
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    corpus = np.random.default_rng(0).integers(
        0, cfg.vocab_size, size=(1, 32)).astype(np.int32)
    eng = RalmEngine.monolithic(params, cfg, RagConfig(mode="none"),
                                max_seq=MAX_SEQ, kv_slots=4)
    # the pool, its scatter and bucket 16 are built by a first admission
    eng.release(eng.start(RalmRequest(prompt=jnp.asarray(corpus[:1, :8]),
                                      steps=2)))
    counts = []
    for T0 in (17, 24, 30):
        del _COMPILES[:]
        seq = eng.start(RalmRequest(prompt=jnp.asarray(corpus[:1, :T0]),
                                    steps=2))
        jax.block_until_ready(seq.hidden0)
        counts.append(len(_COMPILES))
        eng.release(seq)
    assert counts == [1, 0, 0], counts
    st = eng.prefill_stats
    assert st.calls == 4
    assert st.programs == {(16, 1, MAX_SEQ), (32, 1, MAX_SEQ)}
    assert st.prompt_tokens == 8 + 17 + 24 + 30
    assert st.pad_tokens == (16 - 8) + (32 - 17) + (32 - 24) + (32 - 30)
