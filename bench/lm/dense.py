"""The plain reference of the paper's dense decoder-only LM, written out in
``jax.numpy``, independent of the program under test: pre-norm RMSNorm,
rotary positions, softmax attention (GQA), SwiGLU, tied embeddings. It
imports nothing of the program; the matmuls at the three precisions come
from ``reference.py``.

An LM module is what a configuration names with ``"lm": "<name>"`` in
its ``model`` block (``dense`` where the key is absent); the harness
loads ``bench/lm/<name>.py`` by its path and reaches the LM only through
it. Every LM module provides:

``Model.from_config(model_block)``
    the sizes the module needs, from the configuration's ``model`` block,
    with at least ``d_model`` and ``vocab_size``; it refuses a block that
    asks for an architecture the module does not write out;
``make_weights(key, m)``
    seeded bf16 weights, made on the device in one jitted call, in the
    parameter layout the program reads for the ``ModelConfig`` that the
    same block gives;
``forward_hidden(params, tokens, m, prec)``
    last-layer hidden states [B, T, d] before the final norm (the kNN-LM
    query) of a causal pass over ``tokens`` [B, T], at ``REFERENCE``,
    ``CONTROL`` or ``SAMPLE`` precision, needing one layer's float32 copy
    at a time;
``lm_logits(params, hidden, m, prec)``
    logits [N, V] of hidden states [N, d];
``lm_token(model_block, kv_len)`` and ``prefill(model_block, tokens)``
    the operations the algorithm needs for one decode step and for a
    prompt's prefill (``step_mfu_pct``), counting only the active experts
    where there are experts.
"""
from __future__ import annotations

import functools
from typing import Dict, NamedTuple

import jax
import jax.numpy as jnp

from reference import BF16, F32, matmul

#: ``ModelConfig`` keys whose value this pass assumes: a model block that
#: names this module and sets one of them otherwise is refused
SERVES = {"block": "dense", "layer_pattern": ["global"], "qkv_bias": False,
          "rope_mode": "rope", "act": "silu", "arch": "decoder",
          "n_experts": 0, "top_k": 0, "ssm_state": 0, "frontend": None}


class Model(NamedTuple):
    """The model sizes the reference needs (from the configuration file)."""
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_head: int
    d_ff: int
    vocab_size: int
    rope_theta: float = 1e4
    norm_eps: float = 1e-5
    init_scale: float = 0.02

    @classmethod
    def from_config(cls, model: Dict) -> "Model":
        bad = sorted(k for k, v in SERVES.items()
                     if k in model and model[k] != v)
        if model.get("tie_embeddings") is not True:
            bad.append("tie_embeddings (the dense LM ties its embeddings)")
        if bad:
            raise ValueError("bench/lm/dense.py does not serve this model "
                             f"block; it sets otherwise: {', '.join(bad)}")
        return cls(**{f: model[f] for f in cls._fields if f in model})


@functools.partial(jax.jit, static_argnums=1)
def make_weights(key: jax.Array, m: Model) -> Dict:
    """Seeded bf16 weights, N(0, init_scale) matrices and unit norms, in
    one jitted call on the device."""
    L, d, f = m.n_layers, m.d_model, m.d_ff
    H, KV, dh = m.n_heads, m.n_kv_heads, m.d_head
    ks = jax.random.split(key, 8)

    def normal(k, shape):
        return (jax.random.normal(k, shape, F32) * m.init_scale).astype(BF16)

    layer = dict(
        ln1=jnp.ones((L, d), BF16), ln2=jnp.ones((L, d), BF16),
        wq=normal(ks[1], (L, d, H * dh)), wk=normal(ks[2], (L, d, KV * dh)),
        wv=normal(ks[3], (L, d, KV * dh)), wo=normal(ks[4], (L, H * dh, d)),
        wg=normal(ks[5], (L, d, f)), wu=normal(ks[6], (L, d, f)),
        wd=normal(ks[7], (L, f, d)))
    return {"embed": normal(ks[0], (m.vocab_size, d)),
            "final_norm": jnp.ones((d,), BF16),
            "classes": {"global": layer}}


def _rms_norm(x, scale, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(F32)


def _rope(x, positions, theta):
    """Rotate-half rotary positions. x [B, T, H, D], positions [T]."""
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(0, 2 * half, 2, dtype=F32) / (2 * half))
    ang = positions.astype(F32)[:, None] * inv                  # [T, half]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@functools.partial(jax.jit, static_argnums=(2, 3))
def forward_hidden(params: Dict, tokens: jnp.ndarray, m: Model,
                   prec: str) -> jnp.ndarray:
    """Last-layer hidden states [B, T, d] (before the final norm; the
    kNN-LM query) of a causal pass over ``tokens`` [B, T]. Layers run one
    at a time under ``lax.scan``, each layer's weights widened there, so
    the pass needs one layer's float32 copy at a time."""
    B, T = tokens.shape
    H, KV, dh = m.n_heads, m.n_kv_heads, m.d_head
    pos = jnp.arange(T)
    causal = pos[:, None] >= pos[None, :]
    h = params["embed"][tokens].astype(F32)

    def layer(h, p):
        hn = _rms_norm(h, p["ln1"], m.norm_eps)
        q = matmul(hn, p["wq"], prec).reshape(B, T, H, dh)
        k = matmul(hn, p["wk"], prec).reshape(B, T, KV, dh)
        v = matmul(hn, p["wv"], prec).reshape(B, T, KV, dh)
        q, k = _rope(q, pos, m.rope_theta), _rope(k, pos, m.rope_theta)
        k = jnp.repeat(k, H // KV, axis=2)
        v = jnp.repeat(v, H // KV, axis=2)
        s = jnp.einsum("bthd,bshd->bhts", q, k,
                       precision=jax.lax.Precision.HIGHEST) * dh ** -0.5
        s = jnp.where(causal[None, None], s, -jnp.inf)
        a = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, -1), v,
                       precision=jax.lax.Precision.HIGHEST)
        h = h + matmul(a.reshape(B, T, H * dh), p["wo"], prec)
        hn = _rms_norm(h, p["ln2"], m.norm_eps)
        g = jax.nn.silu(matmul(hn, p["wg"], prec)) * matmul(hn, p["wu"], prec)
        return h + matmul(g, p["wd"], prec), None

    h, _ = jax.lax.scan(layer, h, params["classes"]["global"])
    return h


@functools.partial(jax.jit, static_argnums=(2, 3))
def lm_logits(params: Dict, hidden: jnp.ndarray, m: Model, prec: str
              ) -> jnp.ndarray:
    """Tied-embedding logits [N, V] of hidden states [N, d]."""
    hn = _rms_norm(hidden, params["final_norm"], m.norm_eps)
    return matmul(hn, params["embed"].T, prec)


# ---------------------------------------------------------------------------
# operations the algorithm needs (``step_mfu_pct``): no one-hot expansion,
# no padded rows or blocks; a multiply-add is two operations
# ---------------------------------------------------------------------------

def _block_params(model: Dict) -> int:
    d, f = model["d_model"], model["d_ff"]
    H, KV, dh = model["n_heads"], model["n_kv_heads"], model["d_head"]
    return d * H * dh + 2 * d * KV * dh + H * dh * d + 3 * d * f


def lm_token(model: Dict, kv_len: int) -> float:
    """Operations of one decode step at ``kv_len`` cached positions: the
    weight matmuls, attention, and the tied-embedding logits."""
    L = model["n_layers"]
    return (2 * L * _block_params(model)
            + 2 * model["d_model"] * model["vocab_size"]
            + L * 4 * kv_len * model["n_heads"] * model["d_head"])


def prefill(model: Dict, tokens: int) -> float:
    """Operations of a causal prefill of ``tokens`` positions, with the
    logits of the last position only (the one the first token needs)."""
    L = model["n_layers"]
    attn = (L * 4 * model["n_heads"] * model["d_head"]
            * tokens * (tokens + 1) / 2)
    return (2 * L * _block_params(model) * tokens + attn
            + 2 * model["d_model"] * model["vocab_size"])
