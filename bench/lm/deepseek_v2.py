"""The plain reference of DeepSeek-V2's decoder (arXiv 2405.04434, and the
``modeling_deepseek.py`` that ships with the DeepSeek-V2-Lite checkpoint),
written out in ``jax.numpy``, independent of the program under test. It
imports nothing of the program; the matmuls at the three precisions come
from ``reference.py``. An LM module (see ``bench/lm/dense.py`` for what
one provides), named by ``"lm": "deepseek_v2"``.

A layer, pre-norm RMSNorm before each half:

* **multi-head latent attention** (§2.1), in the published non-absorbed
  form: ``q = h W_q`` split per head into ``q_nope`` and ``q_pe``;
  ``[c_kv | k_pe] = h W_kv_a``; ``k_nope, v = rms_norm(c_kv) W_kv_b``
  per head; ``q_pe`` and the one shared ``k_pe`` take rotary positions
  over the channel pairs ``(2i, 2i + 1)``, laid out as halves, at
  YaRN-scaled frequencies; scores ``[q_nope | q_pe] . [k_nope | k_pe]``
  times ``(nope + rope)^-1/2 mscale^2``, causal softmax, values ``v``,
  output ``W_o``. The attention is computed in blocks of query
  positions;
* the first ``first_k_dense`` layers end in a dense SwiGLU of ``d_ff``;
  the rest are **DeepSeekMoE** layers (§2.2): a float32 softmax router
  over ``n_experts``, greedy top-``top_k``, the weights used as they are
  times ``routed_scaling_factor`` (``norm_topk_prob`` false) or
  renormalised (true), SwiGLU experts of ``d_expert``, and
  ``n_shared_experts`` shared experts as one SwiGLU of ``n * d_expert``
  over every token. The routed experts are a ``lax.scan`` over all of
  them: each expert is applied to every token and weighted by its gate,
  zero where the token did not choose it. Plain, dropless and
  memory-bounded (one expert's float32 copy at a time);
* untied embeddings: a final RMSNorm and ``lm_head``.

The weights' layout is the program's: ``classes`` ``lead`` (the dense
layers) and ``global`` (the MoE layers), each stacked over its layers.

``lm_token`` counts the absorbed decode (the scores against the cached
latent rows: each head's ``q_nope`` times its slice of W_UK, the
weighted latent times W_UV) and the active experts only; ``prefill``
counts the expanded form. ``latent_attn_bytes`` and ``expert_flops`` /
``expert_bytes`` serve the latent-attention roofline and the expert
matmuls' share.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, NamedTuple

import jax
import jax.numpy as jnp

from reference import BF16, F32, matmul

#: ``ModelConfig`` keys whose value this pass assumes: a model block that
#: names this module and sets one of them otherwise is refused
SERVES = {"block": "moe", "layer_pattern": ["global"], "qkv_bias": False,
          "rope_mode": "rope", "act": "silu", "arch": "decoder",
          "ssm_state": 0, "frontend": None, "window": 0,
          "tie_embeddings": False}

#: query positions per block of the attention
Q_BLOCK = 256


class Model(NamedTuple):
    """The model sizes the reference needs (from the configuration file)."""
    n_layers: int
    d_model: int
    n_heads: int
    d_ff: int
    vocab_size: int
    n_experts: int
    top_k: int
    d_expert: int
    n_shared_experts: int
    first_k_dense: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    norm_topk_prob: bool = False
    routed_scaling_factor: float = 1.0
    rope_theta: float = 1e4
    yarn_factor: float = 0.0
    yarn_original_max_position: int = 0
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_mscale: float = 1.0
    yarn_mscale_all_dim: float = 0.0
    norm_eps: float = 1e-6
    init_scale: float = 0.02

    @classmethod
    def from_config(cls, model: Dict) -> "Model":
        bad = sorted(k for k, v in SERVES.items()
                     if k in model and model[k] != v)
        if model.get("n_kv_heads", model.get("n_heads")) != model.get(
                "n_heads"):
            bad.append("n_kv_heads (latent attention has n_heads heads)")
        if "d_head" in model:
            bad.append("d_head (latent attention sizes its heads by "
                       "qk_nope_head_dim, qk_rope_head_dim, v_head_dim)")
        missing = [f for f in cls._fields if f not in cls._field_defaults
                   and f not in model]
        if not model.get("kv_lora_rank"):
            bad.append("kv_lora_rank (0: no latent attention)")
        if model.get("block", "dense") != "moe":
            bad.append("block (this module writes out DeepSeekMoE layers)")
        if bad or missing:
            raise ValueError(
                "bench/lm/deepseek_v2.py does not serve this model block; "
                f"it sets otherwise: {', '.join(sorted(set(bad)))}"
                + (f"; it lacks: {', '.join(missing)}" if missing else ""))
        return cls(**{f: model[f] for f in cls._fields if f in model})


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=1)
def make_weights(key: jax.Array, m: Model) -> Dict:
    """Seeded bf16 weights, N(0, init_scale) matrices and unit norms, in
    one jitted call on the device. The routed experts are drawn one
    layer at a time."""
    d, H, r = m.d_model, m.n_heads, m.kv_lora_rank
    nope, rope, vd = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim
    E, fe = m.n_experts, m.d_expert
    fs = m.n_shared_experts * fe
    ks = iter(jax.random.split(key, 32))

    def normal(k, shape):
        return (jax.random.normal(k, shape, F32) * m.init_scale).astype(BF16)

    def attention(n):
        return dict(
            ln1=jnp.ones((n, d), BF16), ln2=jnp.ones((n, d), BF16),
            wq=normal(next(ks), (n, d, H * (nope + rope))),
            wkv_a=normal(next(ks), (n, d, r + rope)),
            kv_norm=jnp.ones((n, r), BF16),
            wkv_b=normal(next(ks), (n, r, H * (nope + vd))),
            wo=normal(next(ks), (n, H * vd, d)))

    def experts(k, shape):
        return jax.lax.map(lambda kk: normal(kk, shape),
                           jax.random.split(k, n_moe))

    n_lead, n_moe = m.first_k_dense, m.n_layers - m.first_k_dense
    lead = dict(attention(n_lead), wg=normal(next(ks), (n_lead, d, m.d_ff)),
                wu=normal(next(ks), (n_lead, d, m.d_ff)),
                wd=normal(next(ks), (n_lead, m.d_ff, d)))
    moe = dict(attention(n_moe), router=normal(next(ks), (n_moe, d, E)),
               wg=experts(next(ks), (E, d, fe)),
               wu=experts(next(ks), (E, d, fe)),
               wd=experts(next(ks), (E, fe, d)))
    if fs:
        moe.update(ws_g=normal(next(ks), (n_moe, d, fs)),
                   ws_u=normal(next(ks), (n_moe, d, fs)),
                   ws_d=normal(next(ks), (n_moe, fs, d)))
    return {"embed": normal(next(ks), (m.vocab_size, d)),
            "final_norm": jnp.ones((d,), BF16),
            "lm_head": normal(next(ks), (d, m.vocab_size)),
            "classes": {"lead": lead, "global": moe}}


# ---------------------------------------------------------------------------
# forward pass
# ---------------------------------------------------------------------------

def _rms_norm(x, scale, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(F32)


def _mscale(m: Model, mscale: float) -> float:
    if m.yarn_factor <= 1.0:
        return 1.0
    return 0.1 * mscale * math.log(m.yarn_factor) + 1.0


def inv_freq(m: Model) -> jnp.ndarray:
    """[rope / 2] YaRN inverse frequencies (``DeepseekV2YarnRotary
    Embedding``): the original ``theta^(-2i/dim)`` below the correction
    range, divided by ``yarn_factor`` above it, a linear ramp between."""
    dim, base = m.qk_rope_head_dim, m.rope_theta
    extra = 1.0 / base ** (jnp.arange(0, dim, 2, dtype=F32) / dim)
    if m.yarn_factor <= 1.0:
        return extra
    lo, hi = correction_range(m)
    if lo == hi:
        hi += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=F32) - lo) / (hi - lo),
                    0.0, 1.0)
    mask = 1.0 - ramp
    return (extra / m.yarn_factor) * (1.0 - mask) + extra * mask


def correction_range(m: Model):
    """YaRN's (low, high) frequency indices: where ``beta_fast`` and
    ``beta_slow`` rotations fit the original context."""
    dim, base = m.qk_rope_head_dim, m.rope_theta

    def at(rotations):
        return (dim * math.log(m.yarn_original_max_position
                               / (rotations * 2 * math.pi))
                / (2 * math.log(base)))
    return (max(math.floor(at(m.yarn_beta_fast)), 0),
            min(math.ceil(at(m.yarn_beta_slow)), dim - 1))


def softmax_scale(m: Model) -> float:
    """``(nope + rope)^-1/2`` times ``mscale(factor, mscale_all_dim)^2``."""
    s = _mscale(m, m.yarn_mscale_all_dim)
    return (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5 * s * s


def _rope(x, positions, m: Model):
    """x [B, T, H, rope]: pairs (2i, 2i + 1) gathered into halves, then
    rotated by half, cos and sin times ``mscale / mscale_all_dim``."""
    ang = positions.astype(F32)[:, None] * inv_freq(m)[None]     # [T, r/2]
    emb = jnp.concatenate([ang, ang], -1)
    scale = _mscale(m, m.yarn_mscale) / _mscale(m, m.yarn_mscale_all_dim)
    cos = (jnp.cos(emb) * scale)[None, :, None]
    sin = (jnp.sin(emb) * scale)[None, :, None]
    B, T, H, D = x.shape
    x = jnp.swapaxes(x.reshape(B, T, H, D // 2, 2), -1, -2).reshape(
        B, T, H, D)
    half = D // 2
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rotated * sin


def _attention(q, k, v, scale):
    """Causal softmax attention of q, k [B, T, H, Dq] and v [B, T, H, Dv]
    in blocks of ``Q_BLOCK`` query positions, float32 at highest."""
    B, T, H, _ = q.shape
    qb = min(Q_BLOCK, T)
    pad = -T % qb
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    blocks = jnp.moveaxis(qp.reshape(B, -1, qb, H, q.shape[-1]), 1, 0)
    kpos = jnp.arange(T)

    def block(args):
        i, qblk = args
        qpos = i * qb + jnp.arange(qb)
        s = jnp.einsum("bthd,bshd->bhts", qblk, k,
                       precision=jax.lax.Precision.HIGHEST) * scale
        s = jnp.where((kpos[None, :] <= qpos[:, None])[None, None], s,
                      -jnp.inf)
        return jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, -1), v,
                          precision=jax.lax.Precision.HIGHEST)

    out = jax.lax.map(block, (jnp.arange(blocks.shape[0]), blocks))
    return jnp.moveaxis(out, 0, 1).reshape(B, -1, H, v.shape[-1])[:, :T]


def _mla(h, p, m: Model, prec):
    B, T, _ = h.shape
    H, r = m.n_heads, m.kv_lora_rank
    nope, rope, vd = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim
    pos = jnp.arange(T)
    hn = _rms_norm(h, p["ln1"], m.norm_eps)
    q = matmul(hn, p["wq"], prec).reshape(B, T, H, nope + rope)
    kv_a = matmul(hn, p["wkv_a"], prec)
    c_kv = _rms_norm(kv_a[..., :r], p["kv_norm"], m.norm_eps)
    k_pe = _rope(kv_a[..., None, r:], pos, m)                # [B, T, 1, rope]
    kv = matmul(c_kv, p["wkv_b"], prec).reshape(B, T, H, nope + vd)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], pos, m)], -1)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_pe, (B, T, H, rope))], -1)
    a = _attention(q, k, kv[..., nope:], softmax_scale(m))
    return h + matmul(a.reshape(B, T, H * vd), p["wo"], prec)


def _swiglu(x, wg, wu, wd, prec):
    return matmul(jax.nn.silu(matmul(x, wg, prec)) * matmul(x, wu, prec),
                  wd, prec)


def gates(hn, router, m: Model, prec):
    """[..., E] routing weights: the chosen experts' softmax
    probabilities (renormalised where ``norm_topk_prob``, else times
    ``routed_scaling_factor``), zero for the others."""
    probs = jax.nn.softmax(matmul(hn, router, prec), -1)
    top, idx = jax.lax.top_k(probs, m.top_k)
    if m.norm_topk_prob:
        top = top / jnp.sum(top, -1, keepdims=True)
    else:
        top = top * m.routed_scaling_factor
    return jnp.sum(jax.nn.one_hot(idx, m.n_experts, dtype=F32)
                   * top[..., None], -2)


def _moe(hn, p, m: Model, prec):
    g = gates(hn, p["router"], m, prec)

    def expert(y, e):
        wg, wu, wd, i = e
        return y + g[..., i, None] * _swiglu(hn, wg, wu, wd, prec), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(hn),
                        (p["wg"], p["wu"], p["wd"], jnp.arange(m.n_experts)))
    if "ws_g" in p:
        y = y + _swiglu(hn, p["ws_g"], p["ws_u"], p["ws_d"], prec)
    return y


@functools.partial(jax.jit, static_argnums=(2, 3))
def forward_hidden(params: Dict, tokens: jnp.ndarray, m: Model,
                   prec: str) -> jnp.ndarray:
    """Last-layer hidden states [B, T, d] (before the final norm; the
    kNN-LM query) of a causal pass over ``tokens`` [B, T]. Layers run one
    at a time under ``lax.scan``, the routed experts one at a time inside
    each, so the pass needs one expert's float32 copy at a time."""
    h = params["embed"][tokens].astype(F32)

    def dense_layer(h, p):
        h = _mla(h, p, m, prec)
        hn = _rms_norm(h, p["ln2"], m.norm_eps)
        return h + _swiglu(hn, p["wg"], p["wu"], p["wd"], prec), None

    def moe_layer(h, p):
        h = _mla(h, p, m, prec)
        return h + _moe(_rms_norm(h, p["ln2"], m.norm_eps), p, m, prec), None

    h, _ = jax.lax.scan(dense_layer, h, params["classes"]["lead"])
    h, _ = jax.lax.scan(moe_layer, h, params["classes"]["global"])
    return h


@functools.partial(jax.jit, static_argnums=(2, 3))
def lm_logits(params: Dict, hidden: jnp.ndarray, m: Model, prec: str
              ) -> jnp.ndarray:
    """Logits [N, V] of hidden states [N, d] through the untied head."""
    hn = _rms_norm(hidden, params["final_norm"], m.norm_eps)
    return matmul(hn, params["lm_head"], prec)


# ---------------------------------------------------------------------------
# operations and bytes the algorithm needs: no padded rows or blocks, no
# expert a token did not choose; a multiply-add is two operations
# ---------------------------------------------------------------------------

def _sizes(model: Dict):
    d, H = model["d_model"], model["n_heads"]
    r, nope = model["kv_lora_rank"], model["qk_nope_head_dim"]
    return d, H, r, nope, model["qk_rope_head_dim"], model["v_head_dim"]


def _projections(model: Dict) -> int:
    """Attention weights a token multiplies outside the scores: q, kv_a,
    o (kv_b is counted where it is used)."""
    d, H, r, nope, rope, vd = _sizes(model)
    return d * H * (nope + rope) + d * (r + rope) + H * vd * d


def _mlp_params(model: Dict, moe: bool) -> int:
    """MLP weights one token multiplies: the dense MLP, or the router,
    its ``top_k`` experts and the shared experts."""
    d = model["d_model"]
    if not moe:
        return 3 * d * model["d_ff"]
    fe = model["d_expert"]
    return (d * model["n_experts"] + model["top_k"] * 3 * d * fe
            + model["n_shared_experts"] * 3 * d * fe)


def _layers(model: Dict):
    lead = model["first_k_dense"]
    return lead, model["n_layers"] - lead


def lm_token(model: Dict, kv_len: int) -> float:
    """Operations of one absorbed decode step at ``kv_len`` cached
    positions: projections, ``q_nope`` W_UK and the weighted latent
    W_UV, scores over the latent rows (``r + rope`` wide) and the
    weighted sum of latents (``r`` wide), the active MLP, the logits."""
    d, H, r, nope, rope, vd = _sizes(model)
    lead, moe = _layers(model)
    attn = 2 * (_projections(model) + H * nope * r + H * r * vd) \
        + 2 * H * kv_len * (r + rope) + 2 * H * kv_len * r
    return (2 * lead * _mlp_params(model, False)
            + 2 * moe * _mlp_params(model, True)
            + (lead + moe) * attn + 2 * d * model["vocab_size"])


def prefill(model: Dict, tokens: int) -> float:
    """Operations of a causal prefill of ``tokens`` positions in the
    expanded form (W_kv_b applied to every latent), with the logits of
    the last position only."""
    d, H, r, nope, rope, vd = _sizes(model)
    lead, moe = _layers(model)
    pairs = tokens * (tokens + 1) / 2
    per_layer = (2 * tokens * (_projections(model) + r * H * (nope + vd))
                 + 2 * H * pairs * (nope + rope) + 2 * H * pairs * vd)
    return ((lead + moe) * per_layer
            + 2 * tokens * (lead * _mlp_params(model, False)
                            + moe * _mlp_params(model, True))
            + 2 * d * model["vocab_size"])


def latent_attn_bytes(model: Dict, kv_len: int) -> float:
    """Bytes one decode step's latent attention must read over every
    layer: ``kv_len`` cached rows of ``r + rope`` bf16 values a layer."""
    _, _, r, _, rope, _ = _sizes(model)
    return model["n_layers"] * kv_len * (r + rope) * 2


def expert_flops(model: Dict, assignments: int) -> float:
    """Operations of the routed experts' three matmuls over
    ``assignments`` (token, expert) pairs."""
    return assignments * 2 * 3 * model["d_model"] * model["d_expert"]


def expert_bytes(model: Dict, experts_touched: int) -> float:
    """Bytes of routed-expert weights (bf16) the expert matmuls must read
    when ``experts_touched`` experts (summed over layers) get a token."""
    return experts_touched * 3 * model["d_model"] * model["d_expert"] * 2
