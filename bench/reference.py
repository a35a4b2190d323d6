"""The benchmark's plain reference: a decoder-only kNN-LM written out in
``jax.numpy``, independent of the program under test.

It imports nothing of the program. It holds:

* ``make_weights``: the seeded random weights the cell serves (bf16), in
  the parameter layout the program's dense decoder reads;
* ``forward_hidden``: the causal forward pass (pre-norm RMSNorm, rotary
  positions, softmax attention, SwiGLU, tied embeddings) at a chosen
  precision: float32 at ``highest`` for the reference, scaled fp8 for the
  lower-precision control, plain bf16 for making datastore samples;
* the kNN search over the benchmark's own IVF-PQ tables: exact probe,
  asymmetric distance (ADC) by table lookup, exact top-K;
* the kNN-LM mixture ``log((1 - lam) softmax(lm) + lam p_knn)``.
"""
from __future__ import annotations

import functools
from typing import Dict, NamedTuple

import jax
import jax.numpy as jnp

F32 = jnp.float32
BF16 = jnp.bfloat16
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0

#: precisions of ``forward_hidden`` and of the search
REFERENCE = "f32"       # float32 at highest: the reference
CONTROL = "fp8"         # scaled float8 e4m3 matmuls: the control
SAMPLE = "bf16"         # bf16 at the default precision: datastore samples


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
        return cls(**{f: model[f] for f in cls._fields if f in model})


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size (a benchmark's seeds may exceed
    32 bits, which ``PRNGKey`` alone would truncate)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


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


# ---------------------------------------------------------------------------
# matmuls at the three precisions
# ---------------------------------------------------------------------------

def _fp8(x: jnp.ndarray, axis: int) -> jnp.ndarray:
    """Round ``x`` to float8 e4m3 with one scale per slice along ``axis``
    (the largest magnitude maps to the format's largest value), and
    return the rounded values in float32."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    return (x / scale).astype(FP8).astype(F32) * scale


def matmul(x: jnp.ndarray, w: jnp.ndarray, prec: str) -> jnp.ndarray:
    """``x [..., k] @ w [k, n]`` at one of the three precisions."""
    if prec == REFERENCE:
        return jnp.matmul(x.astype(F32), w.astype(F32),
                          precision=jax.lax.Precision.HIGHEST)
    if prec == CONTROL:
        # activations scaled per row, weights per output column
        return jnp.matmul(_fp8(x.astype(F32), -1), _fp8(w.astype(F32), 0),
                          precision=jax.lax.Precision.HIGHEST)
    return jnp.matmul(x.astype(BF16), w.astype(BF16),
                      preferred_element_type=F32)


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
# kNN search over the benchmark's IVF-PQ tables
# ---------------------------------------------------------------------------

class Tables(NamedTuple):
    """The datastore the benchmark made (see ``datastore.py``). Vector ids
    are list-major: list ``l`` holds ids ``offsets[l] .. offsets[l] +
    lens[l] - 1`` in its first ``lens[l]`` slots."""
    centroids: jnp.ndarray     # [nlist, d] f32
    codebooks: jnp.ndarray     # [m, ksub, dsub] f32
    codes: jnp.ndarray         # [nlist, cap, m] uint8 (0 past lens)
    ids: jnp.ndarray           # [nlist, cap] int32 (-1 past lens)
    lens: jnp.ndarray          # [nlist] int32
    offsets: jnp.ndarray       # [nlist] int32, first id of each list
    payload: jnp.ndarray       # [N] int32 next token of each vector


def _dot(a, b, prec):
    if prec == REFERENCE:
        return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)
    return jnp.matmul(a.astype(BF16), b.astype(BF16),
                      preferred_element_type=BF16).astype(F32)


def luts(t: Tables, q: jnp.ndarray, prec: str) -> jnp.ndarray:
    """Squared distance of each query sub-vector to each codeword:
    [R, m, ksub]. ``prec`` other than the reference rounds every input
    and product of the table to bf16 (the control)."""
    m, ksub, dsub = t.codebooks.shape
    qs = q.reshape(q.shape[0], m, dsub)
    cb = t.codebooks
    if prec != REFERENCE:
        qs, cb = qs.astype(BF16), cb.astype(BF16)
        diff = (qs[:, :, None, :] - cb[None]).astype(BF16)
        return jnp.sum(diff * diff, -1, dtype=BF16).astype(F32)
    diff = qs[:, :, None, :] - cb[None]
    return jnp.sum(diff * diff, -1)


def _adc(lut: jnp.ndarray, codes: jnp.ndarray, prec: str) -> jnp.ndarray:
    """lut [R, m, ksub], codes [R, n, m] -> distances [R, n]."""
    got = jnp.take_along_axis(lut[:, None], codes[..., None].astype(jnp.int32),
                              axis=-1)[..., 0]                  # [R, n, m]
    if prec != REFERENCE:
        return jnp.sum(got.astype(BF16), -1, dtype=BF16).astype(F32)
    return jnp.sum(got, -1)


@functools.partial(jax.jit, static_argnums=(3, 4))
def search_lists(t: Tables, q: jnp.ndarray, lists: jnp.ndarray, k: int,
                 prec: str):
    """ADC over the valid codes of the given lists [R, L] (-1: none) of
    queries [R, d], the ``k`` smallest: (dists [R, k] ascending, ids)."""
    lut = luts(t, q, prec)
    R, L = lists.shape
    cap = t.codes.shape[1]
    safe = jnp.maximum(lists, 0)
    codes = t.codes[safe].reshape(R, L * cap, -1)
    ids = jnp.where((lists >= 0)[..., None], t.ids[safe], -1
                    ).reshape(R, L * cap)
    d = jnp.where(ids >= 0, _adc(lut, codes, prec), jnp.inf)
    neg, pos = jax.lax.top_k(-d, k)
    return -neg, jnp.take_along_axis(ids, pos, -1)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def search(t: Tables, q: jnp.ndarray, nprobe: int, k: int, prec: str):
    """IVF-PQ search of queries [R, d]: the ``nprobe`` nearest lists by
    exact squared L2, ADC over their valid codes, the ``k`` smallest.
    Returns (dists [R, k] ascending, ids [R, k], probes [R, nprobe])."""
    c = t.centroids
    cd = (jnp.sum(q * q, -1, keepdims=True) - 2.0 * _dot(q, c.T, prec)
          + jnp.sum(c * c, -1)[None])
    _, probes = jax.lax.top_k(-cd, nprobe)                       # [R, np]
    d, ids = search_lists(t, q, probes, k, prec)
    return d, ids, probes


def locate(t: Tables, ids: jnp.ndarray):
    """(list, slot) of vector ids (list-major numbering)."""
    lst = jnp.searchsorted(t.offsets, ids, side="right") - 1
    lst = jnp.clip(lst, 0, t.offsets.shape[0] - 1)
    return lst, ids - t.offsets[lst]


@functools.partial(jax.jit, static_argnums=3)
def distances_of(t: Tables, q: jnp.ndarray, ids: jnp.ndarray, prec: str
                 ) -> jnp.ndarray:
    """ADC distances [R, K] of queries [R, d] to vectors ``ids`` [R, K];
    +inf for an id that names no vector."""
    n = t.payload.shape[0]
    safe = jnp.clip(ids, 0, n - 1)
    lst, slot = locate(t, safe)
    nlist, cap, m = t.codes.shape
    codes = t.codes.reshape(nlist * cap, m)[lst * cap + slot]    # [R, K, m]
    d = _adc(luts(t, q, prec), codes, prec)
    return jnp.where((ids >= 0) & (ids < n), d, jnp.inf)


@functools.partial(jax.jit, static_argnums=(4, 5))
def mixture(lm: jnp.ndarray, dists: jnp.ndarray, ids: jnp.ndarray,
            payload: jnp.ndarray, lam: float, temperature: float
            ) -> jnp.ndarray:
    """kNN-LM log-probabilities [R, V]: ``(1 - lam) softmax(lm) + lam
    p_knn`` with ``p_knn(w)`` proportional to the sum of ``exp(-d / T)``
    over the neighbours whose next token is ``w``."""
    R, V = lm.shape
    ok = jnp.isfinite(dists) & (ids >= 0)
    logw = jnp.where(ok, -dists / temperature, -jnp.inf)
    top = jnp.max(logw, -1, keepdims=True)
    w = jnp.where(ok, jnp.exp(logw - jnp.where(jnp.isfinite(top), top, 0.0)),
                  0.0)
    total = jnp.sum(w, -1, keepdims=True)
    w = w / jnp.maximum(total, 1e-30)
    tok = payload[jnp.clip(ids, 0, payload.shape[0] - 1)]
    p_knn = jnp.zeros((R, V), F32).at[jnp.arange(R)[:, None], tok].add(w)
    lam_r = jnp.where(total[:, 0] > 0, lam, 0.0)[:, None]
    p = (1.0 - lam_r) * jax.nn.softmax(lm.astype(F32), -1) + lam_r * p_knn
    return jnp.log(jnp.maximum(p, 1e-30))
