"""The benchmark's plain reference of the kNN-LM's architecture-independent
part, written out in ``jax.numpy``, independent of the program under test.

It imports nothing of the program, and nothing of ``bench/lm/``. It holds:

* ``seed_key``, and ``matmul`` at the three precisions the LM modules
  (``bench/lm/<name>.py``) compute at: float32 at ``highest`` for the
  reference, scaled fp8 for the lower-precision control, plain bf16 for
  making datastore samples;
* the kNN search over the benchmark's own IVF-PQ tables: exact probe,
  asymmetric distance (ADC) by table lookup, exact top-K;
* the kNN-LM mixture ``log((1 - lam) softmax(lm) + lam p_knn)``.

The LM itself, its weights and its forward pass, is the configuration's
LM module (see ``bench/lm/dense.py`` for what one provides).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

F32 = jnp.float32
BF16 = jnp.bfloat16
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0

#: precisions of an LM module's ``forward_hidden`` and of the search
REFERENCE = "f32"       # float32 at highest: the reference
CONTROL = "fp8"         # scaled float8 e4m3 matmuls: the control
SAMPLE = "bf16"         # bf16 at the default precision: datastore samples


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size (a benchmark's seeds may exceed
    32 bits, which ``PRNGKey`` alone would truncate)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


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
