"""Grouped-einsum oracle for the fused decode-attention kernel.

Same contract as ``kernel.fused_decode_attention`` — and the fix for
the naive decode path itself: scores contract directly over the
KV-head axis (``[B, KV, G, T, S]``), so the GQA-expanded ``[B, S, H,
D]`` K/V copies the legacy path materialized every step never exist.
This is a pure-memory win even with Pallas off, which is why it is the
default ``backend="ref"`` serving flavor on CPU hosts (the Pallas
kernel runs there in interpret mode as a parity harness only).
``_repeat_kv`` stays in ``models/attention.py`` for prefill/flash,
where the repeated layout is load-bearing for the blocked scan.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def decode_validity(position: jnp.ndarray, S: int, window: int,
                    ring: bool) -> jnp.ndarray:
    """[B, S] slot validity from per-row absolute positions — the single
    definition the ref oracle and the legacy einsum path share.

    Linear cache: slot ``i`` holds position ``i``, valid iff
    ``i <= pos``. Ring cache of size S: slot ``i`` holds
    ``pos - ((pos - i) mod S)``, valid iff that is ``>= 0``. A sliding
    ``window`` additionally rejects positions ``<= pos - window``."""
    B = position.shape[0]
    slot = jnp.arange(S)
    if ring:
        p_slot = position[:, None] - ((position[:, None] - slot[None]) % S)
        valid = p_slot >= 0
    else:
        p_slot = jnp.broadcast_to(slot[None], (B, S))
        valid = p_slot <= position[:, None]
    if window > 0:
        valid &= p_slot > position[:, None] - window
    return valid


def ref_decode_attention(q: jnp.ndarray, k_cache: jnp.ndarray,
                         v_cache: jnp.ndarray, position: jnp.ndarray,
                         window: int = 0, ring: bool = False,
                         constrain_scores=None) -> jnp.ndarray:
    """q [B, T, H, D] (T=1 in decode), caches [B, S, KV, D], position [B]
    -> [B, T, H, D]. Grouped over the KV-head axis; no head repeat.

    ``constrain_scores`` (optional) is applied to the [B, KV, G, T, S]
    score tensor — the caller's sharding-hint hook (this package stays
    free of ``repro.models.ctx``, so the TP softmax-stays-distributed
    annotation is injected from ``models/attention.py``)."""
    B, S, KV, D = k_cache.shape
    T, H = q.shape[1], q.shape[2]
    G = H // KV
    qg = q.reshape(B, T, KV, G, D)
    s = jnp.einsum("btkgd,bskd->bkgts", qg, k_cache
                   ).astype(jnp.float32) * D ** -0.5
    if constrain_scores is not None:
        s = constrain_scores(s)
    valid = decode_validity(position, S, window, ring)
    s = jnp.where(valid[:, None, None, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgts,bskd->btkgd", w.astype(v_cache.dtype), v_cache)
    return out.reshape(B, T, H, D)


def ref_latent_decode_attention(q: jnp.ndarray, cache: jnp.ndarray,
                                slots: jnp.ndarray, position: jnp.ndarray,
                                kv_len: int, scale: float, v_dim: int
                                ) -> jnp.ndarray:
    """Latent-mode oracle: q [B, H, W] against rows ``0..position`` of
    pool slots ``slots`` of cache [P, S, W] (linear; the first ``kv_len``
    rows read) -> [B, H, v_dim] float32, the values being each row's
    first ``v_dim`` lanes."""
    c = cache[:, :kv_len][slots]
    s = jnp.einsum("bhw,bsw->bhs", q, c,
                   preferred_element_type=jnp.float32) * scale
    valid = decode_validity(position, kv_len, 0, False)
    w = jax.nn.softmax(jnp.where(valid[:, None], s, NEG_INF), axis=-1)
    return jnp.einsum("bhs,bsv->bhv", w, c[..., :v_dim].astype(jnp.float32))
