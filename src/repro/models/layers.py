"""Primitive layers: norms, activations, RoPE (standard + M-RoPE), MLP."""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp


def rms_norm(x: jnp.ndarray, scale: jnp.ndarray, eps: float = 1e-5
             ) -> jnp.ndarray:
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)).astype(x.dtype) * scale


def swiglu(x: jnp.ndarray, w_gate: jnp.ndarray, w_up: jnp.ndarray,
           w_down: jnp.ndarray, act: str = "silu") -> jnp.ndarray:
    """Gated MLP: (act(x @ w_gate) * (x @ w_up)) @ w_down."""
    g = x @ w_gate
    g = jax.nn.silu(g) if act == "silu" else jax.nn.gelu(g)
    return (g * (x @ w_up)) @ w_down


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------

def rope_freqs(d_head: int, theta: float) -> jnp.ndarray:
    """[d_head//2] inverse frequencies."""
    return 1.0 / (theta ** (jnp.arange(0, d_head, 2, dtype=jnp.float32)
                            / d_head))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float
               ) -> jnp.ndarray:
    """x: [B, T, H, Dh]; positions: [B, T] -> rotated x (rotate-half form)."""
    dh = x.shape[-1]
    inv = rope_freqs(dh, theta)                               # [dh/2]
    ang = positions[..., None].astype(jnp.float32) * inv      # [B, T, dh/2]
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x: jnp.ndarray, positions: jnp.ndarray, theta: float,
                sections: Tuple[int, ...]) -> jnp.ndarray:
    """Multimodal RoPE (Qwen2-VL §3.1): the head-dim frequency bands are
    split into (temporal, height, width) sections, each rotated by its own
    position stream. positions: [3, B, T]; for pure text all three streams
    are equal and M-RoPE degenerates to 1-D RoPE.

    x: [B, T, H, Dh]."""
    dh = x.shape[-1]
    half = dh // 2
    assert sum(sections) == half, (sections, half)
    inv = rope_freqs(dh, theta)                               # [half]
    # build a per-frequency position stream by section
    sec_id = jnp.repeat(jnp.arange(len(sections)),
                        jnp.array(sections), total_repeat_length=half)  # [half]
    pos = positions.astype(jnp.float32)                        # [3, B, T]
    pos_per_freq = pos[sec_id]                                 # [half, B, T]
    ang = jnp.moveaxis(pos_per_freq, 0, -1) * inv              # [B, T, half]
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def positional_rotate(x: jnp.ndarray, positions: jnp.ndarray, cfg
                      ) -> jnp.ndarray:
    """Dispatch on cfg.rope_mode. positions is [B, T] (rope) or [3, B, T]
    (mrope; a [B, T] input is broadcast to all three streams)."""
    if cfg.rope_mode == "none":
        return x
    if cfg.rope_mode == "mrope":
        if positions.ndim == 2:
            positions = jnp.broadcast_to(positions[None],
                                         (3,) + positions.shape)
        return apply_mrope(x, positions, cfg.rope_theta, cfg.mrope_sections)
    return apply_rope(x, positions, cfg.rope_theta)


# ---------------------------------------------------------------------------
# YaRN-scaled rotary positions over interleaved channel pairs (DeepSeek-V2)
# ---------------------------------------------------------------------------

def yarn_correction_range(dim: int, theta: float, original_max: int,
                          beta_fast: float, beta_slow: float
                          ) -> Tuple[int, int]:
    """The frequency indices (of ``dim // 2``) between which YaRN ramps
    from the original to the interpolated frequencies: the dimensions
    that turn ``beta_fast`` and ``beta_slow`` times over
    ``original_max`` positions."""
    def at(turns):
        return (dim * math.log(original_max / (turns * 2 * math.pi))
                / (2 * math.log(theta)))
    return (max(math.floor(at(beta_fast)), 0),
            min(math.ceil(at(beta_slow)), dim - 1))


def yarn_inv_freq(cfg, dim: int) -> jnp.ndarray:
    """[dim // 2] rotary inverse frequencies: plain where
    ``cfg.yarn_factor <= 1``, else YaRN's blend of the original and the
    ``yarn_factor``-interpolated ones, by a linear ramp over the
    correction range."""
    extra = rope_freqs(dim, cfg.rope_theta)
    if cfg.yarn_factor <= 1.0:
        return extra
    lo, hi = yarn_correction_range(dim, cfg.rope_theta,
                                   cfg.yarn_original_max_position,
                                   cfg.yarn_beta_fast, cfg.yarn_beta_slow)
    hi = hi + 0.001 if hi == lo else hi
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - lo)
                    / (hi - lo), 0.0, 1.0)
    keep = 1.0 - ramp               # 1: keep the original frequency
    return extra / cfg.yarn_factor * (1.0 - keep) + extra * keep


def apply_rope_pairs(x: jnp.ndarray, positions: jnp.ndarray,
                     inv_freq: jnp.ndarray, scale: float = 1.0
                     ) -> jnp.ndarray:
    """Rotary positions over the channel pairs ``(2i, 2i + 1)`` of
    x [B, T, H, D], positions [B, T]; the output lays the pairs out as
    halves ``[evens | odds]`` (DeepSeek-V2's ``apply_rotary_pos_emb``),
    with cos and sin scaled by ``scale``."""
    ang = positions[..., None].astype(jnp.float32) * inv_freq    # [B,T,D/2]
    cos = (jnp.cos(ang) * scale)[:, :, None, :]
    sin = (jnp.sin(ang) * scale)[:, :, None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., 0::2], xf[..., 1::2]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)
