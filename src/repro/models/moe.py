"""Mixture-of-Experts FFN (dbrx: 16e top-4; phi3.5-moe: 16e top-2;
DeepSeek-V2-Lite: 64e top-6 with 2 shared experts).

Serving (prefill and decode) is dropless, ``moe_dropless``: every
(token, expert) assignment is computed, so a token's output never depends
on what else is in its batch, and a prompt padded at its tail routes its
pads without touching a real token. The assignments are sorted by expert
and the three expert matmuls run as grouped matmuls over the experts'
group sizes: the TPU's megablox ``gmm`` Pallas kernel on an accelerator
(its device operations are named ``gmm``), ``lax.ragged_dot`` on a CPU
host. The matmuls read only the experts that received tokens, from the
layer's expert weights that the caller's layer scan slices out of the
stack (on a TPU that slice is a copy of all of the layer's experts).

Training keeps ``moe_ffn``, sort-based capacity dispatch (MegaBlocks-lite,
static shapes):
  router top-k -> flatten (token, expert) assignments -> stable-sort by
  expert -> slot-in-expert via segment arithmetic -> scatter into a dense
  [E, C, d] buffer -> batched expert GEMMs -> weighted scatter-add combine.

This keeps compute proportional to top_k (not E) and avoids the GShard
[N, E, C] one-hot dispatch tensor, which does not fit at train_4k scale.
Tokens beyond expert capacity are dropped (standard GShard semantics); the
residual path keeps their representation intact.

``route_topk`` takes float32 logits and a softmax over all experts; the
chosen experts' probabilities are renormalised over the chosen ones where
``norm`` (Mixtral-style, the default) and used as they are, times the
config's ``routed_scaling_factor``, where not (DeepSeek's
``norm_topk_prob`` false).

Sharding (DESIGN.md §7): experts over the "data" axis (EP), expert-internal
f over "model" (TP); the dispatch scatter becomes an all-to-all under GSPMD.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox import gmm

from repro.kernels import registry


def route_topk(x: jnp.ndarray, router_w: jnp.ndarray, top_k: int,
               norm: bool = True) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x [N, d], router_w [d, E] -> (weights [N, k], expert_ids [N, k]):
    the top-k of the softmax over every expert's float32 logit, the
    weights renormalised over the chosen experts where ``norm``."""
    logits = jnp.matmul(x.astype(jnp.float32), router_w.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    w, top_ids = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
    if norm:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return w, top_ids


def load_balance_loss(x: jnp.ndarray, router_w: jnp.ndarray, top_k: int
                      ) -> jnp.ndarray:
    """Switch-style auxiliary loss: E * sum_e f_e * p_e."""
    logits = x.astype(jnp.float32) @ router_w.astype(jnp.float32)
    E = logits.shape[-1]
    p = jax.nn.softmax(logits, axis=-1)
    _, top_ids = jax.lax.top_k(logits, top_k)
    f = jnp.zeros((E,), jnp.float32).at[top_ids.reshape(-1)].add(1.0)
    f = f / jnp.maximum(f.sum(), 1.0)
    return E * jnp.sum(f * p.mean(axis=0))


def moe_ffn(
    x: jnp.ndarray,            # [N, d] flattened tokens
    router_w: jnp.ndarray,     # [d, E]
    w_gate: jnp.ndarray,       # [E, d, f]
    w_up: jnp.ndarray,         # [E, d, f]
    w_down: jnp.ndarray,       # [E, f, d]
    top_k: int,
    capacity_factor: float = 1.25,
    act: str = "silu",
) -> jnp.ndarray:
    """Top-k expert FFN with capacity dropping. Returns [N, d]."""
    N, d = x.shape
    E = router_w.shape[-1]
    C = int(max(1, -(-int(N * top_k * capacity_factor) // E)))
    C = -(-C // 8) * 8  # pad capacity to a lane-friendly multiple

    gate_w, expert_ids = route_topk(x, router_w, top_k)      # [N,k] each
    flat_e = expert_ids.reshape(-1)                          # [N*k]
    flat_t = jnp.repeat(jnp.arange(N), top_k)                # [N*k] token idx
    flat_w = gate_w.reshape(-1)                              # [N*k]

    # stable sort by expert -> contiguous expert segments
    order = jnp.argsort(flat_e, stable=True)
    se, st, sw = flat_e[order], flat_t[order], flat_w[order]
    # slot within expert = rank - segment start
    counts = jnp.zeros((E,), jnp.int32).at[se].add(1)
    seg_start = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                 jnp.cumsum(counts)[:-1]])
    slot = jnp.arange(N * top_k, dtype=jnp.int32) - seg_start[se]
    keep = slot < C

    # dispatch: scatter tokens into the [E, C, d] expert buffer
    e_idx = jnp.where(keep, se, 0)
    s_idx = jnp.where(keep, slot, 0)
    xin = jnp.where(keep[:, None], x[st], 0.0)
    buf = jnp.zeros((E, C, d), x.dtype).at[e_idx, s_idx].add(xin)

    # batched expert GEMMs (gated MLP)
    g = jnp.einsum("ecd,edf->ecf", buf, w_gate)
    g = jax.nn.silu(g) if act == "silu" else jax.nn.gelu(g)
    u = jnp.einsum("ecd,edf->ecf", buf, w_up)
    y_e = jnp.einsum("ecf,efd->ecd", g * u, w_down)          # [E, C, d]

    # combine: gather each assignment's expert output, weight, scatter by token
    y_tok = y_e[e_idx, s_idx]                                # [N*k, d]
    y_tok = jnp.where(keep[:, None], y_tok, 0.0) * sw[:, None].astype(y_e.dtype)
    return jnp.zeros((N, d), y_e.dtype).at[st].add(y_tok)


# ---------------------------------------------------------------------------
# dropless serving path
# ---------------------------------------------------------------------------

#: rows of one m-tile of the grouped matmul, at most
GMM_MAX_TM = 256


def _gmm_tiles(m: int) -> Tuple[int, int]:
    """(padded rows, m-tile): one tile of up to ``GMM_MAX_TM`` rows (a
    decode wave's assignments), so each touched expert's weights stream
    once; tiles of ``GMM_MAX_TM`` beyond."""
    if m <= GMM_MAX_TM:
        tm = max(16, -(-m // 16) * 16)
        return tm, tm
    return -(-m // GMM_MAX_TM) * GMM_MAX_TM, GMM_MAX_TM


def grouped_matmul(x: jnp.ndarray, w: jnp.ndarray, sizes: jnp.ndarray,
                   tiling: Tuple[int, int, int]) -> jnp.ndarray:
    """``x[rows of group e] @ w[e]`` for every group e, float32 out. x [m,
    k] holds the groups' rows in order (``sizes`` [E] of them, the rest
    unread and their output rows undefined), w [E, k, n]. The megablox
    ``gmm`` kernel on an accelerator, ``lax.ragged_dot`` on a CPU host."""
    if registry.on_cpu():
        return jax.lax.ragged_dot(x, w, sizes,
                                  preferred_element_type=jnp.float32)
    return gmm(x, w, sizes, preferred_element_type=jnp.float32,
               tiling=tiling)


def moe_dropless(x: jnp.ndarray, router_w: jnp.ndarray, w_gate: jnp.ndarray,
                 w_up: jnp.ndarray, w_down: jnp.ndarray, top_k: int,
                 norm: bool = True, scale: float = 1.0,
                 act: str = "silu") -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Routed experts of tokens x [N, d], every assignment computed, with
    expert weights w_gate, w_up [E, d, f] and w_down [E, f, d]. Returns
    (out [N, d] float32, stats int32 [3]): the assignments, the experts
    that received any, and the largest expert's load."""
    N, d = x.shape
    E, _, f = w_gate.shape
    w, ids = route_topk(x, router_w, top_k, norm)
    if not norm:
        w = w * scale
    flat_e = ids.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    tok = jnp.repeat(jnp.arange(N), top_k)[order]
    sizes = jnp.zeros((E,), jnp.int32).at[flat_e].add(1)
    M = N * top_k
    Mp, tm = _gmm_tiles(M)
    xs = jnp.pad(x[tok], ((0, Mp - M), (0, 0)))
    g = grouped_matmul(xs, w_gate, sizes, (tm, min(d, 512), f))
    u = grouped_matmul(xs, w_up, sizes, (tm, min(d, 512), f))
    g = jax.nn.silu(g) if act == "silu" else jax.nn.gelu(g)
    y = grouped_matmul((g * u).astype(x.dtype), w_down, sizes,
                       (tm, f, min(d, 512)))[:M]
    y = y * w.reshape(-1)[order][:, None]
    out = jnp.zeros((N, d), jnp.float32).at[tok].add(y)
    stats = jnp.stack([jnp.int32(M), jnp.sum(sizes > 0, dtype=jnp.int32),
                       jnp.max(sizes)])
    return out, stats


def add_stats(a, b):
    """Routing counters of two layers (or waves) together: summed
    assignments and experts touched, the larger largest load; ``None``
    (no MoE layer) adds nothing."""
    if a is None or b is None:
        return b if a is None else a
    return jnp.stack([a[0] + b[0], a[1] + b[1], jnp.maximum(a[2], b[2])])
