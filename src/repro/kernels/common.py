"""Shared in-kernel utilities for the ChamVS Pallas kernels."""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

LANES = 128


def round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def query_tile(nq: int, want: int = 8) -> Tuple[int, int]:
    """(tile, padded nq) for a kernel whose query-row block sits on the
    sublane axis: Mosaic needs that block to be a multiple of 8 rows or
    the whole axis, so a batch that is not a multiple of ``want`` rows
    is padded up to one (a batch smaller than ``want`` is one tile)."""
    if nq <= want:
        return nq, nq
    return want, round_up(nq, want)


def _first_index(d: jnp.ndarray, m: jnp.ndarray, col: jnp.ndarray
                 ) -> jnp.ndarray:
    """[rows, 1] column of the first entry of each row equal to ``m``."""
    big = jnp.int32(d.shape[1])
    return jnp.min(jnp.where(d == m, col, big), axis=1, keepdims=True)


def merge_topk_rows(queue_d: jnp.ndarray, queue_i: jnp.ndarray,
                    tile_d: jnp.ndarray, tile_i: jnp.ndarray, k: int
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Row-batched k-smallest of (running queue ++ new tile), ascending.

    queue_*: [rows, kq] (kq >= k), tile_*: [rows, cand] ->
    ([rows, kq], [rows, kq]): the first ``k`` columns hold the result,
    the rest stay (+inf, -1). Ties resolve to the queue first, then to
    the lower column — the order an argmin over the concatenation would
    give.

    TPU replacement for the FPGA systolic priority queue (DESIGN.md §3):
    k rounds of (row-min, first-index, select). Every step is an
    all-lane VPU reduction or a select — no gather and no concatenation,
    which Mosaic cannot lower at unaligned widths. k is static and small
    (the truncated queue length k' from the paper's binomial bound), so
    the loop is cheap next to the producing scan."""
    rows, kq = queue_d.shape
    col_a = jax.lax.broadcasted_iota(jnp.int32, queue_d.shape, 1)
    col_b = jax.lax.broadcasted_iota(jnp.int32, tile_d.shape, 1)

    def body(j, carry):
        d_a, d_b, out_d, out_i = carry
        m_a = jnp.min(d_a, axis=1, keepdims=True)                # [rows, 1]
        m_b = jnp.min(d_b, axis=1, keepdims=True)
        take_a = m_a <= m_b
        p_a = _first_index(d_a, m_a, col_a)
        p_b = _first_index(d_b, m_b, col_b)
        hit_a = col_a == p_a
        hit_b = col_b == p_b
        imax = jnp.int32(jnp.iinfo(jnp.int32).max)
        id_a = jnp.min(jnp.where(hit_a, queue_i, imax), axis=1, keepdims=True)
        id_b = jnp.min(jnp.where(hit_b, tile_i, imax), axis=1, keepdims=True)
        slot = col_a == j
        out_d = jnp.where(slot, jnp.where(take_a, m_a, m_b), out_d)
        out_i = jnp.where(slot, jnp.where(take_a, id_a, id_b), out_i)
        d_a = jnp.where(hit_a & take_a, jnp.inf, d_a)
        d_b = jnp.where(hit_b & ~take_a, jnp.inf, d_b)
        return d_a, d_b, out_d, out_i

    out_d = jnp.full((rows, kq), jnp.inf, queue_d.dtype)
    out_i = jnp.full((rows, kq), -1, jnp.int32)
    _, _, out_d, out_i = jax.lax.fori_loop(
        0, k, body, (queue_d, tile_d, out_d, out_i))
    return out_d, jnp.where(jnp.isinf(out_d), -1, out_i)
