"""Pallas kernel: approximate hierarchical top-k (paper §4.2.2 on TPU).

Level-1: each grid block scans one tile of the distance row and keeps a
truncated top-k' queue (k' from the binomial bound in
``core/approx_topk_math.py``). Level-2: exact merge of the ``num_blocks * k'``
survivors. Level-1 is the bandwidth-critical stage — it reads the full
distance row; level-2 touches only KBs and runs as a tiny epilogue.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import merge_topk_rows


def _l1_kernel(d_ref, out_d_ref, out_i_ref, *, tile: int, k_prime: int,
               rows: int):
    t = pl.program_id(1)
    d = d_ref[...]                                           # [rows, tile]
    col = t * tile + jax.lax.broadcasted_iota(jnp.int32, d.shape, 1)
    empty = (rows, k_prime)
    top_d, top_i = merge_topk_rows(jnp.full(empty, jnp.inf, d.dtype),
                                   jnp.full(empty, -1, jnp.int32), d, col,
                                   k_prime)
    out_d_ref[...] = top_d[:, None, :]
    out_i_ref[...] = top_i[:, None, :]


@functools.partial(jax.jit,
                   static_argnames=("k", "k_prime", "num_blocks", "row_tile",
                                    "interpret"))
def hierarchical_topk(
    d: jnp.ndarray,
    k: int,
    k_prime: int,
    num_blocks: int,
    row_tile: int = 8,
    interpret: bool = True,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """d: [B, n] f32 (+inf = invalid) -> (dists [B, k], idx [B, k]) ascending.

    Approximate: identical to exact top-k unless one level-1 block holds more
    than k' of the true top-k (probability bounded by
    ``approx_topk_math.queue_overflow_prob(k, num_blocks, k_prime)``)."""
    B, n = d.shape
    assert n % num_blocks == 0, (n, num_blocks)
    tile = n // num_blocks
    assert B % row_tile == 0, (B, row_tile)

    l1_d, l1_i = pl.pallas_call(
        functools.partial(_l1_kernel, tile=tile, k_prime=k_prime,
                          rows=row_tile),
        grid=(B // row_tile, num_blocks),
        in_specs=[pl.BlockSpec((row_tile, tile), lambda b, t: (b, t))],
        out_specs=(
            pl.BlockSpec((row_tile, 1, k_prime), lambda b, t: (b, t, 0)),
            pl.BlockSpec((row_tile, 1, k_prime), lambda b, t: (b, t, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((B, num_blocks, k_prime), d.dtype),
            jax.ShapeDtypeStruct((B, num_blocks, k_prime), jnp.int32),
        ),
        interpret=interpret,
    )(d)

    # Level-2 queue: exact merge over the truncated survivors (tiny).
    flat_d = l1_d.reshape(B, num_blocks * k_prime)
    flat_i = l1_i.reshape(B, num_blocks * k_prime)
    neg, pos = jax.lax.top_k(-flat_d, k)
    out_i = jnp.take_along_axis(flat_i, pos, axis=1)
    out_d = -neg
    return out_d, jnp.where(jnp.isinf(out_d), -1, out_i)
