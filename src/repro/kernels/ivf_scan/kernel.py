"""Pallas kernel: fused IVF index scan (centroid matmul + running top-nprobe).

ChamVS.idx (paper §3): queries are compared against all ``nlist`` coarse
centroids and the ``nprobe`` closest lists are selected. On GPU the paper runs
this as two passes (GEMM then select); here the top-nprobe selection is fused
into the GEMM's epilogue so centroid-distance tiles never round-trip to HBM —
the [tile_q, tile_c] score tile is consumed in VMEM by the running queue
carried in the output refs across the centroid-tile grid axis.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import LANES, merge_topk_rows, round_up


def _ivf_scan_kernel(q_ref, c_ref, c2_ref, out_d_ref, out_i_ref, *,
                     tile_q: int, tile_c: int, nprobe: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        out_d_ref[...] = jnp.full_like(out_d_ref, jnp.inf)
        out_i_ref[...] = jnp.full_like(out_i_ref, -1)

    q = q_ref[...]                                             # [tile_q, D]
    c = c_ref[...]                                             # [tile_c, D]
    # dist = ||q||^2 - 2 q.c + ||c||^2 ; the ||q||^2 term is rank-invariant
    # per row but kept so returned values equal true L2^2 distances.
    scores = jax.lax.dot_general(
        q, c, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)                    # MXU
    q2 = jnp.sum(q * q, axis=-1, keepdims=True)
    d = q2 - 2.0 * scores + c2_ref[...]                        # [tile_q, tile_c]

    col = ci * tile_c + jax.lax.broadcasted_iota(jnp.int32, d.shape, 1)
    top_d, top_i = merge_topk_rows(out_d_ref[...], out_i_ref[...], d, col,
                                   nprobe)
    out_d_ref[...] = top_d
    out_i_ref[...] = top_i


@functools.partial(jax.jit,
                   static_argnames=("nprobe", "tile_q", "tile_c", "interpret"))
def ivf_scan(queries: jnp.ndarray, centroids: jnp.ndarray, nprobe: int,
             tile_q: int = 8, tile_c: int = 512, interpret: bool = True
             ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """queries [nq, D], centroids [nlist, D] ->
    (dists [nq, nprobe], list_ids [nq, nprobe]) ascending."""
    nq, D = queries.shape
    nlist = centroids.shape[0]
    tile_q = min(tile_q, nq)
    tile_c = min(tile_c, nlist)
    assert nq % tile_q == 0 and nlist % tile_c == 0, (nq, tile_q, nlist, tile_c)
    c2 = jnp.sum(centroids.astype(jnp.float32) ** 2, axis=-1)[None, :]

    kq = round_up(nprobe, LANES)   # lane-aligned running queue width

    kernel = functools.partial(_ivf_scan_kernel, tile_q=tile_q, tile_c=tile_c,
                               nprobe=nprobe)
    out_d, out_i = pl.pallas_call(
        kernel,
        name="ivf_scan",
        grid=(nq // tile_q, nlist // tile_c),
        in_specs=[
            pl.BlockSpec((tile_q, D), lambda qi, ci: (qi, 0)),
            pl.BlockSpec((tile_c, D), lambda qi, ci: (ci, 0)),
            pl.BlockSpec((1, tile_c), lambda qi, ci: (0, ci)),
        ],
        out_specs=(
            pl.BlockSpec((tile_q, kq), lambda qi, ci: (qi, 0)),
            pl.BlockSpec((tile_q, kq), lambda qi, ci: (qi, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((nq, kq), jnp.float32),
            jax.ShapeDtypeStruct((nq, kq), jnp.int32),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(queries.astype(jnp.float32), centroids.astype(jnp.float32), c2)
    return out_d[:, :nprobe], out_i[:, :nprobe]
