"""Pallas kernel: the fused streaming ChamVS scan (paper §4 dataflow).

The paper's near-memory accelerator is a *pipeline*, not a sequence of
kernels: the systolic PQ decoder streams ADC distances straight into the
K-selection priority-queue network, and the full distance array never
exists anywhere (§4.2). The staged reproduction ran three dispatches
per shard (ADC scan -> materialized [B, n] distances -> top-k) with a
Python loop over shards on top. This kernel is the dataflow-faithful
version:

  * grid ``(S, nq // tile_q, nprobe)`` — the leading **shard axis**
    makes the scan over every memory node's slice ONE dispatch per
    retrieval wave;
  * per grid step, the probed list's code tile streams HBM->VMEM, the
    per-(query, probe) LUT turns codes into ADC partial distances
    (one-hot contraction on the MXU — the TPU VPU has no per-lane
    byte-addressable BRAM, see pq_adc/kernel.py), and the
    ``[tile_q, cap]`` distance tile is folded immediately into a
    per-query **running top-k'** carried in the output refs across the
    probe grid axis (their index_map ignores the probe index, so the
    queue is scratch-resident between steps — streaming K-selection,
    paper §4.2.2);
  * global vector ids ride along with the distances, so the candidate
    the queue keeps is already ``(dist, global_id)`` — no separate
    local-row -> id remap dispatch afterwards.

Layout. Mosaic tiles the last two dims of every block by (8, 128), so
the wrapper lays the per-probe operands out with the query tile on the
sublane axis: codes ``[S, np, nq, m, cap]`` (code bytes lane-major),
ids ``[S, np, nq, cap]`` and list lengths ``[S, np, nq, 1]``. The
running queue is ``kq = round_up(kk, 128)`` lanes wide; its first
``kk`` columns are the answer.

Validated against the staged pipeline and ``ref.py`` in
``tests/test_chamvs_scan.py`` (hypothesis property test), and compiled
for a described v5e chip in ``tests/test_chip_compile.py``.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import LANES, merge_topk_rows, round_up

CHUNK = 512      # most list rows one-hot-decoded per MXU contraction
SUBLANES = 8


def _lane_chunk(cap: int) -> int:
    """Largest divisor of ``cap`` up to ``CHUNK``, preferring whole lane
    tiles (multiples of 128): every chunk of the list is then the same
    width — the chip's compiler crashes on a ragged last chunk."""
    divisors = [d for d in range(min(cap, CHUNK), 0, -1) if cap % d == 0]
    return next((d for d in divisors if d % LANES == 0 or d == cap),
                divisors[0])


def _chamvs_scan_kernel(lens_ref, lut_ref, codes_ref, gid_ref,
                        out_d_ref, out_i_ref, dist_ref, codes_ref_32, *,
                        tile_q: int, cap: int, m: int, ksub: int, kk: int):
    p = pl.program_id(2)

    @pl.when(p == 0)
    def _init():
        out_d_ref[...] = jnp.full_like(out_d_ref, jnp.inf)
        out_i_ref[...] = jnp.full_like(out_i_ref, -1)

    # ADC as a one-hot contraction, one query row at a time: per
    # (query, sub-space) the code bytes are one-hot against the ksub
    # iota and contracted with that query's LUT row on the MXU
    # (HIGHEST precision: the 0/1 operand is exact, so the distances
    # match the gather oracle to f32 rounding). The list is taken in
    # equal lane chunks of at most ``CHUNK`` so the [ksub, chunk] one-hot
    # stays small in VMEM whatever the list capacity.
    chunk = _lane_chunk(cap)
    group = SUBLANES if m % SUBLANES == 0 else m
    iota = jax.lax.broadcasted_iota(jnp.int32, (ksub, chunk), 0)

    def q_body(qi, _):
        # widen this query's code bytes once: int32 rows can be read in
        # aligned groups at a dynamic offset, packed int8 rows cannot
        codes_ref_32[...] = codes_ref[0, 0, qi].astype(jnp.int32)
        row = []
        for c0 in range(0, cap, chunk):            # static lane chunks

            def m_body(jg, acc):
                # sub-spaces in sublane-aligned groups of ``group`` rows
                j0 = pl.multiple_of(jg * group, group)
                cg = codes_ref_32[pl.ds(j0, group), pl.ds(c0, chunk)]
                lg = lut_ref[qi, 0, pl.ds(j0, group), :]   # [group, ksub]
                for r in range(group):
                    eq = (iota == cg[r:r + 1]).astype(jnp.float32)
                    acc = acc + jax.lax.dot_general(
                        lg[r:r + 1], eq, (((1,), (0,)), ((), ())),
                        precision=jax.lax.Precision.HIGHEST,
                        preferred_element_type=jnp.float32)  # [1, chunk]
                return acc

            row.append(jax.lax.fori_loop(0, m // group, m_body,
                                         jnp.zeros((1, chunk), jnp.float32)))
        dist_ref[pl.ds(qi, 1), :] = jnp.concatenate(row, axis=1)
        return 0

    jax.lax.fori_loop(0, tile_q, q_body, 0)

    # rows beyond the probed list's valid length get +inf
    col = jax.lax.broadcasted_iota(jnp.int32, (tile_q, cap), 1)
    dist = jnp.where(col < lens_ref[0, 0], dist_ref[...], jnp.inf)

    # fold the tile into the running queue carried across the probe axis
    top_d, top_i = merge_topk_rows(out_d_ref[0], out_i_ref[0], dist,
                                   gid_ref[0, 0], kk)
    out_d_ref[0] = top_d
    out_i_ref[0] = top_i


@functools.partial(jax.jit, static_argnames=("kk", "tile_q", "interpret"))
def fused_scan(luts: jnp.ndarray, codes: jnp.ndarray, gids: jnp.ndarray,
               lens: jnp.ndarray, kk: int, tile_q: int = 8,
               interpret: bool = True
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One dispatch: ADC + streaming top-kk over every shard's probed lists.

    luts:  [nq, nprobe, m, ksub] f32 — per-(query, probed list) LUTs
           (shared by all shards; residual PQ makes them probe-dependent)
    codes: [S, nq, nprobe, cap, m] uint8 — gathered probed-list codes
    gids:  [S, nq, nprobe, cap] int32 — global vector ids (-1 = pad)
    lens:  [S, nq, nprobe] int32 — valid prefix length per probed list
    Returns (dists [S, nq, kk], ids [S, nq, kk]) ascending; ids are
    global vector ids, -1 where fewer than kk candidates exist.
    ``tile_q`` must divide nq (compiled, it must also be a multiple of
    8 or nq itself — ``common.query_tile`` picks such a tile).
    """
    S, nq, nprobe, cap, m = codes.shape
    ksub = luts.shape[-1]
    assert nq % tile_q == 0, (nq, tile_q)
    kq = round_up(kk, LANES)
    codes_t = jnp.transpose(codes, (0, 2, 1, 4, 3))     # [S, np, nq, m, cap]
    gids_t = jnp.transpose(gids, (0, 2, 1, 3))           # [S, np, nq, cap]
    lens_t = jnp.transpose(lens, (0, 2, 1))[..., None]   # [S, np, nq, 1]
    grid = (S, nq // tile_q, nprobe)
    kernel = functools.partial(_chamvs_scan_kernel, tile_q=tile_q, cap=cap,
                               m=m, ksub=ksub, kk=kk)
    out_d, out_i = pl.pallas_call(
        kernel,
        name="chamvs_scan",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, tile_q, 1), lambda s, q, p: (s, p, q, 0)),
            pl.BlockSpec((tile_q, 1, m, ksub), lambda s, q, p: (q, p, 0, 0)),
            pl.BlockSpec((1, 1, tile_q, m, cap),
                         lambda s, q, p: (s, p, q, 0, 0)),
            pl.BlockSpec((1, 1, tile_q, cap), lambda s, q, p: (s, p, q, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, tile_q, kq), lambda s, q, p: (s, q, 0)),
            pl.BlockSpec((1, tile_q, kq), lambda s, q, p: (s, q, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((S, nq, kq), jnp.float32),
            jax.ShapeDtypeStruct((S, nq, kq), jnp.int32),
        ),
        scratch_shapes=[pltpu.VMEM((tile_q, cap), jnp.float32),
                        pltpu.VMEM((m, cap), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(lens_t, luts.astype(jnp.float32), codes_t, gids_t)
    return out_d[..., :kk], out_i[..., :kk]
