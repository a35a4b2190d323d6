"""Pallas kernel: streaming decode-attention over the slotted KV pool.

The legacy decode path materializes GQA-expanded K/V to ``[B, S, H, D]``
and a full ``[B, H, 1, S]`` score row over the *entire padded pool seq
axis* every step. This kernel is the dataflow-faithful replacement (the
LM-side twin of ``chamvs_scan``'s streaming K-selection):

  * grid ``(B // tile_b, S // blk)`` — the trailing **kv-block axis** is
    the streaming axis: each step pulls one ``[tile_b, blk, KV*D]``
    K/V block HBM->VMEM and folds it into an online-softmax accumulator
    held in VMEM scratch across the kv-block axis, so the ``[B, H, S]``
    score row never exists;
  * **GQA-native**: each query head is laid out block-diagonally over
    the merged ``KV*D`` lane axis (zero outside its KV group's lanes),
    so scores contract directly against the cache — no ``_repeat_kv``
    materialization anywhere;
  * **length-aware**: per-block validity is derived from each row's
    absolute ``position`` (linear slot ``i`` holds position ``i``; ring
    slot ``i`` holds ``pos - ((pos - i) mod S)``; sliding ``window``
    masks on top), and a whole kv block is **skipped** — zero FLOPs,
    accumulators untouched — when every slot in it is invalid for every
    row in the tile: blocks past the tile's max position, and (linear
    caches with a window) blocks wholly below the tile's min window
    edge. Short sequences in a ragged wave therefore stop paying for
    the pool's ``max_seq`` padding.

Both validity families reduce to the same skip predicate
``block_start > max(position)`` (a ring slot ``i`` is invalid exactly
when ``i > pos`` while the ring has not wrapped, and never invalid
after it wraps — at which point ``max(position) >= S - 1`` keeps every
block live).

**Latent mode** (``fused_latent_decode_attention``, kernel name
``decode_attn_latent``): multi-head latent attention decodes every query
head against one cached latent row per position, ``[normed c_kv |
roped k_pe]``. Each wave row streams its own slot's rows straight from
the pool (slots and positions in SMEM; no gathered copy), ``[blk, W]``
a step; scores contract the whole row with the absorbed query, and the
values are the same block's first ``v_dim`` lanes, never a second copy.
Online softmax and the length-aware block skip are the dense mode's
(per row here, and a skipped block is not fetched); a latent cache is
linear (no ring, no window).

Validated against the grouped ``ref`` oracle and the legacy einsum path
in ``tests/test_decode_attn.py`` (hypothesis property test). Both
in-kernel einsums have one batch dim (the wave row), which is what
Mosaic's matmul takes, and per-row positions come in through SMEM
scalar prefetch; ``tests/test_chip_compile.py`` compiles the kernel for
a described v5e. On a CPU host it runs in interpret mode.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_attn_kernel(pos_ref, q_ref, k_ref, v_ref, out_ref,
                        acc_ref, m_ref, l_ref, *, tile_b: int, blk: int,
                        s_real: int, window: int, ring: bool, scale: float):
    i = pl.program_id(0)
    j = pl.program_id(1)
    nb = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # per-row positions live in SMEM (scalar prefetch): a [tile_b, 1]
    # VMEM block would break the (8, 128) tiling for odd wave tiles
    rows = [pos_ref[i * tile_b + r] for r in range(tile_b)]
    start = j * blk
    # tile-level skip: every slot in this block invalid for every row
    live = start <= functools.reduce(jnp.maximum, rows)
    if window > 0 and not ring:
        # linear cache + sliding window: blocks wholly below the tile's
        # min window edge are dead too (the window slid past them)
        live = jnp.logical_and(
            live, start + blk - 1 > functools.reduce(jnp.minimum, rows)
            - window)

    @pl.when(live)
    def _block():
        q = q_ref[...].astype(jnp.float32)                # [tile_b, H, E]
        k = k_ref[...].astype(jnp.float32)                # [tile_b, blk, E]
        v = v_ref[...].astype(jnp.float32)
        # q is block-diagonal over the KV-head lane groups (see
        # ``fused_decode_attention``), so contracting the whole
        # KV*D lane axis gives each head its own group's scores: one
        # batch dim, which is all Mosaic's matmul takes
        s = jnp.einsum("bhe,bse->bhs", q, k,
                       preferred_element_type=jnp.float32) * scale
        shape = s.shape                                   # [tile_b, H, blk]
        row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        pos = jnp.zeros(shape, jnp.int32)
        for r, p_r in enumerate(rows):
            pos = jnp.where(row == r, p_r, pos)
        slot = start + jax.lax.broadcasted_iota(jnp.int32, shape, 2)
        if ring:
            p_slot = pos - ((pos - slot) % s_real)
            valid = p_slot >= 0
        else:
            p_slot = slot
            valid = p_slot <= pos
        if window > 0:
            valid &= p_slot > pos - window
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_ref[...]                               # [tile_b, H, 1]
        m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        l_ref[...] = l_ref[...] * corr + p.sum(-1, keepdims=True)
        pv = jnp.einsum("bhs,bse->bhe", p, v,
                        preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * corr + pv
        m_ref[...] = m_new

    @pl.when(j == nb - 1)
    def _final():
        out_ref[...] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-20)


@functools.partial(jax.jit, static_argnames=("window", "ring", "tile_b",
                                             "blk", "interpret"))
def fused_decode_attention(q: jnp.ndarray, k_cache: jnp.ndarray,
                           v_cache: jnp.ndarray, position: jnp.ndarray,
                           window: int = 0, ring: bool = False,
                           tile_b: int = 1, blk: int = 128,
                           interpret: bool = True) -> jnp.ndarray:
    """One streaming dispatch for a whole decode wave.

    q [B, 1, H, D] | k_cache/v_cache [B, S, KV, D] | position [B] int32
    -> [B, 1, H, D]. ``tile_b`` must divide B and ``blk`` must divide S
    (the frontend picks legal tiles via the registry heuristics).

    Layout: the caches are read as ``[B, S, KV*D]`` (the KV-head and
    head-dim axes merged into one lane axis), and q is expanded to a
    block-diagonal ``[B, H, KV*D]`` whose head ``h`` row is zero outside
    its KV group's D lanes. Both contractions are then plain
    one-batch-dim matmuls, and the kernel returns each head's output in
    its group's lanes, which the wrapper folds back to ``[B, H, D]``.
    """
    B, S, KV, D = k_cache.shape
    H = q.shape[2]
    G = H // KV
    E = KV * D
    assert B % tile_b == 0 and S % blk == 0, (B, tile_b, S, blk)
    group = jnp.arange(H) // G                                  # [H]
    onehot = (group[:, None] == jnp.arange(KV)[None, :])        # [H, KV]
    q_bd = (q[:, 0, :, None, :] *
            onehot[None, :, :, None].astype(q.dtype)).reshape(B, H, E)
    k2 = k_cache.reshape(B, S, E)
    v2 = v_cache.reshape(B, S, E)
    pos = jnp.asarray(position, jnp.int32).reshape(B)
    kernel = functools.partial(_decode_attn_kernel, tile_b=tile_b, blk=blk,
                               s_real=S, window=window, ring=ring,
                               scale=D ** -0.5)
    out = pl.pallas_call(
        kernel,
        name="decode_attn",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B // tile_b, S // blk),
            in_specs=[
                pl.BlockSpec((tile_b, H, E), lambda i, j, p: (i, 0, 0)),
                pl.BlockSpec((tile_b, blk, E), lambda i, j, p: (i, j, 0)),
                pl.BlockSpec((tile_b, blk, E), lambda i, j, p: (i, j, 0)),
            ],
            # index_map ignores j: the output block is written once, at
            # the last kv block; (acc, m, l) live in VMEM scratch
            out_specs=pl.BlockSpec((tile_b, H, E), lambda i, j, p: (i, 0, 0)),
            scratch_shapes=[pltpu.VMEM((tile_b, H, E), jnp.float32),
                            pltpu.VMEM((tile_b, H, 1), jnp.float32),
                            pltpu.VMEM((tile_b, H, 1), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((B, H, E), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(pos, q_bd, k2, v2)
    # fold each head's own KV-group lanes back out of the [H, KV*D] rows
    out = jnp.einsum("bhkd,hk->bhd", out.reshape(B, H, KV, D),
                     onehot.astype(out.dtype))
    return out[:, None].astype(v_cache.dtype)


def _latent_decode_kernel(slot_ref, pos_ref, q_ref, c_ref, out_ref, acc_ref,
                          m_ref, l_ref, *, blk: int, v_dim: int,
                          scale: float):
    i = pl.program_id(0)
    j = pl.program_id(1)
    nb = pl.num_programs(1)
    pos = pos_ref[i]

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(j * blk <= pos)
    def _block():
        q = q_ref[0].astype(jnp.float32)                  # [H, W]
        c = c_ref[0].astype(jnp.float32)                  # [blk, W]
        s = jax.lax.dot_general(q, c, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        slot = j * blk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        valid = slot <= pos                               # [H, blk]
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_ref[...]                               # [H, 1]
        m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        l_ref[...] = l_ref[...] * corr + p.sum(-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(
            p, c[:, :v_dim], preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == nb - 1)
    def _final():
        out_ref[0] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-20)


@functools.partial(jax.jit, static_argnames=("kv_len", "scale", "v_dim",
                                             "blk", "interpret"))
def fused_latent_decode_attention(q: jnp.ndarray, cache: jnp.ndarray,
                                  slots: jnp.ndarray, position: jnp.ndarray,
                                  kv_len: int, scale: float, v_dim: int,
                                  blk: int = 128,
                                  interpret: bool = True) -> jnp.ndarray:
    """Latent-mode decode attention for a whole wave, read straight from
    the pool.

    q [B, H, W] (each head's absorbed no-rope query, then its roped
    query) | cache [P, S, W] (the pool; row i of a slot: position i's
    latent, then its roped key) | slots, position [B] int32 -> [B, H,
    v_dim] float32: each head's softmax-weighted sum of the first
    ``v_dim`` lanes of its slot's rows ``0..position``. The grid streams
    the first ``kv_len`` rows of each wave row's slot, ``blk`` at a time
    (``blk`` divides ``kv_len``); blocks past a row's position are
    skipped, and their index clamps to its last live block, so they are
    not fetched either."""
    B, H, W = q.shape
    assert kv_len % blk == 0 and kv_len <= cache.shape[1], (kv_len, blk)
    kernel = functools.partial(_latent_decode_kernel, blk=blk, v_dim=v_dim,
                               scale=scale)

    def rows(i, j, s, p):
        return s[i], jnp.minimum(j, p[i] // blk), 0

    return pl.pallas_call(
        kernel,
        name="decode_attn_latent",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, kv_len // blk),
            in_specs=[
                pl.BlockSpec((1, H, W), lambda i, j, s, p: (i, 0, 0)),
                pl.BlockSpec((1, blk, W), rows),
            ],
            out_specs=pl.BlockSpec((1, H, v_dim),
                                   lambda i, j, s, p: (i, 0, 0)),
            scratch_shapes=[pltpu.VMEM((H, v_dim), jnp.float32),
                            pltpu.VMEM((H, 1), jnp.float32),
                            pltpu.VMEM((H, 1), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((B, H, v_dim), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(jnp.asarray(slots, jnp.int32).reshape(B),
      jnp.asarray(position, jnp.int32).reshape(B), q, cache)
