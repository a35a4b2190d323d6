"""Attention: GQA with full / sliding-window masks, and multi-head latent
attention (MLA, DeepSeek-V2 arXiv 2405.04434 §2.1).

Execution paths:
  * ``flash_attention`` — blocked online-softmax over (q-block, kv-block)
    tiles via ``lax.scan`` so the [T, S] score matrix is never materialized
    (required: train_4k batch 256 and prefill_32k would otherwise allocate
    TB-scale score tensors). This is the pure-JAX analogue of a Pallas/TPU
    flash kernel and is what the dry-run lowers.
  * ``naive_attention`` — direct softmax(QK^T)V oracle for tests.
  * ``decode_attention`` — one new token against a KV cache (full or ring),
    spec-routed through the kernel registry: ``backend="ref"`` (default)
    is the grouped-einsum path that contracts the KV-head axis directly,
    ``backend="pallas"`` the streaming ``kernels/decode_attn`` kernel
    (online softmax over kv blocks, skips blocks past each row tile's
    max valid position), ``backend="einsum"`` the legacy
    full-materialization path kept as the parity oracle
    (``decode_attention_einsum``).

MLA caches one row per position, ``[rms_norm(c_kv) | rope(k_pe)]``
(``kv_lora_rank + qk_rope_head_dim`` wide). Prefill expands it to per-head
keys and values (``mla_prefill``, flash attention); decode takes the
absorbed form (``mla_decode``): each head's no-rope query times its slice
of W_UK scores the cached latent directly, and the attention-weighted
latent times W_UV is the head's value, through the ``decode_attn``
kernel's latent mode.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import registry
from repro.kernels.decode_attn.ops import (pallas_decode_attention,
                                           pallas_latent_decode_attention)
from repro.kernels.decode_attn.ref import (decode_validity,
                                           ref_decode_attention,
                                           ref_latent_decode_attention)
from repro.models.ctx import constrain, kv_tags
from repro.models.layers import apply_rope_pairs, rms_norm, yarn_inv_freq

NEG_INF = -1e30


def _repeat_kv(k: jnp.ndarray, q_per_kv: int) -> jnp.ndarray:
    """[B, S, KV, D] -> [B, S, KV*q_per_kv, D] (GQA head expansion)."""
    if q_per_kv == 1:
        return k
    b, s, kv, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, kv, q_per_kv, d)
                            ).reshape(b, s, kv * q_per_kv, d)


def _mask(qpos: jnp.ndarray, kpos: jnp.ndarray, causal: bool, window: int
          ) -> jnp.ndarray:
    """[..., Tq, Tk] boolean validity from absolute positions."""
    m = jnp.ones(qpos.shape[:-1] + (qpos.shape[-1], kpos.shape[-1]), bool)
    if causal:
        m &= kpos[..., None, :] <= qpos[..., :, None]
    if window > 0:
        m &= kpos[..., None, :] > qpos[..., :, None] - window
    return m


def naive_attention(q, k, v, q_positions, k_positions, causal=True, window=0):
    """Oracle. q [B,T,H,D], k/v [B,S,KV,D], positions [B,T]/[B,S] -> [B,T,H,D]."""
    qkv = q.shape[2] // k.shape[2]
    k, v = _repeat_kv(k, qkv), _repeat_kv(v, qkv)
    scores = jnp.einsum("bthd,bshd->bhts", q, k).astype(jnp.float32)
    scores *= q.shape[-1] ** -0.5
    m = _mask(q_positions, k_positions, causal, window)[:, None]   # [B,1,T,S]
    scores = jnp.where(m, scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    # fully-masked rows (window slid past): zero output, not nan
    w = jnp.where(m.any(-1, keepdims=True), w, 0.0)
    return jnp.einsum("bhts,bshd->bthd", w.astype(v.dtype), v)


def flash_attention(q, k, v, q_positions, k_positions, causal=True, window=0,
                    q_block=512, k_block=512):
    """Blocked online-softmax attention (memory O(T*D), not O(T*S)).

    q [B,T,H,D], k/v [B,S,KV,D]; positions carry absolute indices so causal /
    sliding-window masks work for prefill with history and for padded tails.

    Custom VJP (FA2-style): the backward recomputes p-tiles from q/k and the
    saved per-row (m, l) statistics instead of letting autodiff checkpoint
    every kv-scan iteration — plain autodiff of the scan stored ~8 TB/layer
    of residuals for llama3-405b train (EXPERIMENTS.md §Perf iteration 9).
    """
    out, _ = _flash_fwd_stats(q, k, v, q_positions, k_positions, causal,
                              window, q_block, k_block)
    return out


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _flash_fwd_stats(q, k, v, q_positions, k_positions, causal, window,
                     q_block, k_block):
    return _flash_forward(q, k, v, q_positions, k_positions, causal, window,
                          q_block, k_block)


def _flash_forward(q, k, v, q_positions, k_positions, causal=True, window=0,
                   q_block=512, k_block=512, scale=None):
    """Returns (out [B,T,H,Dv], lse [B,T,H]) — log-sum-exp per row for bwd.
    ``v`` may be narrower than q and k (latent attention's value heads);
    ``scale`` defaults to ``D ** -0.5``."""
    B, T, H, D = q.shape
    Dv = v.shape[-1]
    S = k.shape[1]
    qkv = H // k.shape[2]
    q_block = min(q_block, T)
    k_block = min(k_block, S)
    # pad to block multiples; padded q rows are garbage-in/garbage-out (cropped),
    # padded k rows get position +inf-like so every mask rejects them.
    Tp = -(-T // q_block) * q_block
    Sp = -(-S // k_block) * k_block
    qp = jnp.pad(q, ((0, 0), (0, Tp - T), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, Sp - S), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, Sp - S), (0, 0), (0, 0)))
    qpos = jnp.pad(q_positions, ((0, 0), (0, Tp - T)))
    kpos = jnp.pad(k_positions, ((0, 0), (0, Sp - S)),
                   constant_values=jnp.iinfo(jnp.int32).max)
    kp = _repeat_kv(kp, qkv)
    vp = _repeat_kv(vp, qkv)
    # pin the attention layout: batch over dp, heads over model. Without
    # this GSPMD re-shards q/k/v feature-wise inside the kv scan and
    # replicates the batch (measured 27 TB/step prefill traffic for
    # qwen2-0.5b; EXPERIMENTS.md §Perf iteration 6). Head counts that do
    # not divide the axis (14H/16) are padded by GSPMD — bounded waste.
    qp = constrain(qp, "dp", None, "model", None)
    kp = constrain(kp, "dp", None, "model", None)
    vp = constrain(vp, "dp", None, "model", None)

    nq, nk = Tp // q_block, Sp // k_block
    qb = qp.reshape(B, nq, q_block, H, D)
    qbpos = qpos.reshape(B, nq, q_block)
    kb = kp.reshape(B, nk, k_block, H, D)
    vbv = vp.reshape(B, nk, k_block, H, Dv)
    kbpos = kpos.reshape(B, nk, k_block)
    scale = D ** -0.5 if scale is None else scale

    def q_step(_, qi):
        qblk, qbp = qi                                       # [B,qb,H,D],[B,qb]

        def kv_step(carry, ki):
            acc, mx, sm = carry
            kblk, vblk, kbp = ki
            # f32 accumulation via preferred_element_type: a separate
            # .astype makes XLA re-convert the whole stacked K/V every scan
            # step (missed LICM, measured 34 MB/tile; §Perf iteration 7)
            s = jnp.einsum("bthd,bshd->bhts", qblk, kblk,
                           preferred_element_type=jnp.float32) * scale
            msk = _mask(qbp, kbp, causal, window)[:, None]
            # padded keys carry sentinel positions — always reject them
            msk &= (kbp < jnp.iinfo(jnp.int32).max)[:, None, None, :]
            s = jnp.where(msk, s, NEG_INF)
            new_mx = jnp.maximum(mx, s.max(-1))              # [B,H,qb]
            corr = jnp.exp(mx - new_mx)
            p = jnp.exp(s - new_mx[..., None])
            p = jnp.where(msk, p, 0.0)
            sm = sm * corr + p.sum(-1)
            pv = jnp.einsum("bhts,bshd->bhtd", p.astype(vblk.dtype), vblk,
                            preferred_element_type=jnp.float32)
            acc = acc * corr[..., None] + pv
            return (acc, new_mx, sm), None

        acc0 = jnp.zeros((B, H, q_block, Dv), jnp.float32)
        mx0 = jnp.full((B, H, q_block), NEG_INF, jnp.float32)
        sm0 = jnp.zeros((B, H, q_block), jnp.float32)
        (acc, mx, sm), _ = jax.lax.scan(
            kv_step, (acc0, mx0, sm0),
            (jnp.moveaxis(kb, 1, 0), jnp.moveaxis(vbv, 1, 0),
             jnp.moveaxis(kbpos, 1, 0)))
        out = acc / jnp.maximum(sm[..., None], 1e-20)
        lse = mx + jnp.log(jnp.maximum(sm, 1e-20))           # [B,H,qb]
        return None, (out.astype(q.dtype), lse)

    _, (outs, lses) = jax.lax.scan(
        q_step, None,
        (jnp.moveaxis(qb, 1, 0), jnp.moveaxis(qbpos, 1, 0)))
    out = jnp.moveaxis(outs, 0, 1)                           # [B,nq,H,qb,D]
    out = jnp.moveaxis(out, 3, 2).reshape(B, Tp, H, Dv)
    lse = jnp.moveaxis(lses, 0, 1)                           # [B,nq,H,qb]
    lse = jnp.moveaxis(lse, 3, 2).reshape(B, Tp, H)
    return out[:, :T], lse[:, :T]


def _flash_fwd_rule(q, k, v, q_positions, k_positions, causal, window,
                    q_block, k_block):
    out, lse = _flash_forward(q, k, v, q_positions, k_positions, causal,
                              window, q_block, k_block)
    return (out, lse), (q, k, v, q_positions, k_positions, out, lse)


def _flash_bwd_rule(causal, window, q_block, k_block, res, cts):
    """FA2 backward: recompute p-tiles from (q, k, lse); no stored tiles.

    dq pass: scan q blocks, inner scan kv blocks.
    dk/dv pass: scan kv blocks, inner scan q blocks (loop order swapped so
    each accumulator lives in its own outer scan)."""
    q, k, v, q_positions, k_positions, out, lse = res
    dout = cts[0].astype(jnp.float32)
    B, T, H, D = q.shape
    S, KV = k.shape[1], k.shape[2]
    qkv = H // KV
    qb_n = min(q_block, T)
    kb_n = min(k_block, S)
    Tp = -(-T // qb_n) * qb_n
    Sp = -(-S // kb_n) * kb_n

    def padt(x, n, fill=0):
        w = [(0, 0)] * x.ndim
        w[1] = (0, n - x.shape[1])
        return jnp.pad(x, w, constant_values=fill)

    qp = padt(q, Tp).astype(jnp.float32)
    kp = _repeat_kv(padt(k, Sp), qkv).astype(jnp.float32)
    vp = _repeat_kv(padt(v, Sp), qkv).astype(jnp.float32)
    dop = padt(dout, Tp)
    lsep = padt(lse, Tp)
    outp = padt(out, Tp).astype(jnp.float32)
    qpos = padt(q_positions, Tp)
    kpos = padt(k_positions, Sp, fill=jnp.iinfo(jnp.int32).max)
    qp = constrain(qp, "dp", None, "model", None)
    kp = constrain(kp, "dp", None, "model", None)
    vp = constrain(vp, "dp", None, "model", None)
    scale = D ** -0.5
    nq, nk = Tp // qb_n, Sp // kb_n

    # D_i = rowsum(dOut * Out)
    delta = jnp.einsum("bthd,bthd->bth", dop, outp)          # [B,Tp,H]

    def blocks(x, n, blk):
        return jnp.moveaxis(x.reshape(B, n, blk, *x.shape[2:]), 1, 0)

    qB, doB = blocks(qp, nq, qb_n), blocks(dop, nq, qb_n)
    lseB, dltB = blocks(lsep, nq, qb_n), blocks(delta, nq, qb_n)
    qpB = blocks(qpos, nq, qb_n)
    kB, vB = blocks(kp, nk, kb_n), blocks(vp, nk, kb_n)
    kpB = blocks(kpos, nk, kb_n)

    def tile(qblk, qbp, lseb, dltb, dob, kblk, vblk, kbp):
        s = jnp.einsum("bthd,bshd->bhts", qblk, kblk,
                       preferred_element_type=jnp.float32) * scale
        msk = _mask(qbp, kbp, causal, window)[:, None]
        msk &= (kbp < jnp.iinfo(jnp.int32).max)[:, None, None, :]
        p = jnp.where(msk, jnp.exp(s - jnp.moveaxis(lseb, -1, 1)[..., None]),
                      0.0)                                    # [B,H,qb,kb]
        dp = jnp.einsum("bthd,bshd->bhts", dob, vblk,
                        preferred_element_type=jnp.float32)
        ds = p * (dp - jnp.moveaxis(dltb, -1, 1)[..., None]) * scale
        return p, ds

    # pass 1: dq
    def dq_step(_, xs):
        qblk, qbp, lseb, dltb, dob = xs

        def inner(dq_acc, ys):
            kblk, vblk, kbp = ys
            p, ds = tile(qblk, qbp, lseb, dltb, dob, kblk, vblk, kbp)
            dq_acc = dq_acc + jnp.einsum("bhts,bshd->bthd", ds, kblk)
            return dq_acc, None

        dq0 = jnp.zeros((B, qb_n, H, D), jnp.float32)
        dq_blk, _ = jax.lax.scan(inner, dq0, (kB, vB, kpB))
        return None, dq_blk

    _, dqs = jax.lax.scan(dq_step, None, (qB, qpB, lseB, dltB, doB))
    dq = jnp.moveaxis(dqs, 0, 1).reshape(B, Tp, H, D)[:, :T]

    # pass 2: dk, dv
    def dkv_step(_, xs):
        kblk, vblk, kbp = xs

        def inner(carry, ys):
            dk_acc, dv_acc = carry
            qblk, qbp, lseb, dltb, dob = ys
            p, ds = tile(qblk, qbp, lseb, dltb, dob, kblk, vblk, kbp)
            dv_acc = dv_acc + jnp.einsum("bhts,bthd->bshd", p, dob)
            dk_acc = dk_acc + jnp.einsum("bhts,bthd->bshd", ds, qblk)
            return (dk_acc, dv_acc), None

        z = jnp.zeros((B, kb_n, H, D), jnp.float32)
        (dk_blk, dv_blk), _ = jax.lax.scan(inner, (z, z),
                                           (qB, qpB, lseB, dltB, doB))
        return None, (dk_blk, dv_blk)

    _, (dks, dvs) = jax.lax.scan(dkv_step, None, (kB, vB, kpB))
    dk_full = jnp.moveaxis(dks, 0, 1).reshape(B, Sp, H, D)[:, :S]
    dv_full = jnp.moveaxis(dvs, 0, 1).reshape(B, Sp, H, D)[:, :S]
    # un-repeat GQA heads: sum gradient over the q-per-kv group
    dk = dk_full.reshape(B, S, KV, qkv, D).sum(3)
    dv = dv_full.reshape(B, S, KV, qkv, D).sum(3)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            None, None)


_flash_fwd_stats.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def decode_attention(q, k_cache, v_cache, position, window=0,
                     ring: bool = False,
                     spec: Optional[registry.KernelSpec] = None):
    """One-token decode. q [B,1,H,D]; caches [B,S,KV,D]; position [B] int32.

    The batch rows are independent: ``position`` is per-row, and every row
    attends only over its own valid prefix (invalid slots are masked to
    exactly zero weight). This is what lets a wave of pooled cache slots
    with ragged lengths decode as one batch (``transformer.decode_wave``).

    ``ring=True`` means the cache is a sliding ring buffer of size S=window:
    slot i holds absolute position p_i = pos - ((pos - i) mod S); otherwise
    slot i holds absolute position i and validity is i <= pos.

    ``spec`` selects the execution path (see the module docstring). The
    default is ``backend="ref"`` — the grouped-einsum path, the right
    flavor for CPU hosts where Pallas only interprets — NOT the
    registry-wide pallas default; accelerator deployments opt into the
    streaming kernel via ``EngineConfig.attn_backend="pallas"``."""
    spec = registry.resolve("decode_attn", spec, default=registry.REF)
    if spec.backend == "einsum":
        return decode_attention_einsum(q, k_cache, v_cache, position,
                                       window=window, ring=ring)
    tags = kv_tags()
    constrain_scores = None
    if tags is not None:
        # keep the softmax DISTRIBUTED over the seq-sharded cache: without
        # these hints GSPMD all-gathers the full cache per TP column
        # (measured f32 1.1 GB/layer, EXPERIMENTS.md §Perf iteration 4)
        kb, ks = tags
        k_cache = constrain(k_cache, kb, ks, None, None)
        v_cache = constrain(v_cache, kb, ks, None, None)
        # grouped scores are [B, KV, G, T, S]: batch tag on dim 0, the
        # seq-sharded axis on dim 4 — same invariant the einsum oracle
        # pins on its [B, H, T, S] row
        constrain_scores = lambda s: constrain(s, kb, None, None, None, ks)
    if spec.backend == "pallas":
        return pallas_decode_attention(q, k_cache, v_cache, position,
                                       window=window, ring=ring, spec=spec)
    return ref_decode_attention(q, k_cache, v_cache, position,
                                window=window, ring=ring,
                                constrain_scores=constrain_scores)


def decode_attention_einsum(q, k_cache, v_cache, position, window=0,
                            ring: bool = False):
    """The legacy decode path, kept verbatim as the parity oracle: GQA
    heads expanded via ``_repeat_kv`` to [B,S,H,D] and one full
    [B,H,1,S] score row over the entire padded seq axis. Every other
    flavor (grouped ref, streaming Pallas) must match it token-for-token
    under greedy serving (tests/test_decode_attn.py)."""
    B, S, KV, D = k_cache.shape
    H = q.shape[2]
    k = _repeat_kv(k_cache, H // KV)
    v = _repeat_kv(v_cache, H // KV)
    tags = kv_tags()
    if tags is not None:
        kb, ks = tags
        k = constrain(k, kb, ks, None, None)
        v = constrain(v, kb, ks, None, None)
    s = jnp.einsum("bthd,bshd->bhts", q, k).astype(jnp.float32) * D ** -0.5
    if tags is not None:
        s = constrain(s, tags[0], None, None, tags[1])
    valid = decode_validity(position, S, window, ring)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhts,bshd->bthd", w.astype(v.dtype), v)


def prefill_cache(k_cache, v_cache, k_new, v_new, ring: bool = False):
    """Bulk cache construction for prefill of positions 0..T-1 — pad/roll
    instead of a scatter (SPMD scatters into seq-sharded caches force the
    partitioner to replicate operands; §Perf iteration 7)."""
    B, S, KV, D = k_cache.shape
    T = k_new.shape[1]
    dt = k_cache.dtype
    if not ring:
        if T >= S:
            return k_new[:, :S].astype(dt), v_new[:, :S].astype(dt)
        pad = ((0, 0), (0, S - T), (0, 0), (0, 0))
        return jnp.pad(k_new.astype(dt), pad), jnp.pad(v_new.astype(dt), pad)
    if T < S:   # ring not yet wrapped: slots p%S == p
        pad = ((0, 0), (0, S - T), (0, 0), (0, 0))
        return jnp.pad(k_new.astype(dt), pad), jnp.pad(v_new.astype(dt), pad)
    tail_k = k_new[:, T - S:].astype(dt)       # positions T-S .. T-1
    tail_v = v_new[:, T - S:].astype(dt)
    shift = (T - S) % S                         # slot of the first tail pos
    return (jnp.roll(tail_k, shift, axis=1), jnp.roll(tail_v, shift, axis=1))


def update_cache(k_cache, v_cache, k_new, v_new, position, ring: bool = False,
                 slots=None):
    """Write [B,Tn,KV,D] new keys/values at `position` (scalar int or [B]).

    Full cache: slot = position + t. Ring cache: slot = (position + t) % S.
    Scatter form: with donated caches XLA performs the update in place, so
    per-step HBM traffic is O(written slots), not O(cache) — this is what
    keeps the decode memory-roofline term parameter-dominated.

    ``slots`` is the batched-slot path (KV-cache pool): the caches hold
    ``P`` pooled rows while ``k_new``/``v_new`` carry one wave of ``W``
    active rows; row ``w`` of the wave is written into pool row
    ``slots[w]``. Without ``slots`` the wave and the cache batch dims
    coincide (the classic per-sequence layout)."""
    S = k_cache.shape[1]
    B, Tn = k_new.shape[:2]
    pos = jnp.broadcast_to(jnp.asarray(position), (B,))
    t = jnp.arange(Tn)
    seq_idx = pos[:, None] + t[None, :]                       # [B,Tn]
    if ring:
        seq_idx = seq_idx % S
    rows = jnp.arange(B) if slots is None else jnp.asarray(slots)
    bidx = jnp.broadcast_to(rows[:, None], (B, Tn))
    k_cache = k_cache.at[bidx, seq_idx].set(k_new.astype(k_cache.dtype))
    v_cache = v_cache.at[bidx, seq_idx].set(v_new.astype(v_cache.dtype))
    return k_cache, v_cache


# ---------------------------------------------------------------------------
# multi-head latent attention (DeepSeek-V2)
# ---------------------------------------------------------------------------

def _mla_rope(cfg, x, positions):
    """YaRN rotary positions over the roped part x [B, T, H, rope]."""
    inv = yarn_inv_freq(cfg, cfg.qk_rope_head_dim)
    scale = (cfg.yarn_mscale_of(cfg.yarn_mscale)
             / cfg.yarn_mscale_of(cfg.yarn_mscale_all_dim))
    return apply_rope_pairs(x, positions, inv, scale)


def mla_latent(cfg, p, x, positions):
    """The cached rows [B, T, r + rope] of tokens x [B, T, d]: the normed
    latent, then the roped key part every head shares."""
    r = cfg.kv_lora_rank
    kv = x @ p["wkv_a"]
    c = rms_norm(kv[..., :r], p["kv_norm"], cfg.norm_eps)
    k_pe = _mla_rope(cfg, kv[..., None, r:], positions)[:, :, 0]
    return jnp.concatenate([c, k_pe.astype(c.dtype)], -1)


def _mla_query(cfg, p, x, positions):
    B, T, _ = x.shape
    nope = cfg.qk_nope_head_dim
    q = (x @ p["wq"]).reshape(B, T, cfg.n_heads, nope + cfg.qk_rope_head_dim)
    return q[..., :nope], _mla_rope(cfg, q[..., nope:], positions)


def _mla_up(cfg, p):
    """W_UK [r, H, nope] and W_UV [r, H, v] of ``wkv_b``."""
    w = p["wkv_b"].reshape(cfg.kv_lora_rank, cfg.n_heads,
                           cfg.qk_nope_head_dim + cfg.v_head_dim)
    return w[..., :cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim:]


def mla_prefill(cfg, p, x, positions):
    """Causal MLA over x [B, T, d] in the expanded per-head form. Returns
    (out [B, T, H * v], cached rows [B, T, r + rope])."""
    B, T, _ = x.shape
    H, r = cfg.n_heads, cfg.kv_lora_rank
    lat = mla_latent(cfg, p, x, positions)
    q_nope, q_pe = _mla_query(cfg, p, x, positions)
    kv = (lat[..., :r] @ p["wkv_b"]).reshape(
        B, T, H, cfg.qk_nope_head_dim + cfg.v_head_dim)
    k_pe = jnp.broadcast_to(lat[:, :, None, r:],
                            (B, T, H, cfg.qk_rope_head_dim))
    q = jnp.concatenate([q_nope, q_pe], -1)
    k = jnp.concatenate([kv[..., :cfg.qk_nope_head_dim], k_pe], -1)
    out, _ = _flash_forward(q, k, kv[..., cfg.qk_nope_head_dim:], positions,
                            positions, causal=True,
                            scale=cfg.mla_softmax_scale())
    return out.reshape(B, T, H * cfg.v_head_dim), lat


def mla_decode(cfg, p, x, positions, latent, slots, kv_len, spec=None):
    """One decode step of MLA in the absorbed form: x [W, 1, d] at
    positions [W, 1] of pool slots ``slots`` [W] of the latent pool
    [P, S, r + rope]. Writes the step's rows, then reads the first
    ``kv_len`` rows of each slot. Returns (out [W, 1, H * v], pool)."""
    Wv = x.shape[0]
    pos = positions[:, 0]
    lat = mla_latent(cfg, p, x, positions)                   # [W, 1, Lw]
    latent = latent.at[slots, pos].set(lat[:, 0].astype(latent.dtype))
    q_nope, q_pe = _mla_query(cfg, p, x, positions)
    w_uk, w_uv = _mla_up(cfg, p)
    q_lat = jnp.einsum("bhn,rhn->bhr", q_nope[:, 0], w_uk,
                       preferred_element_type=jnp.float32)
    q = jnp.concatenate([q_lat, q_pe[:, 0].astype(jnp.float32)], -1)
    kv_len = latent.shape[1] if kv_len is None else kv_len
    o = latent_decode_attention(q, latent, slots, pos, kv_len,
                                cfg.mla_softmax_scale(), cfg.kv_lora_rank,
                                spec)
    out = jnp.einsum("bhr,rhv->bhv", o.astype(x.dtype), w_uv,
                     preferred_element_type=jnp.float32).astype(x.dtype)
    return out.reshape(Wv, 1, cfg.n_heads * cfg.v_head_dim), latent


def latent_decode_attention(q, latent, slots, position, kv_len: int,
                            scale: float, v_dim: int,
                            spec: Optional[registry.KernelSpec] = None):
    """The latent mode of decode attention, spec-routed like
    ``decode_attention``: the streaming ``decode_attn_latent`` kernel
    (``backend="pallas"``), else the einsum oracle."""
    spec = registry.resolve("decode_attn", spec, default=registry.REF)
    if spec.backend == "pallas":
        return pallas_latent_decode_attention(q, latent, slots, position,
                                              kv_len, scale, v_dim,
                                              spec=spec)
    return ref_latent_decode_attention(q, latent, slots, position, kv_len,
                                       scale, v_dim)
