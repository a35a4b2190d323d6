"""Registry-routed frontend for the Pallas decode-attention kernel.

Born after the registry (like ``chamvs_scan``), so the spec is the only
selector — no legacy ``backend=``/``interpret=`` kwargs. The routing
between the three flavors ("pallas" | "ref" | the legacy "einsum"
oracle) lives in ``repro.models.attention.decode_attention``; this
module owns only the Pallas leg: tile selection, the single-token
contract, and fallback accounting.
"""
from __future__ import annotations

import math
from typing import Optional

import jax.numpy as jnp
import numpy as np

from repro.kernels import registry
from repro.kernels.decode_attn import kernel as _k
from repro.kernels.decode_attn import ref as _ref

#: positions per streamed block of the latent mode
LATENT_BLOCK = 256


def pallas_decode_attention(q: jnp.ndarray, k_cache: jnp.ndarray,
                            v_cache: jnp.ndarray, position: jnp.ndarray,
                            window: int = 0, ring: bool = False,
                            spec: Optional[registry.KernelSpec] = None
                            ) -> jnp.ndarray:
    """Streaming decode-attention — ONE dispatch for the whole wave.

    q [B, 1, H, D] | caches [B, S, KV, D] | position [B] -> [B, 1, H, D].
    Multi-token q (speculative / chunked decode) is outside the kernel's
    single-token contract and routes to the grouped ref oracle with a
    recorded fallback.
    """
    spec = registry.resolve("decode_attn", spec)
    B, S = k_cache.shape[0], k_cache.shape[1]
    if q.shape[1] != 1:
        registry.record_fallback(
            "decode_attn", f"T={q.shape[1]} != 1 (the streaming kernel "
            "decodes one token per row)", spec)
        return _ref.ref_decode_attention(q, k_cache, v_cache, position,
                                         window=window, ring=ring)
    return _k.fused_decode_attention(
        q, k_cache, v_cache, position, window=window, ring=ring,
        tile_b=spec.pick_tile_q(B), blk=spec.pick_block_seq(S),
        interpret=spec.use_interpret())


def pallas_latent_decode_attention(q: jnp.ndarray, cache: jnp.ndarray,
                                   slots: jnp.ndarray,
                                   position: jnp.ndarray, kv_len: int,
                                   scale: float, v_dim: int,
                                   spec: Optional[registry.KernelSpec] = None
                                   ) -> jnp.ndarray:
    """Latent-mode streaming decode attention, one dispatch for the wave:
    q [B, H, W] | pool cache [P, S, W] | slots, position [B] ->
    [B, H, v_dim]. Rows stream in blocks of ``LATENT_BLOCK`` (or the
    spec's ``tile_n``), the largest that divides ``kv_len``: one wave row
    per grid row, so a block is 256 x 576 bf16 at DeepSeek-V2's widths."""
    spec = registry.resolve("decode_attn", spec)
    blk = math.gcd(kv_len, spec.tile_n or LATENT_BLOCK)
    return _k.fused_latent_decode_attention(
        q, cache, slots, position, kv_len=kv_len, scale=scale, v_dim=v_dim,
        blk=blk, interpret=spec.use_interpret())


def count_skipped_blocks(positions: np.ndarray, S: int, blk: int,
                         tile_b: int, window: int = 0, ring: bool = False
                         ) -> tuple:
    """Host-side replica of the kernel's tile-level skip predicate:
    ``(blocks_skipped, blocks_total)`` across the whole grid. Used by
    tests to pin the kernel's skip arithmetic and by stats consumers
    that want the per-tile (not just per-wave) number."""
    pos = np.asarray(positions).reshape(-1)
    assert pos.shape[0] % tile_b == 0 and S % blk == 0
    nb = S // blk
    skipped = total = 0
    for t in range(pos.shape[0] // tile_b):
        tile = pos[t * tile_b:(t + 1) * tile_b]
        for j in range(nb):
            start = j * blk
            live = start <= tile.max()
            if window > 0 and not ring:
                live = live and (start + blk - 1 > tile.min() - window)
            total += 1
            skipped += 0 if live else 1
    return skipped, total
