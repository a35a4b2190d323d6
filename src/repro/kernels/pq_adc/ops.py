"""Public wrappers for the PQ ADC kernels, routed through the kernel
registry (``repro.kernels.registry``).

``pq_adc_topk`` is the staged per-shard unit ChamVS calls per memory
node (the fused multi-shard path lives in ``kernels/chamvs_scan``); it
handles padding to tile multiples and takes a ``KernelSpec``:
  * backend "pallas" — the Pallas kernel (interpret mode on CPU,
    compiled on TPU);
  * backend "ref"    — the pure-jnp oracle (also the paper's
    CPU-baseline flavor).
``backend=``/``interpret=`` kwargs remain as deprecated aliases.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import registry
from repro.kernels.pq_adc import kernel as _k
from repro.kernels.pq_adc import ref as _ref


def _pad_to(x: jnp.ndarray, axis: int, mult: int) -> jnp.ndarray:
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit, static_argnames=("k",))
def _jit_ref_topk(luts, codes, lens, k: int):
    npad = codes.shape[1]
    valid = jnp.arange(npad)[None, :] < lens[:, None]
    d = jax.vmap(_ref.ref_adc)(luts, codes)
    d = jnp.where(valid, d, jnp.inf)
    neg, idx = jax.lax.top_k(-d, k)
    idx = jnp.where(jnp.isinf(-neg), -1, idx)
    return -neg, idx.astype(jnp.int32)


def pq_adc_topk(
    luts: jnp.ndarray,
    codes: jnp.ndarray,
    lens: jnp.ndarray,
    k: int,
    tile_n: Optional[int] = None,
    spec: Optional[registry.KernelSpec] = None,
    backend: Optional[str] = None,
    interpret: Optional[bool] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fused ADC + local top-k over a batch of probed lists.

    luts [B, m, ksub] f32 | codes [B, n, m] uint8 | lens [B] int32
    -> (dists [B, k], row_idx [B, k]) ascending.
    """
    spec = registry.resolve("pq_adc_topk", spec, backend, interpret)
    if tile_n is not None and spec.tile_n != tile_n:
        spec = dataclasses.replace(spec, tile_n=tile_n)
    n = codes.shape[1]
    tile = spec.pick_tile_n(n)
    codes = _pad_to(codes, 1, tile)
    if spec.backend == "pallas":
        return _k.adc_scan(luts, codes, lens, k, tile_n=tile,
                           interpret=spec.use_interpret())
    return _jit_ref_topk(luts, codes, lens, k=k)


@jax.jit
def _jit_ref_shared(luts, codes):
    return _ref.ref_shared_scan(luts, codes).T


def pq_shared_scan(
    luts: jnp.ndarray,
    codes: jnp.ndarray,
    tile_n: Optional[int] = None,
    spec: Optional[registry.KernelSpec] = None,
    backend: Optional[str] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Batched-LUT shared scan: luts [q, m, ksub], codes [n, m] -> [n, q]."""
    spec = registry.resolve("pq_shared_scan", spec, backend, interpret)
    if tile_n is not None and spec.tile_n != tile_n:
        spec = dataclasses.replace(spec, tile_n=tile_n)
    n = codes.shape[0]
    tile = spec.pick_tile_n(n)
    codes_p = _pad_to(codes, 0, tile)
    if spec.backend == "pallas":
        out = _k.shared_scan(luts, codes_p, tile_n=tile,
                             interpret=spec.use_interpret())
    else:
        out = _jit_ref_shared(luts, codes_p)
    return out[:n]
