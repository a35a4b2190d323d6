"""Public frontend for the fused ChamVS scan, routed through the
kernel registry (``repro.kernels.registry.KernelSpec``).

Unlike the older per-kernel frontends there are no legacy
``backend=``/``interpret=`` kwargs here — this frontend was born after
the registry, so the spec is the only selector.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import registry
from repro.kernels.common import query_tile
from repro.kernels.chamvs_scan import kernel as _k
from repro.kernels.chamvs_scan import ref as _ref

_jit_ref = jax.jit(_ref.ref_chamvs_scan, static_argnames=("kk",))


def chamvs_scan(luts: jnp.ndarray, codes: jnp.ndarray, gids: jnp.ndarray,
                lens: jnp.ndarray, kk: int,
                spec: Optional[registry.KernelSpec] = None
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fused multi-shard ADC + streaming top-kk — ONE dispatch for the
    whole retrieval wave.

    luts [nq, np, m, ksub] | codes [S, nq, np, cap, m] uint8 |
    gids [S, nq, np, cap] int32 | lens [S, nq, np] int32
    -> (dists [S, nq, kk], global ids [S, nq, kk]) ascending.
    """
    spec = registry.resolve("chamvs_scan", spec)
    nq = codes.shape[1]
    if spec.backend == "pallas":
        tile, nq_pad = query_tile(nq, spec.tile_q or 8)
        if nq_pad > nq:     # pad rows scan empty lists: (+inf, -1) out
            pad = nq_pad - nq
            luts = _pad_axis(luts, 0, pad, 0)
            codes = _pad_axis(codes, 1, pad, 0)
            gids = _pad_axis(gids, 1, pad, -1)
            lens = _pad_axis(lens, 1, pad, 0)
        d, i = _k.fused_scan(luts, codes, gids, lens, kk, tile_q=tile,
                             interpret=spec.use_interpret())
        return d[:, :nq], i[:, :nq]
    return _jit_ref(luts, codes, gids, lens, kk=kk)


def _pad_axis(x: jnp.ndarray, axis: int, n: int, value) -> jnp.ndarray:
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, n)
    return jnp.pad(x, widths, constant_values=value)


def probed_operands(params, stacked, queries: jnp.ndarray,
                    probe_ids: jnp.ndarray, cfg):
    """The per-(query, probed list) operands of one ``chamvs_scan`` call:
    LUTs [nq, np, m, ksub] plus every shard's probed codes
    [S, nq, np, cap, m], ids [S, nq, np, cap] and lengths [S, nq, np],
    gathered from a ``stack_shards``-packed stack."""
    from repro.core import ivfpq
    luts = ivfpq.compute_luts(params, queries, probe_ids, cfg.ivfpq)
    return (luts, stacked.codes[:, probe_ids], stacked.ids[:, probe_ids],
            stacked.list_len[:, probe_ids])


@functools.partial(jax.jit, static_argnames=("cfg", "kk", "spec"))
def fused_shard_scan(params, stacked, queries: jnp.ndarray,
                     probe_ids: jnp.ndarray, cfg, kk: int,
                     spec: Optional[registry.KernelSpec] = None
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """LUTs + gather + fused scan over a ``stack_shards``-packed shard
    stack. The candidate-preparation twin of ``chamvs.shard_search``,
    but for ALL shards at once: compute the per-(query, probe) LUTs,
    gather every shard's probed slices, and run ONE ``chamvs_scan``
    dispatch over the stack.

    params: IVFPQParams | stacked: IVFPQShard with leading [S] axis |
    queries [nq, D] | probe_ids [nq, np]
    -> (dists [S, nq, kk], global ids [S, nq, kk]).
    """
    operands = probed_operands(params, stacked, queries, probe_ids, cfg)
    return chamvs_scan(*operands, kk,
                       spec=spec if spec is not None else cfg.kernel_spec())
