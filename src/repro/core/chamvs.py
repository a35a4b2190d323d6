"""ChamVS kernel frontend (paper §4): config + the per-shard scan.

This module is the *kernel* side of the search engine — one memory
node's LUT construction -> list streaming -> ADC -> truncated top-k',
with pluggable backends routed through ``repro.kernels.registry``
(``ChamVSConfig.kernel_spec()`` is the ``KernelSpec`` everything below
here runs with):

  ``backend="ref"``    — pure-jnp gather ADC (paper's CPU flavor; the
                          default on a CPU host).
  ``backend="pallas"`` — the near-memory Pallas kernels (the default on an
                          accelerator; interpreted on a CPU host).

``shard_search`` below is the *staged* per-shard pipeline — kept as the
parity oracle for the fused path. The serving default
(``ChamVSConfig.fused=True``) runs ``kernels/chamvs_scan`` instead: ONE
dispatch covering ADC + streaming top-k' for every shard of a retrieval
wave (see ``retrieval/service._scan_stage_fused``).

Everything *above* the kernel now lives in ``repro.retrieval``:

  * batching, futures, caching, stats  -> ``retrieval.service``
    (``search_single`` below is a one-shot call into it — there is
    exactly one search implementation);
  * hierarchical K-selection merge     -> ``retrieval.merge``;
  * mesh placement + broadcast/gather  -> ``retrieval.router``
    (``make_distributed_search`` / ``make_distributed_gather`` remain
    as deprecated wrappers).
"""
from __future__ import annotations

import collections
import dataclasses
import warnings
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.core import ivfpq
from repro.core.approx_topk_math import truncated_queue_len
from repro.core.ivfpq import IVFPQConfig, IVFPQParams, IVFPQShard
from repro.kernels.registry import KernelSpec, serving_spec


@dataclasses.dataclass(frozen=True)
class ChamVSConfig:
    """Serve-time configuration of the search engine."""

    ivfpq: IVFPQConfig
    nprobe: int = 32
    k: int = 100
    eps: float = 0.01             # approx-queue failure budget (paper: 1%)
    backend: Optional[str] = None  # "ref" | "pallas"; None = the
    #                               platform's serving backend (Pallas
    #                               compiled on an accelerator, ref on CPU)
    num_l1_blocks: int = 16       # producers per shard for the approx queue
    fused: bool = True            # ONE fused chamvs_scan dispatch over all
    #                               shards per wave; False keeps the staged
    #                               per-shard pipeline (the parity oracle)

    def kernel_spec(self) -> KernelSpec:
        """The registry ``KernelSpec`` this config routes kernels with —
        the single place ``backend`` is interpreted. Interpret mode and
        the fallback policy come from the platform (see
        ``registry.serving_spec``)."""
        return serving_spec(self.backend)

    def with_kernel(self, backend: Optional[str] = None,
                    fused: Optional[bool] = None) -> "ChamVSConfig":
        """Return a copy with the kernel selection overridden (``None``
        keeps the current value) — the one place the EngineConfig /
        ServiceConfig ``kernel_backend`` / ``kernel_fused`` knobs are
        folded in."""
        if backend is None and fused is None:
            return self
        return dataclasses.replace(
            self,
            backend=backend if backend is not None else self.backend,
            fused=fused if fused is not None else self.fused)

    def k_prime(self, num_shards: int) -> int:
        """Truncated per-shard queue length (paper §4.2.2): the shards are the
        level-one producers of the global top-K, so each only ships k' << K
        candidates over the network. Note k' > K/num_shards always holds, so
        the merge can always fill K slots."""
        return min(self.k, truncated_queue_len(self.k, max(1, num_shards),
                                               self.eps))


def probe_lists(params: IVFPQParams, queries: jnp.ndarray,
                cfg: ChamVSConfig) -> jnp.ndarray:
    """ChamVS.idx: the nprobe closest IVF lists per query [nq, nprobe].
    Shared by the fused, staged and mesh-routed scans (their parity
    requires identical probes), routed through the registry frontend
    when the config runs the Pallas kernels."""
    spec = cfg.kernel_spec()
    if spec.backend == "pallas":
        from repro.kernels.ivf_scan.ops import ivf_index_scan
        _, probe_ids = ivf_index_scan(queries, params.coarse_centroids,
                                      cfg.nprobe, spec=spec)
    else:
        _, probe_ids = ivfpq.scan_ivf_index(params, queries, cfg.nprobe)
    return probe_ids


# ---------------------------------------------------------------------------
# per-shard search (runs inside shard_map; also usable standalone).
# This is the STAGED path — the fused single-dispatch twin is
# kernels/chamvs_scan.ops.fused_shard_scan; the two must stay
# result-identical (tests/test_chamvs_scan.py property test).
# ---------------------------------------------------------------------------

def shard_search(params: IVFPQParams, shard: IVFPQShard, queries: jnp.ndarray,
                 probe_ids: jnp.ndarray, cfg: ChamVSConfig, kk: int
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One memory node's work: LUTs -> stream probed lists -> ADC -> top-kk.

    Returns (dists [nq, kk], global_ids [nq, kk])."""
    icfg = cfg.ivfpq
    nq, nprobe = probe_ids.shape
    luts = ivfpq.compute_luts(params, queries, probe_ids, icfg)  # [nq,np,m,ksub]
    codes = shard.codes[probe_ids]                               # [nq,np,cap,m]
    ids = shard.ids[probe_ids]                                   # [nq,np,cap]
    lens = shard.list_len[probe_ids]                             # [nq,np]

    spec = cfg.kernel_spec()
    if spec.backend == "pallas":
        from repro.kernels.pq_adc.ops import pq_adc_topk
        B = nq * nprobe
        d_l, i_l = pq_adc_topk(
            luts.reshape(B, icfg.m, icfg.ksub),
            codes.reshape(B, icfg.list_cap, icfg.m),
            lens.reshape(B),
            k=min(kk, icfg.list_cap),
            spec=spec)
        # local row idx -> global vector id via the per-list id table
        gid = jnp.take_along_axis(
            ids.reshape(B, icfg.list_cap),
            jnp.maximum(i_l, 0), axis=1)
        gid = jnp.where(i_l < 0, -1, gid)
        kcap = d_l.shape[-1]
        d = d_l.reshape(nq, nprobe * kcap)
        g = gid.reshape(nq, nprobe * kcap)
    else:
        valid = (jnp.arange(icfg.list_cap)[None, None, :] < lens[..., None])
        d3 = ivfpq.adc_scan_ref(luts, codes)                     # [nq,np,cap]
        d3 = jnp.where(valid, d3, jnp.inf)
        d = d3.reshape(nq, -1)
        g = ids.reshape(nq, -1)

    neg, pos = jax.lax.top_k(-d, min(kk, d.shape[-1]))
    out_d = -neg
    out_i = jnp.take_along_axis(g, pos, axis=1)
    out_i = jnp.where(jnp.isinf(out_d), -1, out_i)
    if out_d.shape[-1] < kk:  # fewer candidates than kk: pad
        pad = kk - out_d.shape[-1]
        out_d = jnp.pad(out_d, ((0, 0), (0, pad)), constant_values=jnp.inf)
        out_i = jnp.pad(out_i, ((0, 0), (0, pad)), constant_values=-1)
    return out_d, out_i


def stack_shards(shards: list[IVFPQShard]) -> IVFPQShard:
    """[S] shards -> one IVFPQShard with a leading shard axis (to be placed
    with a sharded ``jax.device_put`` along the db axes)."""
    return IVFPQShard(
        codes=jnp.stack([s.codes for s in shards]),
        ids=jnp.stack([s.ids for s in shards]),
        list_len=jnp.stack([s.list_len for s in shards]),
    )


# LRU memo of the last few (params, shards, cfg) -> RetrievalService. A
# fresh service per call would re-pack the whole database with
# ``stack_shards`` every time (the fused path's one-dispatch layout) —
# fine once per deployment, pathological per search. Keyed on the jax
# buffer identities: the cached service holds references to those exact
# buffers, so a live key can never alias a different index. The memo
# deliberately pins up to ``_SERVICE_MEMO_CAP`` indexes (including their
# packed fused stacks) in device memory; long-lived processes juggling
# many indexes should hold their own ``RetrievalService`` instead, or
# ``_SERVICE_MEMO.clear()`` to release them.
_SERVICE_MEMO: "collections.OrderedDict" = collections.OrderedDict()
_SERVICE_MEMO_CAP = 4


def search_single(params: IVFPQParams, shards: list[IVFPQShard],
                  queries: jnp.ndarray, cfg: ChamVSConfig
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Single-process search over a list of shards (tests, builds).

    Now a one-shot ``RetrievalService`` call, so the legacy path and the
    serving path share one implementation (the service is memoized per
    (index, config) and its jitted stages are module-level, so repeated
    calls neither re-pack the shard stack nor re-trace). ``measure`` and
    ``bucket_pow2`` are off: a bare function call should not block the
    dispatch stream for stage timings, and a one-shot batch gains
    nothing from shape bucketing (it would only scan padded rows)."""
    from repro.retrieval.service import RetrievalService, ServiceConfig
    key = (tuple(id(leaf) for s in shards for leaf in s),
           id(params.coarse_centroids), id(params.codebooks), cfg)
    svc = _SERVICE_MEMO.get(key)
    if svc is None:
        svc = RetrievalService.local(params, shards, cfg,
                                     ServiceConfig(measure=False,
                                                   bucket_pow2=False))
        while len(_SERVICE_MEMO) >= _SERVICE_MEMO_CAP:
            _SERVICE_MEMO.popitem(last=False)    # evict least-recent
        _SERVICE_MEMO[key] = svc
    else:
        _SERVICE_MEMO.move_to_end(key)           # LRU refresh on hit
    return svc.search(queries)


# ---------------------------------------------------------------------------
# deprecated wrappers (moved to repro.retrieval.router)
# ---------------------------------------------------------------------------

def make_distributed_search(
    mesh: Mesh,
    cfg: ChamVSConfig,
    db_axes: Tuple[str, ...] = ("data",),
    query_axis: Optional[str] = "model",
    nq: Optional[int] = None,
):
    """Deprecated: use ``repro.retrieval.router.build_search`` (or a
    ``ShardRouter``, which also owns placement)."""
    warnings.warn(
        "chamvs.make_distributed_search moved to "
        "repro.retrieval.router.build_search", DeprecationWarning,
        stacklevel=2)
    from repro.retrieval.router import build_search
    return build_search(mesh, cfg, db_axes=db_axes, query_axis=query_axis,
                        nq=nq)


def make_distributed_gather(mesh: Mesh, table_axes: Tuple[str, ...]):
    """Deprecated: use ``repro.retrieval.router.build_gather`` (or a
    ``ShardRouter``)."""
    warnings.warn(
        "chamvs.make_distributed_gather moved to "
        "repro.retrieval.router.build_gather", DeprecationWarning,
        stacklevel=2)
    from repro.retrieval.router import build_gather
    return build_gather(mesh, table_axes)
