"""``RetrievalService`` — ChamVS as a standalone vector-search service.

The paper's disaggregation argument (§3) is that vector search deserves
its own service tier, scaled and scheduled independently of the LM.
This module is that tier in-process:

  * an **in-flight request table**: every ``submit()`` gets a ticket and
    a ``SearchHandle`` future, so callers (the serve scheduler) issue
    queries for one wave of sequences while the previous wave is still
    decoding;
  * **deadline-based micro-batching**: pending queries from many
    concurrent sequences coalesce into *one* batched IVF-scan/PQ-ADC/
    top-k dispatch, flushed when ``max_batch`` rows accumulate, when the
    oldest query's ``deadline_s`` expires, or explicitly at the end of a
    scheduler wave (RAGO, arXiv:2503.14649, shows this cross-request
    batching dominates RAG serving throughput);
  * an **LRU result cache** on quantized query vectors — a hit skips
    the kernel entirely;
  * **per-stage stats** (queue wait / scan / merge / gather) feeding the
    Fig. 9/10-style benchmark.

The search math itself lives in ``core/chamvs.py`` (kernel frontend)
and ``retrieval/merge.py`` (K-selection); this module only batches,
caches, and accounts. ``chamvs.search_single`` is a one-shot call into
this service, so there is exactly one search implementation.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.chamvs import (ChamVSConfig, probe_lists, shard_search,
                               stack_shards)
from repro.obs.trace import NULL_TRACER
from repro.core.ivfpq import IVFPQParams, IVFPQShard
from repro.kernels.chamvs_scan.ops import fused_shard_scan
from repro.retrieval import merge as merge_lib
from repro.retrieval.cache import QueryCache
from repro.retrieval.chaos import ChaosInjector, FaultPlan, ScanHang
from repro.retrieval.replica import (EJECTED, HEALTHY, PROBATION,
                                     FailoverConfig, ReplicaGroup)
from repro.retrieval.stats import RetrievalStats


@dataclasses.dataclass(frozen=True)
class ServiceConfig:
    """Batching / caching knobs of one service instance."""
    max_batch: int = 64           # flush when this many rows are pending
    deadline_s: float = 0.0       # flush when the oldest row waited this
    #                               long (checked at submit/poll; 0 = only
    #                               max_batch or an explicit flush())
    bucket_pow2: bool = True      # pad batches to powers of two so jit
    #                               retraces O(log max_batch) shapes
    cache_entries: int = 0        # LRU result-cache entries (0 = off).
    #                               NOTE: the cache keys on host-side
    #                               query values, so enabling it syncs
    #                               each submit (and each flush, for the
    #                               insert) — it trades async overlap for
    #                               skipping whole kernel dispatches
    cache_quant: float = 1e-3     # query quantization step for cache keys
    cache_partial: bool = True    # per-row cache hits: cached rows are
    #                               served immediately and ONLY the
    #                               missed rows go to the kernel (the
    #                               flush stitches the batch back
    #                               together). False restores the old
    #                               all-or-nothing batch lookup.
    merge_fanout: Optional[int] = None  # None = flat K-selection;
    #                               >= 2 = hierarchical tree merge
    measure: bool = True          # block per stage to record scan/merge
    #                               times (off = maximum async overlap)
    kernel_backend: Optional[str] = None  # override ChamVSConfig.backend
    #                               ("ref" | "pallas") so serving configs
    #                               can select the scan path
    kernel_fused: Optional[bool] = None  # override ChamVSConfig.fused:
    #                               one fused chamvs_scan dispatch per
    #                               wave (True) vs the staged per-shard
    #                               pipeline (False, the parity oracle)
    failover: Optional[FailoverConfig] = None  # fault-tolerant dispatch:
    #                               replica groups + per-dispatch
    #                               deadlines + hedged re-dispatch +
    #                               partial results (repro.retrieval.
    #                               replica). None = the legacy direct
    #                               dispatch, bit-identical to before.
    #                               NOTE: deadline enforcement needs the
    #                               scan's real latency, so the FT layer
    #                               blocks per flush like measure=True


def next_pow2(n: int) -> int:
    """Smallest power of two >= n — the shape-bucketing unit shared by
    the query micro-batcher here and the serve KV pool's wave buckets."""
    b = 1
    while b < n:
        b *= 2
    return b


# ---------------------------------------------------------------------------
# the pipeline stages, jitted once at module level (shared across
# service instances and the `search_single` one-shot path)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("cfg", "kk"))
def _scan_stage(params: IVFPQParams, shards: Tuple[IVFPQShard, ...],
                queries: jnp.ndarray, *, cfg: ChamVSConfig, kk: int
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """STAGED scan: centroid scan + Python loop of per-shard IVF/PQ
    scans + per-shard top-kk — one chamvs dispatch per shard. Kept as
    the parity oracle for ``_scan_stage_fused``.

    Returns stacked candidates (dists [S, nq, kk], ids [S, nq, kk])."""
    probe_ids = probe_lists(params, queries, cfg)
    per = [shard_search(params, s, queries, probe_ids, cfg, kk)
           for s in shards]
    return (jnp.stack([p[0] for p in per]),
            jnp.stack([p[1] for p in per]))


@functools.partial(jax.jit, static_argnames=("cfg", "kk"))
def _scan_stage_fused(params: IVFPQParams, stacked: IVFPQShard,
                      queries: jnp.ndarray, *, cfg: ChamVSConfig, kk: int
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """FUSED scan (the serving default): centroid scan + ONE
    ``chamvs_scan`` dispatch covering ADC + streaming top-kk for every
    shard in the ``stack_shards``-packed stack — no materialized
    [B, n] distance matrix, no per-shard dispatch loop, no separate
    top-k pass. Same return contract as ``_scan_stage``."""
    probe_ids = probe_lists(params, queries, cfg)
    return fused_shard_scan(params, stacked, queries, probe_ids, cfg, kk)


@functools.partial(jax.jit, static_argnames=("k", "fanout"))
def _merge_stage(dists: jnp.ndarray, ids: jnp.ndarray, *, k: int,
                 fanout: Optional[int]
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    return merge_lib.merge_topk(dists, ids, k, fanout=fanout)


class LocalPipeline:
    """Single-process scan/merge over a list of shards.

    ``cfg.fused`` picks the scan flavor: the fused single-dispatch
    ``chamvs_scan`` over a ``stack_shards``-packed stack (default), or
    the staged per-shard loop (the parity oracle). The packed stack is
    a second copy of the code tables — it IS the fused path's physical
    layout (one contiguous [S, ...] allocation the single dispatch
    scans), priced once per service; ``chamvs.search_single`` memoizes
    its service so one-shot callers don't re-pack per call. Deployments
    that cannot afford the copy run ``fused=False``.
    """

    row_multiple = 1    # no constraint on the batched row count

    def __init__(self, params: IVFPQParams, shards: List[IVFPQShard],
                 cfg: ChamVSConfig):
        self.params = params
        self.shards = tuple(shards)
        self.stacked = stack_shards(list(shards)) if cfg.fused else None
        self.cfg = cfg
        self.kk = cfg.k_prime(len(self.shards))

    @property
    def k(self) -> int:
        return self.cfg.k

    @property
    def scan_dispatches(self) -> int:
        """ChamVS scan kernel dispatches per flush: ONE for the fused
        path regardless of shard count, one per shard when staged."""
        return 1 if self.cfg.fused else max(1, len(self.shards))

    @property
    def fault_domains(self) -> int:
        """Independent failure domains of this pipeline: each shard can
        fail on its own (candidates stay per-shard until the merge)."""
        return max(1, len(self.shards))

    def scan(self, queries: jnp.ndarray):
        if self.cfg.fused:
            return _scan_stage_fused(self.params, self.stacked, queries,
                                     cfg=self.cfg, kk=self.kk)
        return _scan_stage(self.params, self.shards, queries,
                           cfg=self.cfg, kk=self.kk)

    def merge(self, candidates, fanout: Optional[int]):
        d, i = candidates
        return _merge_stage(d, i, k=self.cfg.k, fanout=fanout)


class RouterPipeline:
    """Scan/merge over a retrieval mesh via a ``ShardRouter``. The merge
    happens in-network inside the shard_map graph, so the merge stage is
    a pass-through (its time is accounted under scan and
    ``ServiceConfig.merge_fanout`` does not apply)."""

    scan_dispatches = 1   # the whole in-graph search is one dispatch
    fault_domains = 1     # the in-graph search merges in-network, so
    #                       the whole mesh fails (or answers) as one
    #                       domain — partial results degrade to
    #                       total loss here

    def __init__(self, router, params: IVFPQParams,
                 shards: List[IVFPQShard]):
        self.router = router
        self.cfg = router.cfg
        # flushed batches must divide over the mesh's query-split columns
        self.row_multiple = router.query_size
        self.db_params = router.place_params(params)
        self.db_shard = router.place_shards(shards)

    @property
    def k(self) -> int:
        return self.cfg.k

    def scan(self, queries: jnp.ndarray):
        return self.router.search(self.db_params, self.db_shard, queries)

    def merge(self, candidates, fanout: Optional[int]):
        return candidates


# ---------------------------------------------------------------------------
# futures + the service
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class _InFlight:
    """One row-range of the in-flight request table."""
    ticket: int
    nrows: int
    submit_t: float
    result_d: Optional[jnp.ndarray] = None   # [nrows, K] once complete
    result_i: Optional[jnp.ndarray] = None
    kernel_rows: int = -1                    # rows the kernel must serve
    #                                          (< nrows on a partial
    #                                          cache hit); -1 = nrows
    stitch: Optional[Tuple[np.ndarray, np.ndarray, np.ndarray]] = None
    #                                          (dists, ids, hit mask) of
    #                                          the cached rows to merge
    #                                          with the kernel rows
    partial: bool = False                    # served from a live subset
    #                                          of the fault domains (a
    #                                          shard was down past the
    #                                          deadline): exact top-k
    #                                          over the survivors only
    live_frac: float = 1.0                   # fraction of fault domains
    #                                          that contributed


class SearchHandle:
    """Future for one submitted query batch.

    ``result()`` forces a flush if the batch is still queued, so a
    handle can always be resolved — the scheduler simply resolves late
    (after dispatching the next wave's decodes) to get overlap."""

    def __init__(self, service: "RetrievalService", entry: _InFlight):
        self._service = service
        self._entry = entry

    @property
    def ticket(self) -> int:
        return self._entry.ticket

    @property
    def partial(self) -> bool:
        """True when the result covers only the surviving fault domains
        (exact top-k over the live subset — see ``_dispatch_scan``).
        Meaningful once ``done()``; consumers use it to count quality
        impact and to skip seeding speculation with degraded results."""
        return self._entry.partial

    @property
    def live_fraction(self) -> float:
        return self._entry.live_frac

    def done(self) -> bool:
        return self._entry.result_d is not None

    def result(self) -> Tuple[jnp.ndarray, jnp.ndarray]:
        if not self.done():
            self._service.flush()
        assert self._entry.result_d is not None
        self._service._retire(self._entry)
        return self._entry.result_d, self._entry.result_i

    def cancel(self) -> None:
        """Drop the handle without consuming its result (speculation
        points discarded by a rollback or a cancelled request). A still-
        pending batch is computed and thrown away at the next flush —
        abandoned results must not wedge the in-flight table."""
        self._service._retire(self._entry)


class RetrievalService:
    """Deadline-batched, cached, instrumented front door to ChamVS."""

    def __init__(self, pipeline, config: Optional[ServiceConfig] = None):
        self.pipeline = pipeline
        self.config = config or ServiceConfig()
        self.stats = RetrievalStats()
        self.tracer = NULL_TRACER   # engine.set_tracer swaps a live one in
        self.cache: Optional[QueryCache] = (
            QueryCache(self.config.cache_entries,
                       quant=self.config.cache_quant,
                       partial=self.config.cache_partial)
            if self.config.cache_entries > 0 else None)
        self._inflight: Dict[int, _InFlight] = {}
        self._pending: List[Tuple[_InFlight, jnp.ndarray]] = []
        self._pending_rows = 0
        self._next_ticket = 0
        # -- fault tolerance (replica failover / deadlines / chaos) ----
        self.replicas: Optional[ReplicaGroup] = None
        self.chaos: Optional[ChaosInjector] = None
        self._degraded_partial = False    # degrade-ladder rung: serve
        #                                   the live subset immediately,
        #                                   no hedging or retries
        if self.config.failover is not None:
            self.replicas = ReplicaGroup(
                getattr(pipeline, "fault_domains", 1),
                self.config.failover,
                on_transition=self._on_replica_transition)

    # -- fault tolerance ----------------------------------------------------

    def _on_replica_transition(self, shard: int, replica: int,
                               old: str, new: str) -> None:
        if new == EJECTED:
            self.stats.ft_ejections += 1
            if self.tracer.enabled:
                self.tracer.instant("retrieval.eject", "retrieval",
                                    args={"shard": shard,
                                          "replica": replica, "from": old})
        elif old == PROBATION and new == HEALTHY:
            self.stats.ft_recoveries += 1
            if self.tracer.enabled:
                self.tracer.instant("retrieval.recover", "retrieval",
                                    args={"shard": shard,
                                          "replica": replica})

    def install_chaos(self, plan) -> ChaosInjector:
        """Arm a ``FaultPlan`` (or a path to its JSON) at this service's
        scan boundary. Chaos requires the fault-tolerant dispatch loop,
        so a replica group is created on demand (single-replica: every
        fault beyond retries degrades to partial results)."""
        if isinstance(plan, str):
            plan = FaultPlan.load(plan)
        if isinstance(plan, FaultPlan):
            injector = ChaosInjector(plan)
        else:
            injector = plan
        if self.replicas is None:
            self.replicas = ReplicaGroup(
                getattr(self.pipeline, "fault_domains", 1),
                FailoverConfig(replicas=1),
                on_transition=self._on_replica_transition)
        self.chaos = injector
        return injector

    def set_degraded_partial(self, flag: bool) -> None:
        """Degrade-ladder hook ("partial-retrieval" rung): when set, the
        dispatch loop gives every domain ONE attempt and serves whatever
        subset answered — shedding hedges, retries, and tail waits. A
        no-op unless the fault-tolerant layer is active."""
        self._degraded_partial = bool(flag)

    # -- constructors -------------------------------------------------------

    @classmethod
    def local(cls, params: IVFPQParams, shards: List[IVFPQShard],
              cfg: ChamVSConfig, config: Optional[ServiceConfig] = None
              ) -> "RetrievalService":
        """Single-process service (tests, builds, monolithic serving).
        ``ServiceConfig.kernel_backend`` / ``kernel_fused`` override the
        corresponding ``ChamVSConfig`` fields, so a deployment config
        can select the scan path without rebuilding the search config by
        hand."""
        if config is not None:
            cfg = cfg.with_kernel(config.kernel_backend,
                                  config.kernel_fused)
        return cls(LocalPipeline(params, shards, cfg), config=config)

    @classmethod
    def distributed(cls, router, params: IVFPQParams,
                    shards: List[IVFPQShard],
                    config: Optional[ServiceConfig] = None
                    ) -> "RetrievalService":
        """Service over a retrieval mesh (one memory node per device).
        The kernel config is baked into the router at construction, so
        ``ServiceConfig`` kernel overrides cannot apply here — reject
        them loudly rather than silently serving ref-scan numbers."""
        if config is not None and (config.kernel_backend is not None or
                                   config.kernel_fused is not None):
            raise ValueError(
                "ServiceConfig.kernel_backend/kernel_fused cannot "
                "override a distributed pipeline — "
                "the ShardRouter owns its ChamVSConfig; build the router "
                "with cfg.with_kernel(...) instead")
        return cls(RouterPipeline(router, params, shards), config=config)

    # -- the in-flight request table ---------------------------------------

    @property
    def num_inflight(self) -> int:
        return len(self._inflight)

    @property
    def num_pending_rows(self) -> int:
        return self._pending_rows

    def _retire(self, entry: _InFlight) -> None:
        self._inflight.pop(entry.ticket, None)

    # -- submission ---------------------------------------------------------

    def submit(self, queries: jnp.ndarray) -> SearchHandle:
        """Enqueue a [B, d] query batch; returns a future.

        A full-batch cache hit completes the handle immediately (no
        kernel). Otherwise the rows join the pending micro-batch, which
        flushes on ``max_batch`` / ``deadline_s`` / ``flush()``."""
        q = jnp.asarray(queries, jnp.float32)
        if q.ndim != 2:
            raise ValueError(f"queries must be [B, d], got {q.shape}")
        now = time.perf_counter()
        entry = _InFlight(ticket=self._next_ticket, nrows=q.shape[0],
                          submit_t=now)
        self._next_ticket += 1
        self._inflight[entry.ticket] = entry
        self.stats.record_submit(entry.nrows)

        q_kernel = q
        if self.cache is not None:
            stale0 = self.cache.stale
            hit = self.cache.get_batch(np.asarray(q))
            self.stats.cache_stale += self.cache.stale - stale0
            if hit is not None and len(hit) == 2:
                # all-or-nothing full hit (either cache mode)
                entry.result_d = jnp.asarray(hit[0])
                entry.result_i = jnp.asarray(hit[1])
                self.stats.cache_hits += entry.nrows
                self.stats.queue_wait.add(0.0)
                return SearchHandle(self, entry)
            if hit is not None:
                # partial per-row hit: serve the cached rows now, send
                # ONLY the missed rows to the kernel; flush stitches
                dists, ids, mask = hit
                nhit = int(mask.sum())
                if nhit == entry.nrows:
                    entry.result_d = jnp.asarray(dists)
                    entry.result_i = jnp.asarray(ids)
                    self.stats.cache_hits += entry.nrows
                    self.stats.queue_wait.add(0.0)
                    return SearchHandle(self, entry)
                entry.stitch = (dists, ids, mask)
                entry.kernel_rows = entry.nrows - nhit
                q_kernel = q[jnp.asarray(np.flatnonzero(~mask))]
                self.stats.cache_hits += nhit
                self.stats.cache_misses += entry.kernel_rows
            else:
                self.stats.cache_misses += entry.nrows
        if entry.kernel_rows < 0:
            entry.kernel_rows = entry.nrows

        self._pending.append((entry, q_kernel))
        self._pending_rows += entry.kernel_rows
        if self._pending_rows >= self.config.max_batch:
            self.flush()
        else:
            self.poll(now)
        return SearchHandle(self, entry)

    def poll(self, now: Optional[float] = None) -> None:
        """Deadline check: flush if the oldest pending row has waited
        longer than ``deadline_s``. Call from any serving loop tick."""
        if not self._pending or self.config.deadline_s <= 0.0:
            return
        now = time.perf_counter() if now is None else now
        if now - self._pending[0][0].submit_t >= self.config.deadline_s:
            self.flush()

    # -- the batched dispatch ----------------------------------------------

    def _bucket(self, n: int) -> int:
        b = next_pow2(n) if self.config.bucket_pow2 else n
        # distributed pipelines query-split over the TP columns, which
        # requires the row count to divide evenly
        mult = getattr(self.pipeline, "row_multiple", 1)
        if b % mult:
            b += mult - b % mult
        return b

    def _dispatch_scan(self, batch: jnp.ndarray
                       ) -> Tuple[Optional[Tuple[jnp.ndarray, jnp.ndarray]],
                                  Optional[np.ndarray]]:
        """Fault-tolerant scan dispatch. Returns ``(candidates, live)``:
        ``live`` is ``None`` when the FT layer is inactive (the legacy
        direct dispatch, bit-identical to before), else a bool [S] over
        the pipeline's fault domains — False domains get masked to the
        padding sentinel before the merge (partial results).

        The loop is a synchronous, deterministic model of hedged
        dispatch: per round, every unresolved domain is assigned a
        replica via the health-aware ``ReplicaGroup.pick``; the chaos
        injector (if armed) decides the replica's fate. A hang costs the
        quantile-based hedge delay, then re-dispatches to the next
        replica (a *hedge*); a transient error retries with backoff up
        to ``max_retries`` before failing over; a crash fails over
        immediately and ejects. In-process all replicas answer from the
        same arrays, so the physical scan runs ONCE and a failover
        re-serves bit-identical candidates — the control plane (who is
        asked, when we give up, what latency is accounted and, under
        ``FaultPlan.realtime``, slept) is what is modeled. Domains
        still unresolved when the deadline is spent, or with every
        replica ejected, are reported dead in ``live``."""
        group = self.replicas
        if group is None:
            return self.pipeline.scan(batch), None
        cfg = group.cfg
        clock = group.clock
        realtime = self.chaos is not None and self.chaos.plan.realtime
        S = group.num_shards
        flush_idx = self.stats.num_batches
        stats = self.stats
        tr = self.tracer
        live = np.zeros(S, dtype=bool)
        candidates = None
        scan_s = 0.0
        spent = 0.0                     # modeled elapsed across rounds
        pending = set(range(S))
        tried: List[set] = [set() for _ in range(S)]
        retries = [0] * S
        attempts = [0] * S
        t_wall = clock()
        # bounded by construction, belt-and-braces against plan bugs
        guard = S * cfg.replicas * (cfg.max_retries + 2) + 4
        while pending and guard > 0:
            guard -= 1
            assign = [(s, group.pick(s, exclude=tried[s]))
                      for s in sorted(pending)]
            assign = [(s, r) for s, r in assign if r is not None]
            for s in pending - {s for s, _ in assign}:
                tried[s] = set(range(cfg.replicas))   # no target: dead
            pending = {s for s, _ in assign}
            if not assign:
                break
            if candidates is None:
                t0 = clock()
                candidates = self.pipeline.scan(batch)
                jax.block_until_ready(candidates)
                scan_s = clock() - t0
            hedge = group.hedge_delay_s()
            round_cost = 0.0
            for s, rid in assign:
                attempts[s] += 1
                fault = (self.chaos.outcome(flush_idx, s, rid,
                                            attempts[s])
                         if self.chaos is not None else None)
                kind = fault.kind if fault is not None else None
                if kind is None or kind == "slow":
                    lat = scan_s + (fault.slow_s if fault else 0.0)
                    if realtime and fault is not None:
                        group.sleep(min(fault.slow_s, cfg.sleep_cap_s))
                    late = (cfg.dispatch_deadline_s > 0.0 and
                            spent + lat > cfg.dispatch_deadline_s)
                    group.report(s, rid, "slow" if late else "ok",
                                 latency_s=lat)
                    if late:
                        stats.ft_timeouts += 1   # late success: result
                        #                          used, replica charged
                    live[s] = True
                    pending.discard(s)
                elif kind == "hang":
                    lat = hedge
                    stats.ft_timeouts += 1
                    stats.ft_hedges += 1
                    group.report(s, rid, "timeout")
                    tried[s].add(rid)
                    if tr.enabled:
                        tr.instant("retrieval.hedge", "retrieval",
                                   args={"shard": s, "replica": rid,
                                         "delay_us": hedge * 1e6})
                    if realtime:
                        group.sleep(min(hedge, cfg.sleep_cap_s))
                elif kind == "error":
                    lat = cfg.backoff_s * (2 ** retries[s])
                    stats.ft_retries += 1
                    group.report(s, rid, "error")
                    retries[s] += 1
                    if retries[s] > cfg.max_retries:
                        tried[s].add(rid)
                        retries[s] = 0
                    if realtime and lat > 0:
                        group.sleep(min(lat, cfg.sleep_cap_s))
                else:  # crash: fail fast, eject, fail over
                    lat = 0.0
                    stats.ft_crashes += 1
                    group.report(s, rid, "crash")
                    tried[s].add(rid)
                round_cost = max(round_cost, lat)
            spent += round_cost
            if self._degraded_partial:
                break   # partial-retrieval rung: one attempt per domain
            if cfg.dispatch_deadline_s > 0.0 and \
                    spent >= cfg.dispatch_deadline_s:
                break   # deadline spent: survivors only
        stats.ft_dispatch.add(clock() - t_wall)
        if not live.all() and not cfg.allow_partial:
            dead = [int(s) for s in np.flatnonzero(~live)]
            raise ScanHang(
                f"fault domains {dead} unresolved past the deadline and "
                "ServiceConfig.failover.allow_partial is False")
        return candidates, live

    def _fail_pending(self, pending: List[Tuple[_InFlight, jnp.ndarray]]
                      ) -> None:
        """A flush that raises must still complete its entries: fill the
        missing-neighbor sentinel (``knnlm_interpolate`` degrades to the
        bare LM distribution on it) and flag them partial, so handles
        stay resolvable and the in-flight table cannot wedge — callers
        that swallow the exception still drain cleanly."""
        k = self.pipeline.k
        for entry, _ in pending:
            if entry.result_d is None:
                entry.result_d = jnp.full((entry.nrows, k), jnp.inf,
                                          jnp.float32)
                entry.result_i = jnp.full((entry.nrows, k), -1, jnp.int32)
                entry.partial = True
                entry.live_frac = 0.0

    def flush(self) -> None:
        """Coalesce every pending row into one scan+merge dispatch and
        complete the corresponding in-flight entries."""
        if not self._pending:
            return
        pending, self._pending = self._pending, []
        nrows, self._pending_rows = self._pending_rows, 0
        try:
            self._flush_batch(pending, nrows)
        except Exception:
            self._fail_pending(pending)
            raise

    def _flush_batch(self, pending: List[Tuple[_InFlight, jnp.ndarray]],
                     nrows: int) -> None:

        batch = (pending[0][1] if len(pending) == 1
                 else jnp.concatenate([q for _, q in pending], axis=0))
        pad = self._bucket(nrows) - nrows
        if pad:
            batch = jnp.pad(batch, ((0, pad), (0, 0)))

        measure = self.config.measure
        tr = self.tracer
        t0 = time.perf_counter()
        for entry, _ in pending:   # queue wait ends when the batch launches
            self.stats.queue_wait.add(t0 - entry.submit_t)
        if tr.enabled:
            # retroactive span: the wait started when the OLDEST pending
            # row was submitted, which predates this call site
            oldest = pending[0][0].submit_t
            tr.complete("retrieval.queue_wait", "retrieval", oldest,
                        t0 - oldest, args={"rows": nrows,
                                           "entries": len(pending)})
        # NOTE: with measure=False the scan/merge spans time only the
        # async dispatch (jax returns before the kernel finishes); the
        # kernels' device time is in the profiler trace, under their
        # names (chamvs_scan, ivf_scan). measure=True blocks per stage to
        # make the spans stage times (the fault-tolerant dispatch always
        # blocks: deadline/hedge decisions need the scan's real latency)
        with tr.span("retrieval.scan", "retrieval",
                     args={"rows": nrows} if tr.active else None):
            candidates, live = self._dispatch_scan(batch)
            if measure and candidates is not None:
                jax.block_until_ready(candidates)
        t1 = time.perf_counter()
        partial = live is not None and not bool(live.all())
        live_frac = float(live.mean()) if live is not None else 1.0
        with tr.span("retrieval.merge", "retrieval"):
            if not partial:
                dists, ids = self.pipeline.merge(candidates,
                                                 self.config.merge_fanout)
            elif candidates is not None and bool(live.any()) and \
                    candidates[0].ndim == 3 and \
                    candidates[0].shape[0] == live.shape[0]:
                # per-shard candidate lists: mask the dead producers to
                # the (+inf, -1) padding sentinel, then the ordinary
                # K-selection IS the exact top-k over the live subset
                md, mi = merge_lib.mask_producers(
                    candidates[0], candidates[1], jnp.asarray(live))
                dists, ids = self.pipeline.merge(
                    (md, mi), self.config.merge_fanout)
            else:
                # total loss (or an in-graph-merged pipeline whose one
                # domain died): every row gets the missing-neighbor
                # sentinel; knnlm_interpolate degrades to the bare LM
                # distribution on it, so requests complete un-augmented
                n, k = batch.shape[0], self.pipeline.k
                dists = jnp.full((n, k), jnp.inf, jnp.float32)
                ids = jnp.full((n, k), -1, jnp.int32)
            if measure:
                jax.block_until_ready((dists, ids))
        if measure:
            self.stats.scan.add(t1 - t0)
            self.stats.merge.add(time.perf_counter() - t1)
        self.stats.record_batch(
            nrows, dispatches=getattr(self.pipeline, "scan_dispatches", 1))
        if partial:
            self.stats.ft_partial_flushes += 1
            self.stats.ft_partial_rows += nrows
            if tr.enabled:
                tr.instant("retrieval.partial", "retrieval",
                           args={"rows": nrows,
                                 "live": int(live.sum()),
                                 "domains": int(live.shape[0])})

        offset = 0
        for entry, q in pending:
            entry.partial = partial
            entry.live_frac = live_frac
            kd = dists[offset:offset + entry.kernel_rows]
            ki = ids[offset:offset + entry.kernel_rows]
            if self.cache is not None and not partial:
                # partial results never enter the cache: they would
                # outlive the fault and silently serve degraded
                # neighbors at full-quality lookups
                self.cache.put_batch(np.asarray(q), np.asarray(kd),
                                     np.asarray(ki))
            if entry.stitch is not None:
                # merge the cached rows with the kernel rows back into
                # submit order (host-side: the cached half already lives
                # on the host, and the cache insert above synced anyway)
                cd, ci, mask = entry.stitch
                full_d = np.array(cd)
                full_i = np.array(ci)
                miss = np.flatnonzero(~mask)
                full_d[miss] = np.asarray(kd)
                full_i[miss] = np.asarray(ki)
                entry.result_d = jnp.asarray(full_d)
                entry.result_i = jnp.asarray(full_i)
            else:
                entry.result_d, entry.result_i = kd, ki
            offset += entry.kernel_rows

    # -- speculation support ------------------------------------------------

    def stale_lookup(self, queries: jnp.ndarray
                     ) -> Optional[Tuple[jnp.ndarray, jnp.ndarray]]:
        """Any-generation cache lookup feeding speculative decode: the
        caller continues on these possibly-stale neighbors while the
        real search runs, so freshness is a quality hint, not a
        correctness requirement. None when any row is absent (or the
        cache is off)."""
        if self.cache is None:
            return None
        hit = self.cache.get_stale(np.asarray(queries, np.float32))
        if hit is None:
            return None
        return jnp.asarray(hit[0]), jnp.asarray(hit[1])

    def mark_cache_stale(self) -> None:
        """Generation-bump the result cache (quality knob changed):
        entries stop serving fresh lookups but remain speculation
        seeds. No-op without a cache."""
        if self.cache is not None:
            self.cache.mark_stale()

    # -- synchronous convenience -------------------------------------------

    def search(self, queries: jnp.ndarray
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Blocking search: submit + flush + result (the legacy
        ``chamvs.search_single`` surface)."""
        return self.submit(queries).result()
