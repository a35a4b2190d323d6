"""The unified RALM serving surface (request/response types + the
``Retriever`` protocol).

Chameleon's system claim (paper §3) is that LM inference and vector
search are independent services behind a narrow boundary. This module is
that boundary as an API:

  * ``RalmRequest`` / ``RalmResponse`` — one generation request (a batch
    of prompts decoded in lockstep) and its result;
  * ``EngineConfig`` — everything needed to stand an engine up;
  * ``Retriever`` — the two-method protocol every retrieval service
    implements: ``search(queries) -> (dists, ids)`` (paper steps 1-8) and
    ``resolve(ids) -> payload`` (paper step 9, the vector-ID -> payload
    conversion, with missing-id masking folded in so no caller ever
    re-implements it);
  * ``LocalRetriever`` — single-process ChamVS (tests, examples, builds);
  * ``DistributedRetriever`` — ChamVS routed over a retrieval mesh (the
    paper's disaggregated memory nodes) via ``retrieval.ShardRouter``,
    including the sharded payload gather;
  * ``AsyncRetriever`` — the service-backed implementation: queries go
    through a ``repro.retrieval.RetrievalService``, so concurrent
    sequences' searches coalesce into one batched kernel dispatch and
    ``search_async`` returns a ``SearchHandle`` the scheduler can hold
    while decoding the next wave.

Everything in ``repro.serve`` speaks only this protocol; monolithic and
disaggregated deployments differ solely in which implementation is
plugged in.
"""
from __future__ import annotations

import dataclasses
import time
from typing import (Callable, List, Optional, Protocol, Tuple,
                    runtime_checkable)

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.core import chamvs as chamvs_lib
from repro.core import rag as rag_lib
from repro.core.chamvs import ChamVSConfig
from repro.core.ivfpq import IVFPQParams, IVFPQShard
from repro.core.rag import RagConfig
from repro.models.config import ModelConfig
from repro.retrieval.router import ShardRouter
from repro.retrieval.service import RetrievalService, SearchHandle


# ---------------------------------------------------------------------------
# request / response / config
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class RequestTiming:
    """Wall-clock milestones of one request's serving lifetime, stamped
    by the scheduler/engine as the request moves through the system
    (``time.perf_counter()`` seconds — deltas are meaningful, absolutes
    are not):

      * ``arrival``     — entered the system (``submit()``, or earlier:
        the HTTP gateway stamps it at request parse, before admission
        control, so queueing under backpressure is visible);
      * ``admit``       — claimed KV slots + prefilled (``engine.start``);
      * ``first_token`` — first generated token materialized on the host
        (TTFT = first_token - arrival);
      * ``finish``      — final token emitted (TPOT = (finish -
        first_token) / (steps - 1) for steps > 1).
    """
    arrival: Optional[float] = None
    admit: Optional[float] = None
    first_token: Optional[float] = None
    finish: Optional[float] = None

    def ttft_s(self) -> Optional[float]:
        if self.arrival is None or self.first_token is None:
            return None
        return self.first_token - self.arrival

    def tpot_s(self, steps: int) -> Optional[float]:
        if self.first_token is None or self.finish is None or steps < 2:
            return None
        return (self.finish - self.first_token) / (steps - 1)


@dataclasses.dataclass
class RalmRequest:
    """One serving request: a prompt batch decoded in lockstep.

    ``trace``: optional list collecting per-step dicts (retrieved ids
    etc.) for benchmarks and tests, same contract as the old
    ``generate(..., trace=)``.

    ``tenant`` names the submitting client class for per-tenant
    admission accounting (quotas, fair dequeue, queue-depth stats) —
    purely an accounting label, it never changes the math.

    ``on_token`` is the streaming hook: called as ``on_token(step,
    tokens)`` with the host-materialized ``[B]`` int array of the
    step's sampled tokens, from the thread running the scheduler, the
    moment the step's wave completes. Setting it costs one host sync
    per wave (the tokens must leave the device), so leave it ``None``
    for throughput-only workloads.

    ``cancelled`` aborts the request at the next scheduler step (slots
    are released, the response is flagged); flip it via
    ``RalmScheduler.cancel`` — e.g. the gateway on a mid-stream client
    disconnect."""
    prompt: jnp.ndarray                  # [B, T0] int32
    steps: int
    greedy: bool = True
    rng: Optional[jax.Array] = None
    trace: Optional[list] = None
    request_id: Optional[int] = None     # assigned at submit()
    trace_id: Optional[int] = None       # observability flow id: defaults
    #                                      to request_id at submit(); links
    #                                      this request's spans/flow events
    #                                      across tracks in the trace
    tenant: str = "default"
    on_token: Optional[Callable[[int, np.ndarray], None]] = None
    cancelled: bool = False
    times: RequestTiming = dataclasses.field(default_factory=RequestTiming)
    partial_steps: int = 0               # decode steps served from a
    #                                      partial (live-subset) retrieval
    #                                      result — the per-request quality
    #                                      accounting of fault degradation


@dataclasses.dataclass
class RalmResponse:
    request_id: int
    tokens: np.ndarray                   # [B, T0 + steps]
    steps: int
    trace: Optional[list] = None
    tenant: str = "default"
    cancelled: bool = False
    times: Optional[RequestTiming] = None
    partial_steps: int = 0               # steps decoded on partial
    #                                      retrieval results (0 = full
    #                                      quality throughout)


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Deployment shape of one RALM engine (the Fig. 13 knobs)."""
    model: ModelConfig
    rag: RagConfig
    max_seq: Optional[int] = None        # KV budget; default T0 + steps
    disaggregate: bool = False           # split devices into two pools
    lm_devices: int = 1                  # LM pool size (disaggregated)
    ret_devices: int = 1                 # retrieval pool size (")
    max_active: Optional[int] = None     # scheduler admission limit
    async_retrieval: bool = False        # route search through a
    #                                      RetrievalService (AsyncRetriever)
    retrieval_cache: int = 0             # service LRU cache entries (0=off)
    speculate_k: int = 0                 # speculative retrieval depth: max
    #                                      speculation points a sequence
    #                                      keeps outstanding (0 = off). A
    #                                      due row decodes ahead on its
    #                                      previous (stale) neighbors
    #                                      while the real search runs
    #                                      async; verification happens
    #                                      speculate_k waves later, off
    #                                      the critical path. Requires
    #                                      async_retrieval + wave_decode.
    speculate_verify: bool = True        # verify speculated tokens against
    #                                      the real neighbors and roll
    #                                      back on mismatch (greedy
    #                                      parity with speculation off).
    #                                      False trusts stale neighbors
    #                                      outright — bounded quality
    #                                      drift for zero rollback cost
    retrieval_measure: bool = True       # per-stage service timings; False
    #                                      drops the per-flush host blocks
    #                                      for maximum decode/search overlap
    wave_decode: bool = True             # one LM dispatch per wave over a
    #                                      slotted KVCachePool; False keeps
    #                                      the per-sequence oracle loop
    kv_slots: Optional[int] = None       # KV pool capacity in prompt rows;
    #                                      None = grow on demand, fixed
    #                                      values defer admission until
    #                                      completions free slots
    kernel_backend: Optional[str] = None  # override ChamVSConfig.backend
    #                                      ("ref" | "pallas"); None = the
    #                                      platform's serving backend
    #                                      (compiled Pallas on an
    #                                      accelerator, ref on CPU)
    kernel_fused: Optional[bool] = None  # override ChamVSConfig.fused:
    #                                      ONE chamvs_scan dispatch per
    #                                      retrieval wave (True) vs the
    #                                      staged per-shard oracle (False)
    attn_backend: Optional[str] = None   # wave decode-attention kernel:
    #                                      None = the platform's serving
    #                                      backend ("pallas", the
    #                                      streaming decode_attn kernel,
    #                                      on an accelerator; "ref", the
    #                                      grouped einsum, on CPU);
    #                                      "einsum" = the legacy
    #                                      full-materialization oracle
    trace: bool = False                  # enable the observability
    #                                      tracer (repro.obs): per-request
    #                                      spans across scheduler waves,
    #                                      retrieval stages, KV pool and
    #                                      kernels, exported as Chrome
    #                                      trace-event JSON
    trace_path: Optional[str] = None     # where RalmEngine.write_trace()
    #                                      saves the trace by default
    retrieval_deadline_s: float = 0.0    # per-dispatch retrieval latency
    #                                      budget: a fault domain still
    #                                      unresolved past it is dropped
    #                                      and the flush serves the exact
    #                                      top-k over the survivors
    #                                      (0 = wait indefinitely)
    hedge_quantile: float = 0.95         # latency quantile after which a
    #                                      hung dispatch is hedged to
    #                                      another replica
    shard_replicas: int = 1              # dispatch-target replicas per
    #                                      retrieval fault domain; > 1 (or
    #                                      a deadline/chaos plan) arms the
    #                                      fault-tolerant dispatch layer
    chaos_plan: Optional[str] = None     # path to a FaultPlan JSON to arm
    #                                      at the service's scan boundary
    #                                      (deterministic fault injection)
    attn_seq_block: int = 16             # KV-pool seq-axis alignment:
    #                                      per-wave attention reads crop
    #                                      to this quantum (kv_len), so
    #                                      ragged waves skip the pool's
    #                                      max_seq padding; bounds the
    #                                      extra decode-graph variants
    #                                      at max_seq / attn_seq_block


# ---------------------------------------------------------------------------
# the Retriever protocol
# ---------------------------------------------------------------------------

@runtime_checkable
class Retriever(Protocol):
    """What the engine needs from a retrieval service — nothing more.

    ``resolve`` owns missing-id masking: ids < 0 come back as -1 tokens
    (kind="tokens") or PAD-0 chunks (kind="chunks"), so the decode loop
    never inspects ids itself."""

    def search(self, queries: jnp.ndarray
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """[B, d] queries -> (dists [B, K], global ids [B, K])."""
        ...

    def resolve(self, ids: jnp.ndarray, kind: str = "tokens"
                ) -> jnp.ndarray:
        """[B, K] ids -> payload: next-tokens [B, K] (kNN-LM) or chunks
        [B, K, chunk_len] (RETRO), masked for missing ids."""
        ...


def _resolve_from_tables(payload_tokens, chunk_table, ids, kind,
                         gather=rag_lib.gather_payload):
    """Shared resolve() body: gather from the right table, mask missing
    ids exactly once (the old loops each re-implemented this)."""
    if kind == "tokens":
        if payload_tokens is None:
            raise ValueError("retriever has no payload_tokens table")
        toks = gather(payload_tokens, ids)
        return jnp.where(ids >= 0, toks, -1)
    if kind == "chunks":
        if chunk_table is None:
            raise ValueError("retriever has no chunk_table")
        chunks = gather(chunk_table, ids)
        return jnp.where((ids >= 0)[..., None], chunks, 0)
    raise ValueError(f"unknown payload kind: {kind!r}")


@dataclasses.dataclass
class LocalRetriever:
    """Single-process ChamVS over a list of shards (tests, examples,
    datastore builds). Field layout is the old ``RetrievalEngine``'s, so
    existing constructors keep working through the compat shim."""
    params: IVFPQParams
    shards: List[IVFPQShard]
    cfg: ChamVSConfig
    payload_tokens: Optional[jnp.ndarray] = None   # [N] next-token table
    chunk_table: Optional[jnp.ndarray] = None      # [N, chunk_len]
    query_proj: Optional[jnp.ndarray] = None       # [d_model, dq]

    def search(self, queries: jnp.ndarray
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        q = queries.astype(jnp.float32)
        if self.query_proj is not None:
            q = q @ self.query_proj
        return chamvs_lib.search_single(self.params, self.shards, q,
                                        self.cfg)

    def resolve(self, ids: jnp.ndarray, kind: str = "tokens"
                ) -> jnp.ndarray:
        return _resolve_from_tables(self.payload_tokens, self.chunk_table,
                                    ids, kind)


class DistributedRetriever:
    """ChamVS over a retrieval mesh, routed by a ``ShardRouter``: the
    router owns shard/table placement, the in-graph broadcast + scan +
    merge for the query path, and the sharded payload gather (no host
    round-trip and no full-table all-gather — see ``build_gather``'s
    docstring)."""

    def __init__(self, mesh: Mesh, params: IVFPQParams,
                 shards: List[IVFPQShard], cfg: ChamVSConfig,
                 payload_tokens: Optional[jnp.ndarray] = None,
                 chunk_table: Optional[jnp.ndarray] = None,
                 query_proj: Optional[jnp.ndarray] = None,
                 db_axes: Tuple[str, ...] = ("data",),
                 query_axis: Optional[str] = None):
        self.mesh, self.cfg = mesh, cfg
        self.query_proj = query_proj
        self.router = ShardRouter(mesh, cfg, db_axes=db_axes,
                                  query_axis=query_axis)
        self.db_params = self.router.place_params(params)
        self.db_shard = self.router.place_shards(shards)
        self.payload_tokens = self.router.place_table(payload_tokens)
        self.chunk_table = self.router.place_table(chunk_table)

    def search(self, queries: jnp.ndarray
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        q = jnp.asarray(queries, jnp.float32)
        if self.query_proj is not None:
            q = q @ self.query_proj
        return self.router.search(self.db_params, self.db_shard, q)

    def resolve(self, ids: jnp.ndarray, kind: str = "tokens"
                ) -> jnp.ndarray:
        def gather(table, ids):
            return self.router.gather(table, jnp.maximum(ids, 0))
        return _resolve_from_tables(self.payload_tokens, self.chunk_table,
                                    ids, kind, gather=gather)


@dataclasses.dataclass
class AsyncRetriever:
    """``Retriever`` backed by a ``repro.retrieval.RetrievalService``.

    ``search`` keeps the synchronous protocol (submit + flush + result);
    the extra surface is what the scheduler exploits:

      * ``search_async(queries) -> SearchHandle`` — enqueue without
        dispatching, so queries from every sequence in a wave coalesce;
      * ``flush()`` — run the coalesced batch as one kernel dispatch.

    Payload resolution is table-local like ``LocalRetriever``'s."""
    service: RetrievalService
    payload_tokens: Optional[jnp.ndarray] = None   # [N] next-token table
    chunk_table: Optional[jnp.ndarray] = None      # [N, chunk_len]
    query_proj: Optional[jnp.ndarray] = None       # [d_model, dq]

    def _project(self, queries: jnp.ndarray) -> jnp.ndarray:
        q = jnp.asarray(queries, jnp.float32)
        if self.query_proj is not None:
            q = q @ self.query_proj
        return q

    def search_async(self, queries: jnp.ndarray) -> SearchHandle:
        return self.service.submit(self._project(queries))

    def stale_lookup(self, queries: jnp.ndarray):
        """Any-generation cache probe: possibly-stale neighbors to seed
        speculative decode (None on a miss or without a cache)."""
        return self.service.stale_lookup(self._project(queries))

    def flush(self) -> None:
        self.service.flush()

    def search(self, queries: jnp.ndarray
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        return self.search_async(queries).result()

    def resolve(self, ids: jnp.ndarray, kind: str = "tokens"
                ) -> jnp.ndarray:
        measure = self.service.config.measure
        t0 = time.perf_counter()
        with self.service.tracer.span("retrieval.gather", "retrieval"):
            out = _resolve_from_tables(self.payload_tokens,
                                       self.chunk_table, ids, kind)
            if measure:
                jax.block_until_ready(out)
        if measure:
            self.service.stats.gather.add(time.perf_counter() - t0)
        return out
