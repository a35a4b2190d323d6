"""``RalmEngine`` — the single generation loop behind every entry point.

The decode -> retrieve -> interpolate -> sample step used to live in two
divergent copies (``core/generate.py`` and ``core/coordinator.py``; they
even disagreed on the step-0 retrieval query). It now lives here once,
split into the two phases the scheduler pipelines:

  * ``dispatch_decode(seq)`` — advance the LM one token (async dispatch);
  * ``finish_step(seq, ...)`` — retrieval + kNN-LM interpolation / RETRO
    re-encode + sampling.

Backends own the decode side of the boundary:

  * ``MonolithicBackend`` — one mesh / the default devices; decode and
    search share hardware (the paper's GPU-only baseline);
  * ``DisaggregatedBackend`` — the paper's split: an LM pool and a
    retrieval pool with independent meshes, plus ``PoolTimes`` measuring
    the per-pool step times that give the Fig. 13 optimal-ratio estimate.

Decode has two shapes. The default (``wave=True``) runs over a
``KVCachePool``: every active sequence's rows live in pooled cache
slots, and ``decode_wave`` advances the whole wave as ONE dispatch
(``tokens [W], slots [W], positions [W]``, W bucketed to powers of two
like the retrieval service's query batches). kNN interpolation and
greedy sampling batch the same way. The per-sequence path
(``wave=False``) is kept as the parity oracle — greedy outputs must be
token-identical between the two, including staggered admission and
ragged prompt lengths (tests/test_kvpool.py).

Retrieval is any object satisfying ``api.Retriever``; the engine never
looks past ``search``/``resolve``.

Step-0 correctness note: the first retrieval query is the *prefill*'s
last-position hidden state (exactly what the decode step would have
produced), so monolithic and disaggregated runs are token-identical
under greedy decoding — the old loops disagreed here (embedding
stand-in vs re-decoding the last prompt token).
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.compat import use_mesh
from repro.core import rag as rag_lib
from repro.kernels import registry
from repro.core.chamvs import ChamVSConfig
from repro.core.ivfpq import IVFPQParams, IVFPQShard
from repro.core.rag import RagConfig
from repro.launch.mesh import make_mesh_for
from repro.models import moe as moe_lib
from repro.models import transformer as tf
from repro.models.config import ModelConfig
from repro.obs.trace import NULL_TRACER, Tracer
from repro.serve.api import (DistributedRetriever, EngineConfig,
                             RalmRequest, RalmResponse, Retriever)
from repro.serve.kvpool import KVCachePool, next_pow2
from repro.serve.scheduler import RalmScheduler


@dataclasses.dataclass
class PoolTimes:
    """Per-pool step times (paper Fig. 13 instrumentation)."""
    decode_s: List[float] = dataclasses.field(default_factory=list)
    search_s: List[float] = dataclasses.field(default_factory=list)

    def optimal_ratio(self) -> float:
        """Paper Fig. 13: LM-pool units needed to saturate one retrieval
        engine = (retrieval throughput) / (decode throughput) per batch."""
        if not self.decode_s or not self.search_s:
            return float("nan")
        return float(np.median(self.decode_s) / np.median(self.search_s))


# ---------------------------------------------------------------------------
# decode backends
# ---------------------------------------------------------------------------

PREFILL_MIN_BUCKET = 16


@dataclasses.dataclass
class PrefillStats:
    """Admission-prefill accounting (/statsz, /metricsz, tests)."""
    calls: int = 0               # prefills run
    prompt_tokens: int = 0       # real prompt tokens consumed (rows x len)
    pad_tokens: int = 0          # tail padding up to each call's bucket
    programs: set = dataclasses.field(default_factory=set)
    #                            # distinct (bucket, rows, max_seq) keys:
    #                            # one compiled prefill program each


def pads_unread(cfg: ModelConfig) -> bool:
    """Whether a prompt padded at its tail prefills its real rows and
    cache entries as the exact prompt does, with no pad entry ever read.
    True when every cache class is a full-length KV or latent cache
    (dense or MoE blocks, no sliding-window ring): causal attention keeps
    the pads out of every real row, and their K/V land at positions >=
    the prompt length, which decode overwrites before it reads them;
    serving MoE is dropless, so a pad's experts never touch a real
    token. A ring would wrap pad K/V over real positions, and a
    recurrent state (``rwkv6``, ``hybrid``) would carry the pads
    forward."""
    return cfg.block in ("dense", "moe") and not (
        cfg.window > 0 and "local" in cfg.pattern_classes())


def prefill_bucket(cfg: ModelConfig, prompt_len: int, max_seq: int) -> int:
    """The length the prefill program runs at: the prompt's power-of-two
    bucket (at least ``PREFILL_MIN_BUCKET``, at most ``max_seq``) where
    ``pads_unread``, else the exact length."""
    if not pads_unread(cfg) or prompt_len >= max_seq:
        return prompt_len
    return min(max(next_pow2(prompt_len), PREFILL_MIN_BUCKET), max_seq)


@functools.partial(jax.jit, static_argnums=1,
                   static_argnames=("max_seq", "enc_len"))
def _jit_prefill(params, cfg: ModelConfig, prompt, length, *, max_seq: int,
                 enc_len: int):
    """Consume a prompt [B, T] whose real tokens fill positions
    < ``length`` (a traced scalar, so every length of a bucket shares one
    program). Returns (caches, enc_states, last_logits [B,V],
    last_hidden [B,d]) — the hidden state at the last real position is
    the step-0 retrieval query. ``enc_len`` is the width of the neutral
    encoder input (0: no encoder). One shared jit cache for all
    backends/engines, keyed on (cfg, bucket, rows, max_seq, enc_len)."""
    B = prompt.shape[0]
    caches = tf.init_cache(cfg, B, max_seq=max_seq, enc_len=0)
    enc_states = None
    if enc_len:
        neutral = jnp.zeros((B, enc_len), jnp.int32)
        enc_states = tf.encode(params, cfg, tf.embed_tokens(params, neutral))
    logits, caches, hidden = tf.prefill(params, cfg, caches, prompt, length,
                                        enc_states=enc_states)
    return caches, enc_states, logits, hidden


def _neutral_enc_len(cfg: ModelConfig, rag: RagConfig) -> int:
    """Width of the neutral encoder input an encdec prefill attends to
    (RETRO: k retrieved chunks; at least 8 tokens); 0 for a decoder."""
    if cfg.arch != "encdec":
        return 0
    return max(rag.k * rag.chunk_len if rag.mode == "retro" else 0, 8)


@functools.partial(jax.jit, static_argnums=1,
                   static_argnames=("attn_spec",))
def _jit_decode(params, cfg: ModelConfig, caches, token, position,
                enc_states, *, attn_spec=None):
    """One shared jit cache for all backends/engines (``cfg`` is frozen
    and hashable), so repeatedly constructing engines — e.g. the
    ``generate()`` compat shim — never re-traces decode_step."""
    return tf.decode_step(params, cfg, caches, token, position,
                          enc_states=enc_states, return_hidden=True,
                          attn_spec=attn_spec)


@functools.partial(jax.jit, static_argnums=1, donate_argnums=(2,),
                   static_argnames=("kv_len", "attn_spec"))
def _jit_decode_wave(params, cfg: ModelConfig, caches, token, slots,
                     position, enc_states, totals=None, *, kv_len=None,
                     attn_spec=None):
    """One dispatch per wave over the slotted KV-cache pool. The pool
    caches are donated: the per-layer K/V writes land in place, so step
    cost is O(wave), not O(pool). Shared jit cache across engines, keyed
    on (cfg, wave bucket, pool shape, kv_len, attn_spec) — ``kv_len`` is
    the wave's block-aligned valid prefix (attention reads crop to it,
    see ``KVCachePool.attn_len``), ``attn_spec`` the static
    decode-attention kernel selection. An MoE model passes its running
    routing ``totals`` (int32 [3]: assignments, experts touched, largest
    load) and gets them back, fourth, with the wave's added on the
    device: no host sync. A dense model passes ``None`` and its program
    computes no counter."""
    out = tf.decode_wave(params, cfg, caches, token, slots, position,
                         enc_states=enc_states, return_hidden=True,
                         kv_len=kv_len, attn_spec=attn_spec,
                         return_stats=totals is not None)
    if totals is None:
        return out
    return out[:3] + (moe_lib.add_stats(totals, out[3]),)


def _moe_totals(cfg: ModelConfig) -> Optional[jnp.ndarray]:
    """The routing counters a backend carries through its waves: zeros
    for an MoE model, ``None`` (no counter, no work) for a dense one."""
    return jnp.zeros((3,), jnp.int32) if cfg.block == "moe" else None


class MonolithicBackend:
    """Decode on the default device set — LM and retrieval share
    hardware. No per-step blocking, so jax's async dispatch pipelines."""

    name = "monolithic"
    times: Optional[PoolTimes] = None

    def __init__(self, params, cfg: ModelConfig):
        self.params, self.cfg = params, cfg
        self.decode_dispatches = 0      # LM dispatch counter (tests/bench)
        self.moe_totals = _moe_totals(cfg)

    def prefill(self, rag: RagConfig, prompt, length: int, max_seq: int):
        """Prefill ``prompt`` [B, T] whose first ``length`` positions are
        the real prompt (see ``RalmEngine._prefill``)."""
        return _jit_prefill(self.params, self.cfg, prompt, np.int32(length),
                            max_seq=max_seq,
                            enc_len=_neutral_enc_len(self.cfg, rag))

    def decode(self, caches, token, position, enc_states=None,
               attn_spec=None):
        self.decode_dispatches += 1
        return _jit_decode(self.params, self.cfg, caches, token, position,
                           enc_states, attn_spec=attn_spec)

    def decode_wave(self, caches, token, slots, position, enc_states=None,
                    kv_len=None, attn_spec=None):
        """Advance one wave of pooled slots: token/slots/position [W]."""
        self.decode_dispatches += 1
        out = _jit_decode_wave(self.params, self.cfg, caches, token, slots,
                               position, enc_states, self.moe_totals,
                               kv_len=kv_len, attn_spec=attn_spec)
        if self.moe_totals is not None:
            self.moe_totals = out[3]
        return out[:3]

    def encode_chunks(self, chunks: jnp.ndarray) -> jnp.ndarray:
        """RETRO re-encode of retrieved chunk tokens [B, L] — LM-side
        work, so it lives on the backend like prefill/decode."""
        emb = tf.embed_tokens(self.params, chunks)
        return tf.encode(self.params, self.cfg, emb)


class DisaggregatedBackend:
    """The paper's split device set: an LM pool and a retrieval pool with
    independent meshes. The retrieval mesh is exposed for a
    ``DistributedRetriever`` to live on; ``PoolTimes`` records both
    pools' step times (decode here, search in the engine)."""

    name = "disaggregated"

    def __init__(self, params, cfg: ModelConfig,
                 lm_devices: int = 1, ret_devices: int = 1,
                 measure: bool = True):
        """``measure=True`` records PoolTimes (Fig. 13 ratio) — at the
        cost of a block_until_ready per pool step, which serializes the
        pools. Pass ``measure=False`` to let the scheduler's two-phase
        dispatch actually overlap decode and retrieval across batches."""
        devs = jax.devices()
        assert lm_devices + ret_devices <= len(devs), (
            lm_devices, ret_devices, len(devs))
        self.params, self.cfg = params, cfg
        self.decode_dispatches = 0
        self.moe_totals = _moe_totals(cfg)
        self.times = PoolTimes() if measure else None
        # LM pool: pure data-parallel decode (each unit = one "GPU process")
        self.lm_mesh = make_mesh_for(devs[:lm_devices], data=lm_devices)
        # Retrieval pool: ChamVS memory nodes over their own mesh
        self.ret_mesh = make_mesh_for(
            devs[lm_devices:lm_devices + ret_devices], data=ret_devices)

    def prefill(self, rag: RagConfig, prompt, length: int, max_seq: int):
        with use_mesh(self.lm_mesh):
            return _jit_prefill(self.params, self.cfg, prompt,
                                np.int32(length), max_seq=max_seq,
                                enc_len=_neutral_enc_len(self.cfg, rag))

    def decode(self, caches, token, position, enc_states=None,
               attn_spec=None):
        self.decode_dispatches += 1
        t0 = time.time()
        with use_mesh(self.lm_mesh):
            logits, caches, hidden = _jit_decode(
                self.params, self.cfg, caches, token, position, enc_states,
                attn_spec=attn_spec)
        if self.times is not None:
            logits.block_until_ready()
            self.times.decode_s.append(time.time() - t0)
        return logits, caches, hidden

    def decode_wave(self, caches, token, slots, position, enc_states=None,
                    kv_len=None, attn_spec=None):
        """One LM-pool dispatch for the whole wave (paper §5: the GPU
        pool batches inference across requests)."""
        self.decode_dispatches += 1
        t0 = time.time()
        with use_mesh(self.lm_mesh):
            out = _jit_decode_wave(
                self.params, self.cfg, caches, token, slots, position,
                enc_states, self.moe_totals, kv_len=kv_len,
                attn_spec=attn_spec)
        logits, caches, hidden = out[:3]
        if self.moe_totals is not None:
            self.moe_totals = out[3]
        if self.times is not None:
            logits.block_until_ready()
            self.times.decode_s.append(time.time() - t0)
        return logits, caches, hidden

    def encode_chunks(self, chunks: jnp.ndarray) -> jnp.ndarray:
        """RETRO re-encode on the LM pool (encoder work belongs to the
        LM side of the pool split, like prefill's encoder pass)."""
        with use_mesh(self.lm_mesh):
            emb = tf.embed_tokens(self.params, chunks)
            return tf.encode(self.params, self.cfg, emb)


# ---------------------------------------------------------------------------
# per-request state + the engine
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class SpecPoint:
    """One outstanding speculation: a retrieval-due step that decoded
    ahead on stale neighbors while the real search runs async.

    Everything needed to verify later — and to roll back on a mismatch
    — is captured at emit time: the pre-interpolation LM logits (so the
    verification interpolation is bit-identical to what the baseline
    would have computed), the token actually emitted from the stale
    mix, and the ``seq.out`` length before that emit (the truncation
    watermark)."""
    step: int                      # the due step that speculated
    handle: Any                    # SearchHandle of the real search
    logits: jnp.ndarray            # [B, V] LM logits at `step`
    emitted: jnp.ndarray           # [B, 1] token emitted from stale mix
    out_len: int                   # len(seq.out) BEFORE the emit
    age: int = 0                   # waves since issue; verified when
    #                                age reaches the speculation depth


class _SpecIssue:
    """Phase-2a marker for a speculated row: ``finish_wave`` integrates
    the stale ``(dists, ids)`` instead of blocking on ``handle`` (the
    real search, resolved by ``spec_harvest`` 1..k waves later)."""

    __slots__ = ("handle", "dists", "ids")

    def __init__(self, handle, dists, ids):
        self.handle = handle
        self.dists = dists
        self.ids = ids


@dataclasses.dataclass
class SequenceState:
    """One active request's decode state (owned by the scheduler).

    Wave mode: ``caches``/``enc_states`` are ``None`` — the KV lives in
    the engine's ``KVCachePool`` at rows ``slots`` (one per prompt row),
    claimed at admission and freed at completion."""
    request: RalmRequest
    caches: Any
    enc_states: Optional[jnp.ndarray]
    out: List[jnp.ndarray]
    cur: jnp.ndarray                     # [B, 1] last sampled token
    t0: int                              # prompt length
    logits0: Optional[jnp.ndarray]       # prefill logits (consumed at s=0)
    hidden0: Optional[jnp.ndarray]       # prefill hidden  (step-0 query)
    rng: Optional[jax.Array]
    step: int = 0
    slots: Optional[np.ndarray] = None   # pool rows (wave mode)
    last_neighbors: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None
    #                                      most recent VERIFIED (dists,
    #                                      ids) — the stale neighbors
    #                                      the next due step speculates
    #                                      with
    spec_points: List[SpecPoint] = dataclasses.field(default_factory=list)

    @property
    def done(self) -> bool:
        return self.request.cancelled or self.step >= self.request.steps

    def tokens(self) -> jnp.ndarray:
        return jnp.concatenate(self.out, axis=1)


class RalmEngine:
    """Facade: one decode backend + one ``Retriever`` + the canonical
    generation step. All entry points (examples, launchers, the old
    ``generate``/``DisaggregatedRuntime`` shims) go through here."""

    def __init__(self, backend, retriever: Optional[Retriever] = None,
                 rag: Optional[RagConfig] = None,
                 max_seq: Optional[int] = None,
                 max_active: Optional[int] = None,
                 wave: bool = True, kv_slots: Optional[int] = None,
                 attn_backend: Optional[str] = None,
                 attn_seq_block: int = 16,
                 tracer: Optional[Tracer] = None,
                 speculate_k: int = 0,
                 speculate_verify: bool = True):
        """``wave=True`` (default) decodes every active sequence in one
        dispatch per scheduler wave over a slotted ``KVCachePool``;
        ``wave=False`` keeps the per-sequence oracle loop (one dispatch
        per sequence, private caches). ``kv_slots`` fixes the pool
        capacity in rows — admission then defers until completions free
        slots; ``None`` lets the pool grow on demand.

        ``attn_backend`` selects the wave decode-attention kernel:
        ``None`` (default) is the platform's serving backend — the
        streaming ``kernels/decode_attn`` Pallas kernel, compiled, on an
        accelerator, and ``"ref"`` (grouped einsum over the KV-head
        axis) on a CPU host; ``"pallas"`` on a CPU host interprets the
        kernel; ``"einsum"`` is the legacy full-materialization oracle
        ("kernel off"). ``attn_seq_block`` is the pool's seq-axis alignment
        quantum: per wave the engine crops attention reads to the
        block-aligned valid prefix (``KVCachePool.attn_len``), so short
        waves stop paying for pool padding at the cost of O(max_seq /
        attn_seq_block) extra decode-graph variants."""
        self.backend = backend
        self.attn_spec = registry.serving_spec(attn_backend)
        self.attn_seq_block = attn_seq_block
        self.retriever = retriever
        self.rag = rag if rag is not None else RagConfig(mode="none")
        self.cfg = backend.cfg
        if wave and self.rag.mode == "retro" and \
                self.cfg.arch == "encdec" and \
                self.rag.k * self.rag.chunk_len < 8:
            # the pooled enc buffer needs one width for all slots, but
            # prefill's neutral encoder floor is 8 tokens while re-encode
            # rows would be k*chunk_len wide — fail at construction, not
            # mid-generation inside write_enc
            raise ValueError(
                f"wave decode needs rag.k * rag.chunk_len >= 8 for RETRO "
                f"(got {self.rag.k} * {self.rag.chunk_len}); use "
                "wave=False for this config")
        self.max_seq = max_seq
        self.wave = wave
        self.kv_slots = kv_slots
        # -- speculative retrieval (RaLMSpec, arXiv 2401.14021) --------
        self.speculate_k = int(speculate_k)
        self.speculate_verify = speculate_verify
        if self.speculate_k > 0:
            import warnings
            if not wave:
                warnings.warn(
                    "speculate_k > 0 requires wave decode (the "
                    "per-sequence oracle path is the thing speculation "
                    "verifies against) — disabling speculation.",
                    RuntimeWarning, stacklevel=2)
                self.speculate_k = 0
            elif self.cfg.ssm_state > 0 or \
                    self.cfg.block in ("rwkv6", "hybrid"):
                warnings.warn(
                    f"speculate_k > 0 is unsupported for recurrent-state "
                    f"blocks (block={self.cfg.block!r}, ssm_state="
                    f"{self.cfg.ssm_state}): the state update cannot be "
                    "rewound on rollback — disabling speculation.",
                    RuntimeWarning, stacklevel=2)
                self.speculate_k = 0
        # verification depth in waves. Ring (sliding-window) caches
        # alias KV positions modulo the window, so only a depth-1
        # rollback rewrites exactly the slots it invalidated — deeper
        # speculation is clamped for windowed models (see
        # KVCachePool.rewind).
        self._spec_depth = self.speculate_k
        if self.speculate_k > 0 and self.cfg.window > 0 and \
                "local" in self.cfg.pattern_classes():
            self._spec_depth = 1
        self._local_spec_stats = None    # fallback when no service
        self.pool: Optional[KVCachePool] = None   # built at first admission
        self.prefill_stats = PrefillStats()
        self.times: Optional[PoolTimes] = getattr(backend, "times", None)
        self.scheduler = RalmScheduler(self, max_active=max_active)
        self._unclaimed: List[RalmResponse] = []
        self.tracer = NULL_TRACER
        self.trace_path: Optional[str] = None
        if tracer is not None:
            self.set_tracer(tracer)

    # -- observability ------------------------------------------------------

    def set_tracer(self, tracer: Tracer) -> None:
        """Install a tracer and propagate it to every component the
        engine owns a span site in: the retrieval service (scan/merge/
        queue-wait/gather) and the KV pool (alloc/release/recompile).
        Components created later (the lazy pool) pick it up at
        construction."""
        self.tracer = tracer
        service = getattr(self.retriever, "service", None)
        if service is not None:
            service.tracer = tracer
        if self.pool is not None:
            self.pool.tracer = tracer

    def write_trace(self, path: Optional[str] = None) -> str:
        """Dump the trace buffer as Chrome trace-event JSON. ``path``
        defaults to ``EngineConfig.trace_path`` or ``trace.json``."""
        path = path or self.trace_path or "trace.json"
        self.tracer.write(path)
        return path

    @property
    def decode_dispatches(self) -> int:
        """LM dispatches issued so far (wave mode: one per wave)."""
        return self.backend.decode_dispatches

    def moe_stats(self) -> Optional[dict]:
        """An MoE model's routing counters over every decode wave so far
        (pad rows of a bucket included: what the expert matmuls
        computed): assignments and experts touched, summed over layers
        and waves, and the largest one expert's load in any layer of any
        wave. Read from the device at call time; ``None`` for a dense
        model."""
        totals = getattr(self.backend, "moe_totals", None)
        if totals is None:
            return None
        a, touched, top = (int(x) for x in np.asarray(totals))
        return dict(assignments=a, experts_touched=touched, max_load=top)

    # -- constructors -------------------------------------------------------

    @classmethod
    def monolithic(cls, params, cfg: ModelConfig, rag: RagConfig,
                   retriever: Optional[Retriever] = None,
                   max_seq: Optional[int] = None, wave: bool = True,
                   kv_slots: Optional[int] = None,
                   attn_backend: Optional[str] = None,
                   attn_seq_block: int = 16,
                   speculate_k: int = 0,
                   speculate_verify: bool = True) -> "RalmEngine":
        return cls(MonolithicBackend(params, cfg), retriever, rag,
                   max_seq=max_seq, wave=wave, kv_slots=kv_slots,
                   attn_backend=attn_backend,
                   attn_seq_block=attn_seq_block,
                   speculate_k=speculate_k,
                   speculate_verify=speculate_verify)

    @classmethod
    def disaggregated(cls, params, cfg: ModelConfig, rag: RagConfig,
                      db_params: IVFPQParams, db_shards: List[IVFPQShard],
                      search_cfg: ChamVSConfig,
                      payload_tokens: Optional[jnp.ndarray] = None,
                      chunk_table: Optional[jnp.ndarray] = None,
                      lm_devices: int = 1, ret_devices: int = 1,
                      query_proj: Optional[jnp.ndarray] = None,
                      max_seq: Optional[int] = None,
                      measure: bool = True, wave: bool = True,
                      kv_slots: Optional[int] = None,
                      attn_backend: Optional[str] = None,
                      attn_seq_block: int = 16) -> "RalmEngine":
        backend = DisaggregatedBackend(params, cfg, lm_devices=lm_devices,
                                       ret_devices=ret_devices,
                                       measure=measure)
        retriever = DistributedRetriever(
            backend.ret_mesh, db_params, db_shards, search_cfg,
            payload_tokens=payload_tokens, chunk_table=chunk_table,
            query_proj=query_proj)
        return cls(backend, retriever, rag, max_seq=max_seq, wave=wave,
                   kv_slots=kv_slots, attn_backend=attn_backend,
                   attn_seq_block=attn_seq_block)

    @classmethod
    def from_config(cls, config: EngineConfig, params, datastore,
                    search_cfg: ChamVSConfig,
                    query_proj: Optional[jnp.ndarray] = None
                    ) -> "RalmEngine":
        """Stand an engine up from an ``EngineConfig`` + a built
        ``Datastore`` (see ``repro.serve.datastore``). A disaggregated
        config needs ``lm_devices + ret_devices`` devices and raises
        when the host has fewer."""
        # plumb the search-kernel selection (Pallas vs ref, fused vs
        # staged scan) from the deployment config down to ChamVSConfig —
        # the registry KernelSpec everything routes with
        search_cfg = search_cfg.with_kernel(config.kernel_backend,
                                            config.kernel_fused)
        if config.disaggregate:
            need = config.lm_devices + config.ret_devices
            if len(jax.devices()) < need:
                raise ValueError(
                    f"EngineConfig.disaggregate=True needs lm_devices + "
                    f"ret_devices = {need} devices; found "
                    f"{len(jax.devices())}")
        if config.disaggregate and config.async_retrieval:
            import warnings
            warnings.warn(
                "EngineConfig.async_retrieval is not wired into the "
                "disaggregated path yet — falling back to the synchronous "
                "DistributedRetriever (no RetrievalService coalescing or "
                "cache).", RuntimeWarning, stacklevel=2)
        if config.disaggregate:
            if config.speculate_k > 0:
                import warnings
                warnings.warn(
                    "EngineConfig.speculate_k is not wired into the "
                    "disaggregated path (the synchronous "
                    "DistributedRetriever has no async handles) — "
                    "speculation stays off.", RuntimeWarning,
                    stacklevel=2)
            eng = cls.disaggregated(
                params, config.model, config.rag, datastore.params,
                datastore.shards, search_cfg,
                payload_tokens=datastore.payload_tokens,
                chunk_table=datastore.chunk_table,
                lm_devices=config.lm_devices,
                ret_devices=config.ret_devices, query_proj=query_proj,
                max_seq=config.max_seq, wave=config.wave_decode,
                kv_slots=config.kv_slots,
                attn_backend=config.attn_backend,
                attn_seq_block=config.attn_seq_block)
        else:
            if config.retrieval_cache > 0 and not config.async_retrieval:
                import warnings
                warnings.warn(
                    "EngineConfig.retrieval_cache requires "
                    "async_retrieval=True (the cache lives in the "
                    "RetrievalService) — ignoring it.", RuntimeWarning,
                    stacklevel=2)
            speculate_k = config.speculate_k
            if speculate_k > 0 and not config.async_retrieval:
                import warnings
                warnings.warn(
                    "EngineConfig.speculate_k requires "
                    "async_retrieval=True (speculation hides the "
                    "RetrievalService's async scan behind decode; a "
                    "synchronous retriever has nothing to hide) — "
                    "disabling speculation.", RuntimeWarning,
                    stacklevel=2)
                speculate_k = 0
            ft_wanted = (config.shard_replicas > 1 or
                         config.retrieval_deadline_s > 0.0 or
                         config.chaos_plan is not None)
            if ft_wanted and not config.async_retrieval:
                import warnings
                warnings.warn(
                    "EngineConfig retrieval fault-tolerance knobs "
                    "(shard_replicas / retrieval_deadline_s / chaos_plan) "
                    "require async_retrieval=True (the dispatch loop "
                    "lives in the RetrievalService) — ignoring them.",
                    RuntimeWarning, stacklevel=2)
            if config.async_retrieval:
                from repro.retrieval.replica import FailoverConfig
                from repro.retrieval.service import ServiceConfig
                failover = None
                if ft_wanted:
                    failover = FailoverConfig(
                        replicas=max(1, config.shard_replicas),
                        dispatch_deadline_s=config.retrieval_deadline_s,
                        hedge_quantile=config.hedge_quantile)
                retriever = datastore.async_retriever(
                    search_cfg, query_proj=query_proj,
                    service_cfg=ServiceConfig(
                        cache_entries=config.retrieval_cache,
                        measure=config.retrieval_measure,
                        failover=failover))
                if config.chaos_plan is not None:
                    retriever.service.install_chaos(config.chaos_plan)
            else:
                retriever = datastore.retriever(search_cfg,
                                                query_proj=query_proj)
            eng = cls.monolithic(params, config.model, config.rag,
                                 retriever=retriever,
                                 max_seq=config.max_seq,
                                 wave=config.wave_decode,
                                 kv_slots=config.kv_slots,
                                 attn_backend=config.attn_backend,
                                 attn_seq_block=config.attn_seq_block,
                                 speculate_k=speculate_k,
                                 speculate_verify=config.speculate_verify)
        eng.scheduler.max_active = config.max_active
        if config.trace:
            eng.set_tracer(Tracer(enabled=True))
        eng.trace_path = config.trace_path
        return eng

    # -- KV-cache pool admission (wave mode) --------------------------------

    def check_admissible(self, request: RalmRequest) -> None:
        """Reject-at-submit guard: a request that can NEVER fit the
        fixed-capacity pool must fail in ``submit()``, not poison the
        FIFO queue for everyone behind it when ``_admit`` reaches it."""
        if self.wave and self.kv_slots is not None and \
                request.prompt.shape[0] > self.kv_slots:
            raise ValueError(
                f"request batch of {request.prompt.shape[0]} rows can "
                f"never fit kv_slots={self.kv_slots}")

    def can_admit(self, request: RalmRequest) -> bool:
        """Admission check the scheduler consults before ``start``: a
        fixed-capacity pool defers requests until completions free
        enough slot rows (an auto-growing pool admits everything)."""
        if not self.wave or self.kv_slots is None:
            return True
        B = request.prompt.shape[0]
        return self.pool is None or self.pool.num_free >= B

    def _ensure_pool(self, rows: int, need_seq: int) -> KVCachePool:
        """Create the pool lazily (shapes depend on the first admitted
        request unless ``max_seq``/``kv_slots`` pin them) and grow it —
        slot rows double, the sequence axis extends — when an admission
        needs more than it has."""
        if self.pool is None:
            cap = (self.kv_slots if self.kv_slots is not None
                   else max(next_pow2(rows), 8))
            self.pool = KVCachePool(self.cfg, cap,
                                    self.max_seq or need_seq,
                                    fixed=self.kv_slots is not None,
                                    seq_block=self.attn_seq_block)
            self.pool.tracer = self.tracer
        pool = self.pool
        if self.max_seq is None and need_seq > pool.max_seq:
            pool.grow_seq(need_seq)
        if pool.num_free < rows:
            pool.grow_slots(max(pool.capacity * 2,
                                next_pow2(pool.num_used + rows)))
        return pool

    def release(self, seq: SequenceState) -> None:
        """Return a finished sequence's slot rows to the pool."""
        if seq.spec_points:
            # safety net — the scheduler settles points via
            # spec_finalize before releasing; anything left here
            # (e.g. a cancelled request) is discarded unverified
            stats = self.spec_stats
            for p in seq.spec_points:
                cancel = getattr(p.handle, "cancel", None)
                if cancel is not None:
                    cancel()
                stats.spec_discarded += 1
            seq.spec_points.clear()
        if seq.slots is not None and self.pool is not None:
            self.pool.release(seq.slots)
            seq.slots = None

    # -- the canonical step (called by the scheduler) -----------------------

    def start(self, request: RalmRequest) -> SequenceState:
        """Prefill a request into an active sequence. Wave mode: claim
        one pool slot per prompt row, prefill at the pool's ``max_seq``
        (so cache leaves line up slot-for-slot) and scatter the rows in;
        the request itself holds no cache."""
        B, T0 = request.prompt.shape
        request.times.admit = time.perf_counter()
        tr = self.tracer
        if tr.enabled:
            # retroactive span on the request track: the queue wait
            # started back at submit() (times.arrival), which predates
            # this call — plus the flow arrow Perfetto draws from here
            # to wherever this request's first token lands (see _emit)
            args = {"request_id": request.request_id,
                    "trace_id": request.trace_id, "tenant": request.tenant,
                    "rows": B}
            if request.times.arrival is not None:
                tr.complete("queue.wait", "requests",
                            request.times.arrival,
                            request.times.admit - request.times.arrival,
                            args=args)
            if request.trace_id is not None:
                tr.flow_start(request.trace_id)
        span_args = {"request_id": request.request_id, "rows": B,
                     "prompt_len": T0} if tr.active else None
        with tr.span("sched.admit", "requests", args=span_args):
            if self.wave:
                pool = self._ensure_pool(B, T0 + request.steps)
                slots = pool.alloc(B)
                (caches, enc_states, logits0, hidden0), cur = \
                    self._prefill(request, pool.max_seq, span_args)
                with tr.span("prefill.scatter", "requests"):
                    pool.write_prefill(slots, caches)
                if enc_states is not None:
                    pool.write_enc(slots, enc_states)
                return SequenceState(
                    request=request, caches=None, enc_states=None,
                    out=[request.prompt], cur=cur, t0=T0, logits0=logits0,
                    hidden0=hidden0, rng=request.rng, slots=slots)
            max_seq = self.max_seq or (T0 + request.steps)
            (caches, enc_states, logits0, hidden0), cur = \
                self._prefill(request, max_seq, span_args)
            return SequenceState(
                request=request, caches=caches, enc_states=enc_states,
                out=[request.prompt], cur=cur, t0=T0, logits0=logits0,
                hidden0=hidden0, rng=request.rng)

    def _prefill(self, request: RalmRequest, max_seq: int,
                 span_args: Optional[dict]):
        """Run the request's prefill at its ``prefill_bucket`` length: the
        prompt is padded at its tail with token 0 on the host, so every
        length of a bucket runs the same compiled program. Returns the
        backend's (caches, enc_states, logits0, hidden0) and the last
        prompt token [B, 1], also taken on the host (no program per exact
        length)."""
        prompt = np.asarray(request.prompt)
        B, T0 = prompt.shape
        bucket = prefill_bucket(self.cfg, T0, max_seq)
        st = self.prefill_stats
        st.calls += 1
        st.prompt_tokens += B * T0
        st.pad_tokens += B * (bucket - T0)
        st.programs.add((bucket, B, max_seq))
        if span_args is not None:
            span_args = dict(span_args, bucket=bucket)
        with self.tracer.span("prefill", "requests", args=span_args):
            out = self.backend.prefill(
                self.rag, np.pad(prompt, ((0, 0), (0, bucket - T0))), T0,
                max_seq)
        return out, jnp.asarray(prompt[:, -1:])

    def dispatch_decode(self, seq: SequenceState
                        ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Phase 1: one LM step. At step 0 the prefill already produced
        both the logits and the retrieval query, so nothing runs."""
        if seq.step == 0:
            logits, hidden = seq.logits0, seq.hidden0
            seq.logits0 = seq.hidden0 = None
            return logits, hidden
        B = seq.cur.shape[0]
        position = jnp.full((B,), seq.t0 + seq.step - 1, jnp.int32)
        logits, seq.caches, hidden = self.backend.decode(
            seq.caches, seq.cur, position, enc_states=seq.enc_states,
            attn_spec=self.attn_spec)
        return logits, hidden

    def _search(self, queries: jnp.ndarray):
        t0 = time.time()
        dists, ids = self.retriever.search(queries)
        if self.times is not None:
            dists.block_until_ready()
            self.times.search_s.append(time.time() - t0)
        return dists, ids

    def _retrieval_due(self, step: int) -> bool:
        # pure host arithmetic (same semantics as rag.should_retrieve):
        # this runs in phase 2a while decodes are in flight, so it must
        # not touch the device
        return (self.retriever is not None and self.rag.mode != "none" and
                (self.rag.interval <= 1 or step % self.rag.interval == 0))

    def dispatch_search(self, seq: SequenceState, hidden: jnp.ndarray):
        """Phase 2a: issue this sequence's retrieval query, without
        dispatching the kernel. Returns a ``SearchHandle`` when the
        retriever batches asynchronously (``AsyncRetriever``), else
        ``None`` — the synchronous path searches inside ``finish_step``.
        """
        if not self._retrieval_due(seq.step):
            return None
        submit = getattr(self.retriever, "search_async", None)
        if submit is None:
            return None
        return submit(hidden)

    def flush_searches(self) -> None:
        """Phase 2b: coalesce every query issued by ``dispatch_search``
        into one batched kernel dispatch (no-op for sync retrievers)."""
        flush = getattr(self.retriever, "flush", None)
        if flush is not None:
            flush()

    def finish_step(self, seq: SequenceState, logits: jnp.ndarray,
                    hidden: jnp.ndarray, search=None) -> None:
        """Phase 2 (2c when async): retrieve (if due) + integrate +
        sample one token. ``search`` is the ``SearchHandle`` returned by
        ``dispatch_search``, if any."""
        s, rag = seq.step, self.rag
        log_or_prob = logits
        if self._retrieval_due(s):
            if search is not None:
                t0 = time.time()
                dists, ids = search.result()
                if getattr(search, "partial", False):
                    seq.request.partial_steps += 1
                if self.times is not None:
                    dists.block_until_ready()
                    self.times.search_s.append(time.time() - t0)
            else:
                dists, ids = self._search(hidden)
            if seq.request.trace is not None:
                seq.request.trace.append(dict(step=s, ids=np.asarray(ids)))
            if rag.mode == "knnlm":
                toks = self.retriever.resolve(ids, kind="tokens")
                log_or_prob = rag_lib.knnlm_interpolate(
                    logits, dists, toks, rag.lam, rag.temperature)
            elif rag.mode == "retro" and self.cfg.arch == "encdec":
                B = seq.cur.shape[0]
                chunks = self.retriever.resolve(ids, kind="chunks")
                seq.enc_states = self.backend.encode_chunks(
                    chunks.reshape(B, -1))
        if seq.request.greedy or seq.rng is None:
            nxt = jnp.argmax(log_or_prob, axis=-1).astype(jnp.int32)
        else:
            seq.rng, k = jax.random.split(seq.rng)
            nxt = jax.random.categorical(k, log_or_prob).astype(jnp.int32)
        self._emit(seq, nxt)

    # -- the wave-batched step (one dispatch per phase per wave) ------------

    def dispatch_wave(self, seqs: List[SequenceState]
                      ) -> List[Tuple[jnp.ndarray, jnp.ndarray]]:
        """Phase 1, wave mode: ONE ``decode_wave`` dispatch advances every
        step>0 sequence (step-0 sequences consume their prefill outputs —
        nothing to run). Returns per-sequence (logits [B,V], hidden
        [B,d]) views sliced from the wave outputs."""
        outs: List = [None] * len(seqs)
        wave = []
        for i, seq in enumerate(seqs):
            if seq.step == 0:
                outs[i] = (seq.logits0, seq.hidden0)
                seq.logits0 = seq.hidden0 = None
            else:
                wave.append((i, seq))
        if not wave:
            return outs
        pool = self.pool
        tokens = jnp.concatenate([seq.cur for _, seq in wave], axis=0)
        slots = np.concatenate([seq.slots for _, seq in wave])
        positions = np.concatenate(
            [np.full(seq.cur.shape[0], seq.t0 + seq.step - 1, np.int32)
             for _, seq in wave])
        # the wave's positions are host arrays, so the block-aligned
        # valid prefix is known before dispatch: attention reads crop to
        # kv_len instead of the pool's padded max_seq (pad rows sit at
        # position 0 and never extend it)
        max_pos = int(positions.max())
        tokens, slots, positions = pool.pad_wave(tokens, slots, positions)
        kv_len = pool.attn_len(max_pos, bucket=len(slots))
        tr = self.tracer
        with tr.span("wave.decode", "wave",
                     args={"rows": len(wave), "bucket": len(slots),
                           "kv_len": kv_len} if tr.active else None):
            logits, pool.caches, hidden = self.backend.decode_wave(
                pool.caches, tokens, jnp.asarray(slots),
                jnp.asarray(positions), enc_states=pool.gather_enc(slots),
                kv_len=kv_len, attn_spec=self.attn_spec)
        off = 0
        for i, seq in wave:
            B = seq.cur.shape[0]
            outs[i] = (logits[off:off + B], hidden[off:off + B])
            off += B
        return outs

    def dispatch_search_wave(self, seqs: List[SequenceState],
                             decoded: List) -> List:
        """Phase 2a/2b, wave mode: issue every due sequence's retrieval
        query. Async retrievers coalesce via the service (flushed by the
        scheduler's ``flush_searches``); synchronous retrievers get their
        rows concatenated into ONE batched ``search`` here."""
        searches: List = [None] * len(seqs)
        due = [i for i, seq in enumerate(seqs)
               if self._retrieval_due(seq.step)]
        if not due:
            return searches
        submit = getattr(self.retriever, "search_async", None)
        if submit is not None:
            issued = 0
            for i in due:
                seq = seqs[i]
                if self._spec_eligible(seq):
                    src = self._spec_source(seq, decoded[i][1])
                    if src is not None:
                        # fire-and-forget: the real search coalesces
                        # into this wave's flush; decode continues on
                        # the stale neighbors; spec_harvest verifies
                        # 1..k waves later, off the critical path
                        searches[i] = _SpecIssue(submit(decoded[i][1]),
                                                 src[0], src[1])
                        self.spec_stats.spec_issued += 1
                        issued += 1
                        continue
                searches[i] = submit(decoded[i][1])
            if issued and self.tracer.enabled:
                self.tracer.instant("spec.issue", "wave",
                                    args={"points": issued})
            return searches
        queries = jnp.concatenate([decoded[i][1] for i in due], axis=0)
        dists, ids = self._search(queries)
        off = 0
        for i in due:
            B = decoded[i][1].shape[0]
            searches[i] = (dists[off:off + B], ids[off:off + B])
            off += B
        return searches

    def finish_wave(self, seqs: List[SequenceState], decoded: List,
                    searches: List) -> None:
        """Phase 2c, wave mode: integrate + sample for the whole wave in
        batched dispatches — one ``resolve`` + one ``knnlm_interpolate``
        over all due rows, one RETRO re-encode over all due chunks, one
        greedy argmax over every greedy row. Per-request ``rng`` sampling
        stays per-sequence (each request owns an independent key chain,
        so batching it would change the sampled tokens). Its three
        phases are the spans ``wave.mix``, ``wave.sample`` and
        ``wave.stream`` (the emit loop, with the wave's host sync)."""
        tr = self.tracer
        with tr.span("wave.mix", "wave"):
            rows, spec_new = self._mix_wave(seqs, decoded, searches)
        with tr.span("wave.sample", "wave"):
            nxt = self._sample_wave(seqs, rows)
        with tr.span("wave.stream", "wave"):
            for seq, tok in zip(seqs, nxt):
                self._emit(seq, tok)
            # register the wave's speculation points AFTER the emits so
            # each captures the token it produced and the pre-emit out
            # length (eligibility guarantees these rows are greedy, so
            # `seq.cur` now holds the token the stale mix argmax'd)
            for seq, issue, logits in spec_new:
                seq.spec_points.append(SpecPoint(
                    step=seq.step - 1, handle=issue.handle, logits=logits,
                    emitted=seq.cur, out_len=len(seq.out) - 1))

    def _mix_wave(self, seqs: List[SequenceState], decoded: List,
                  searches: List) -> Tuple[List[jnp.ndarray], List]:
        """The wave's search results into its rows of logits: returns
        each sequence's distribution to sample from, and the rows that
        mixed speculated (stale) neighbours."""
        rag = self.rag
        rows: List[jnp.ndarray] = []
        knn = []                # (row_idx, logits, dists, ids)
        retro = []              # (seq, chunks [B, k*chunk_len])
        spec_new = []           # (seq, _SpecIssue, logits)
        for seq, out, search in zip(seqs, decoded, searches):
            logits, hidden = out
            if search is not None:
                if isinstance(search, _SpecIssue):
                    # speculated row: integrate the STALE neighbors now
                    # (no result() — the real search stays in flight);
                    # the trace entry waits for verification, which
                    # records the real ids
                    knn.append((len(rows), logits,
                                jnp.asarray(search.dists),
                                jnp.asarray(search.ids)))
                    spec_new.append((seq, search, logits))
                    rows.append(logits)
                    continue
                if hasattr(search, "result"):      # async SearchHandle
                    t0 = time.time()
                    dists, ids = search.result()
                    if self.times is not None:
                        dists.block_until_ready()
                        self.times.search_s.append(time.time() - t0)
                else:                              # pre-sliced sync batch
                    dists, ids = search
                partial = getattr(search, "partial", False)
                if partial:
                    seq.request.partial_steps += 1
                if seq.request.trace is not None:
                    seq.request.trace.append(
                        dict(step=seq.step, ids=np.asarray(ids)))
                if rag.mode == "knnlm":
                    knn.append((len(rows), logits, dists, ids))
                    if self.speculate_k > 0 and not partial:
                        # a non-speculated due row still refreshes the
                        # seed the NEXT due step speculates with (a
                        # partial result would seed speculation with
                        # degraded neighbors — keep the last full set)
                        seq.last_neighbors = (dists, ids)
                elif rag.mode == "retro" and self.cfg.arch == "encdec":
                    retro.append((seq, ids))
            rows.append(logits)
        if knn:
            logits_cat = jnp.concatenate([e[1] for e in knn], axis=0)
            dists_cat = jnp.concatenate([e[2] for e in knn], axis=0)
            ids_cat = jnp.concatenate([e[3] for e in knn], axis=0)
            toks = self.retriever.resolve(ids_cat, kind="tokens")
            mixed = rag_lib.knnlm_interpolate(
                logits_cat, dists_cat, toks, rag.lam, rag.temperature)
            off = 0
            for idx, logits, _, _ in knn:
                B = logits.shape[0]
                rows[idx] = mixed[off:off + B]
                off += B
        if retro:
            # one chunk resolve + one re-encode over every due row, like
            # the knnlm branch above
            chunks = self.retriever.resolve(
                jnp.concatenate([ids for _, ids in retro], axis=0),
                kind="chunks")
            W = chunks.shape[0]
            enc = self.backend.encode_chunks(chunks.reshape(W, -1))
            off = 0
            for seq, _ in retro:
                B = seq.cur.shape[0]
                self.pool.write_enc(seq.slots, enc[off:off + B])
                off += B
        return rows, spec_new

    @staticmethod
    def _sample_wave(seqs: List[SequenceState],
                     rows: List[jnp.ndarray]) -> List[jnp.ndarray]:
        """Each sequence's next tokens: one argmax over every greedy
        row, and a categorical draw per sampling sequence."""
        nxt: List = [None] * len(seqs)
        greedy = [i for i, seq in enumerate(seqs)
                  if seq.request.greedy or seq.rng is None]
        if greedy:
            nxt_cat = jnp.argmax(
                jnp.concatenate([rows[i] for i in greedy], axis=0),
                axis=-1).astype(jnp.int32)
            off = 0
            for i in greedy:
                B = rows[i].shape[0]
                nxt[i] = nxt_cat[off:off + B]
                off += B
        for i, seq in enumerate(seqs):
            if seq.request.greedy or seq.rng is None:
                continue
            seq.rng, k = jax.random.split(seq.rng)
            nxt[i] = jax.random.categorical(k, rows[i]).astype(jnp.int32)
        return nxt

    def _emit(self, seq: SequenceState, nxt: jnp.ndarray) -> None:
        seq.cur = nxt[:, None]
        seq.out.append(seq.cur)
        req = seq.request
        first = req.times.first_token is None
        if req.on_token is not None:
            # the streaming hook needs host tokens, which forces the
            # wave's device work to complete here — one sync per wave
            # (the first row's asarray blocks; the rest are free). The
            # first-token timestamp is taken AFTER the sync so TTFT
            # measures token availability, not dispatch.
            host = np.asarray(nxt)
            if first:
                req.times.first_token = time.perf_counter()
            req.on_token(seq.step, host)
        elif first:
            # no streaming consumer: stamp dispatch time (approximate —
            # jax async dispatch means the value may still be in flight)
            req.times.first_token = time.perf_counter()
        if first and req.trace_id is not None and self.tracer.enabled:
            # close the TTFT flow arrow opened at admission: Perfetto
            # draws queue.wait -> the wave that produced the first token
            self.tracer.flow_end(req.trace_id, track="wave",
                                 t_s=req.times.first_token)
        seq.step += 1

    # -- speculative retrieval (RaLMSpec, arXiv 2401.14021) -----------------

    @property
    def spec_stats(self):
        """Where speculation counters land: the retrieval service's
        ``RetrievalStats`` when one exists (so /statsz and the bench see
        one retrieval plane), else a local instance."""
        service = getattr(self.retriever, "service", None)
        if service is not None:
            return service.stats
        if self._local_spec_stats is None:
            from repro.retrieval.stats import RetrievalStats
            self._local_spec_stats = RetrievalStats()
        return self._local_spec_stats

    def _spec_eligible(self, seq: SequenceState) -> bool:
        """Per-row speculation gate, evaluated at each due step (the
        degrade ladder mutates ``rag`` between waves, so this cannot be
        decided at construction): greedy kNN-LM rows only — sampling
        consumes rng state a rollback cannot restore, and a streaming
        consumer (``on_token``) would have already seen tokens a
        rollback retracts."""
        req = seq.request
        return (self.speculate_k > 0
                and self.rag.mode == "knnlm"
                and (req.greedy or seq.rng is None)
                and req.on_token is None
                and len(seq.spec_points) < self.speculate_k)

    def _spec_source(self, seq: SequenceState, hidden: jnp.ndarray):
        """The stale neighbors to decode ahead with: the sequence's
        last verified result, else a stale-tolerant cache probe (a
        cross-request seed — partial-batch cache hits feeding
        speculation), else None (the row searches synchronously and
        seeds the next due step)."""
        if seq.last_neighbors is not None:
            return seq.last_neighbors
        lookup = getattr(self.retriever, "stale_lookup", None)
        if lookup is not None:
            return lookup(hidden)
        return None

    def spec_harvest(self, seqs: List[SequenceState],
                     decoded: Optional[List] = None,
                     force: bool = False) -> None:
        """Verify speculation points whose real search has had
        ``_spec_depth`` waves to land (all of them under ``force``).

        Verification compares *emitted tokens*, not neighbor ids: the
        point's saved pre-interpolation logits are re-mixed with the
        REAL (dists, tokens) — exactly the baseline's ``finish_step``
        math — and the argmax is compared against the token the stale
        mix emitted. Match -> the speculated timeline IS the baseline
        timeline (accept). Mismatch -> roll back and replay
        (``_spec_rollback``). The forcing of the in-flight results is
        timed into ``spec_wait`` — the residual retrieval time NOT
        hidden behind decode, the bench's numerator."""
        pts: List[Tuple[Optional[int], SequenceState, SpecPoint]] = []
        for idx, seq in enumerate(seqs):
            if not seq.spec_points:
                continue
            for p in seq.spec_points:
                p.age += 1
            take = 0
            for p in seq.spec_points:
                if force or p.age >= self._spec_depth:
                    take += 1
                else:
                    break
            for p in seq.spec_points[:take]:
                pts.append((idx if decoded is not None else None, seq, p))
            del seq.spec_points[:take]
        if not pts:
            return
        stats = self.spec_stats
        tr = self.tracer
        rag = self.rag
        with tr.span("spec.verify", "wave",
                     args={"points": len(pts), "force": force}
                     if tr.active else None):
            t0 = time.perf_counter()
            res = [p.handle.result() for _, _, p in pts]
            # spec_wait times ONLY the forcing of the in-flight search
            # results. XLA drains its queue in enqueue order, so this
            # wait excludes the decode wave dispatched after the scan —
            # it is the residual retrieval time the overlap failed to
            # hide, comparable to the baseline's queue_wait + scan.
            # Results already materialized (is_ready) were fully hidden.
            for d, i in res:
                ready_d = getattr(d, "is_ready", None)
                ready_i = getattr(i, "is_ready", None)
                if (ready_d is None or ready_d()) and \
                        (ready_i is None or ready_i()):
                    stats.spec_landed += 1
            jax.block_until_ready([x for pair in res for x in pair])
            stats.spec_wait.add(time.perf_counter() - t0)
            partials = [getattr(p.handle, "partial", False)
                        for _, _, p in pts]
            for (_, seq, _), part in zip(pts, partials):
                if part:
                    # the real search timed out into a partial result:
                    # the point still settles (verify math below runs on
                    # the degraded neighbors, so verification can never
                    # hang on a dead shard), but the result is not a
                    # speculation seed
                    stats.ft_spec_flushed += 1
                    seq.request.partial_steps += 1
            if not self.speculate_verify:
                # trust-the-stale mode: adopt the real neighbors as the
                # next seed, never compare, never roll back
                for (_, seq, _), (d, i), part in zip(pts, res, partials):
                    if not part:
                        seq.last_neighbors = (d, i)
                return
            # ONE batched interpolate + argmax + host sync over every
            # point being verified this wave; this math is NOT counted
            # in spec_wait — the baseline pays the same interpolate in
            # its finish phase
            d_cat = jnp.concatenate([d for d, _ in res], axis=0)
            i_cat = jnp.concatenate([i for _, i in res], axis=0)
            logits_cat = jnp.concatenate([p.logits for _, _, p in pts],
                                         axis=0)
            toks = self.retriever.resolve(i_cat, kind="tokens")
            mixed = rag_lib.knnlm_interpolate(
                logits_cat, d_cat, toks, rag.lam, rag.temperature)
            nxt_cat = np.asarray(
                jnp.argmax(mixed, axis=-1).astype(jnp.int32))
            emit_cat = np.asarray(
                jnp.concatenate([p.emitted[:, 0] for _, _, p in pts]))
            off = 0
            rolled: set = set()
            for (idx, seq, p), (d, i), part in zip(pts, res, partials):
                B = p.logits.shape[0]
                corrected = nxt_cat[off:off + B]
                emitted = emit_cat[off:off + B]
                off += B
                if id(seq) in rolled:
                    # a later point of a sequence that already rolled
                    # back this harvest: its query came from the
                    # discarded timeline
                    stats.spec_discarded += 1
                    continue
                stats.spec_verified += 1
                if not part:
                    seq.last_neighbors = (d, i)
                if seq.request.trace is not None:
                    # the REAL retrieval for this step — same entry the
                    # baseline records (acceptance is token equality,
                    # which doesn't require id equality)
                    seq.request.trace.append(
                        dict(step=p.step, ids=np.asarray(i)))
                if np.array_equal(corrected, emitted):
                    stats.spec_accepted += 1
                else:
                    stats.spec_rollbacks += 1
                    rolled.add(id(seq))
                    self._spec_rollback(seq, p, corrected, decoded, idx)

    def _spec_rollback(self, seq: SequenceState, point: SpecPoint,
                       corrected: np.ndarray,
                       decoded: Optional[List], idx: Optional[int]) -> None:
        """Mismatch path: rewind to the speculation point and replay
        through the per-sequence oracle semantics with verified
        neighbors.

        The corrected token for the speculation step itself is free —
        the verification interpolation already computed it. Later steps
        replay as single-row waves with BLOCKING searches at due steps,
        which is exactly the baseline's math on the corrected token
        stream, so greedy parity holds by induction."""
        stats = self.spec_stats
        tr = self.tracer
        t0 = time.perf_counter()
        cur_step = seq.step
        with tr.span("spec.rollback", "wave",
                     args={"step": point.step, "depth":
                           cur_step - point.step}
                     if tr.active else None):
            # later points' queries/logits came from the timeline being
            # discarded — drop them unverified
            for p in seq.spec_points:
                cancel = getattr(p.handle, "cancel", None)
                if cancel is not None:
                    cancel()
                stats.spec_discarded += 1
            seq.spec_points.clear()
            # token watermark: truncate to before the speculated emit
            del seq.out[point.out_len:]
            seq.cur = seq.out[-1][:, -1:]
            seq.step = point.step
            if self.pool is not None and seq.slots is not None:
                # KV watermark. Positions written so far: the prompt
                # (t0) plus one per decode step 1..s at t0+s-1 — plus
                # the current wave's phase-1 decode when we are
                # mid-wave (decoded is not None).
                old_len = seq.t0 + cur_step - (0 if decoded is not None
                                               else 1)
                keep_len = seq.t0 + point.step
                if old_len > keep_len:
                    self.pool.rewind(seq.slots, keep_len=keep_len,
                                     old_len=old_len)
            # the speculation step's corrected token (no decode needed)
            self._emit(seq, jnp.asarray(corrected, jnp.int32))
            stats.spec_replayed_steps += 1
            # replay the steps that decoded on the wrong token stream
            while seq.step < cur_step:
                logits, hidden = self.dispatch_wave([seq])[0]
                log_or_prob = logits
                if self._retrieval_due(seq.step):
                    dists, ids = self.retriever.search(hidden)  # blocks
                    seq.last_neighbors = (dists, ids)
                    if seq.request.trace is not None:
                        seq.request.trace.append(
                            dict(step=seq.step, ids=np.asarray(ids)))
                    toks = self.retriever.resolve(ids, kind="tokens")
                    log_or_prob = rag_lib.knnlm_interpolate(
                        logits, dists, toks, self.rag.lam,
                        self.rag.temperature)
                self._emit(seq, jnp.argmax(
                    log_or_prob, axis=-1).astype(jnp.int32))
                stats.spec_replayed_steps += 1
            if decoded is not None and idx is not None:
                # mid-wave: the current wave's phase-1 output for this
                # row was computed from the wrong token — redo it so
                # the pending finish_wave integrates corrected logits
                decoded[idx] = self.dispatch_wave([seq])[0]
        stats.spec_replay.add(time.perf_counter() - t0)

    def spec_finalize(self, seq: SequenceState) -> None:
        """Settle a finishing sequence's outstanding points BEFORE its
        response is emitted: cancelled requests discard them, completed
        ones force-verify (so the response tokens carry the parity
        guarantee)."""
        if not seq.spec_points:
            return
        if seq.request.cancelled:
            stats = self.spec_stats
            for p in seq.spec_points:
                cancel = getattr(p.handle, "cancel", None)
                if cancel is not None:
                    cancel()
                stats.spec_discarded += 1
            seq.spec_points.clear()
            return
        self.spec_harvest([seq], decoded=None, force=True)

    def flush_speculation(self) -> None:
        """Force-verify EVERY outstanding speculation point. The
        degrade ladder calls this before mutating retrieval quality
        (nprobe/interval/mode): in-flight points must verify with the
        math they were issued under, and the next due step re-seeds at
        the new quality."""
        if self.speculate_k <= 0:
            return
        seqs = [s for s in self.scheduler.active if s.spec_points]
        if seqs:
            self.spec_harvest(seqs, decoded=None, force=True)

    # -- serving API --------------------------------------------------------

    def submit(self, request: RalmRequest) -> int:
        return self.scheduler.submit(request)

    def step(self) -> List[RalmResponse]:
        return self.scheduler.step()

    def run(self) -> List[RalmResponse]:
        """Drain the scheduler; includes any responses that completed
        during an interleaved ``generate()`` call."""
        out = self._unclaimed + self.scheduler.run()
        self._unclaimed = []
        return out

    def generate(self, prompt: jnp.ndarray, steps: int, *,
                 greedy: bool = True, rng: Optional[jax.Array] = None,
                 trace: Optional[list] = None) -> jnp.ndarray:
        """Synchronous convenience: one request, run to completion.
        Other in-flight requests also advance; their responses are held
        for the next ``run()`` call, not discarded."""
        rid = self.submit(RalmRequest(prompt=jnp.asarray(prompt),
                                      steps=steps, greedy=greedy, rng=rng,
                                      trace=trace))
        result = None
        for resp in self.scheduler.run():
            if resp.request_id == rid:
                result = resp
            else:
                self._unclaimed.append(resp)
        if result is None:  # pragma: no cover
            raise RuntimeError("request did not complete")
        return jnp.asarray(result.tokens)

    def generate_batches(self, prompts: List[jnp.ndarray], steps: int
                         ) -> List[np.ndarray]:
        """Pipelined convenience: several request batches in flight at
        once (the old ``generate_pipelined``). Results in submit order."""
        rids = [self.submit(RalmRequest(prompt=jnp.asarray(p), steps=steps))
                for p in prompts]
        by_id = {r.request_id: r.tokens for r in self.run()}
        return [np.asarray(by_id[rid]) for rid in rids]
