"""``DatastoreBuilder`` — the one place an IVF-PQ datastore is built.

The train-quantizers / build-shards / keep-payload-tables recipe used to
be copy-pasted (with drifting hyperparameters) across
``launch/serve.py``, ``examples/serve_ralm.py``,
``examples/quickstart.py`` and the system-test fixture. It lives here
now, in two flavors:

  * ``build(vectors, ...)`` — index an explicit vector set (quickstart,
    ANN benchmarks);
  * ``from_corpus(params, cfg, corpus, ...)`` — the kNN-LM datastore:
    run the LM over a token corpus and index *its own hidden states*,
    each keyed to the next token (paper §2.1, Khandelwal et al.).

The result is a ``Datastore`` that hands out ``Retriever``
implementations for either deployment shape.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.core.chamvs import ChamVSConfig
from repro.core.ivfpq import (IVFPQConfig, IVFPQParams, IVFPQShard,
                              build_shards, list_sizes, train_ivfpq)
from repro.models import transformer as tf
from repro.models.config import ModelConfig
from repro.retrieval.service import RetrievalService, ServiceConfig
from repro.serve.api import (AsyncRetriever, DistributedRetriever,
                             LocalRetriever)


CORPUS_BATCH_TOKENS = 1 << 16   # tokens per corpus forward when keying


@functools.partial(jax.jit, static_argnums=1)
def _corpus_hidden(params, cfg: ModelConfig, tokens: jnp.ndarray
                   ) -> jnp.ndarray:
    """[B, T] tokens -> [B, T - 1, d] f32 keys (every prefix but the
    last, which has no next token)."""
    return tf.hidden_states(params, cfg, tokens)[:, :-1].astype(jnp.float32)


@dataclasses.dataclass
class Datastore:
    """A built index + its payload tables."""
    params: IVFPQParams
    shards: List[IVFPQShard]
    index_cfg: IVFPQConfig
    payload_tokens: Optional[jnp.ndarray] = None   # [N] next-token table
    chunk_table: Optional[jnp.ndarray] = None      # [N, chunk_len]
    num_vectors: int = 0

    @property
    def num_shards(self) -> int:
        return len(self.shards)

    def search_config(self, nprobe: int = 32, k: int = 100,
                      backend: Optional[str] = None, **kw) -> ChamVSConfig:
        return ChamVSConfig(ivfpq=self.index_cfg, nprobe=nprobe, k=k,
                            backend=backend, **kw)

    def retriever(self, search_cfg: ChamVSConfig,
                  query_proj: Optional[jnp.ndarray] = None
                  ) -> LocalRetriever:
        """Single-process ``Retriever`` over this datastore."""
        return LocalRetriever(params=self.params, shards=self.shards,
                              cfg=search_cfg,
                              payload_tokens=self.payload_tokens,
                              chunk_table=self.chunk_table,
                              query_proj=query_proj)

    def async_retriever(self, search_cfg: ChamVSConfig,
                        query_proj: Optional[jnp.ndarray] = None,
                        service_cfg: Optional[ServiceConfig] = None
                        ) -> AsyncRetriever:
        """Service-backed ``Retriever``: searches go through a
        ``RetrievalService`` (micro-batching + futures + optional result
        cache), so the scheduler coalesces concurrent sequences' queries
        into one batched kernel dispatch."""
        service = RetrievalService.local(self.params, self.shards,
                                         search_cfg, config=service_cfg)
        return AsyncRetriever(service=service,
                              payload_tokens=self.payload_tokens,
                              chunk_table=self.chunk_table,
                              query_proj=query_proj)

    def distributed_retriever(self, mesh: Mesh, search_cfg: ChamVSConfig,
                              query_proj: Optional[jnp.ndarray] = None,
                              db_axes: Tuple[str, ...] = ("data",)
                              ) -> DistributedRetriever:
        """``Retriever`` with the shards laid out over ``mesh`` (one
        memory node per device along ``db_axes``)."""
        return DistributedRetriever(
            mesh, self.params, self.shards, search_cfg,
            payload_tokens=self.payload_tokens,
            chunk_table=self.chunk_table, query_proj=query_proj,
            db_axes=db_axes)


@dataclasses.dataclass
class DatastoreBuilder:
    """Hyperparameters of the build, with the defaults the old call
    sites converged on. ``m=None`` derives the PQ sub-quantizer count
    from the dimension (``dim // 16``, floor 4); ``list_cap=None``
    derives the per-shard list capacity from the data (the longest
    list slice, rounded up to 128 rows)."""
    dim: int
    nlist: int = 8
    m: Optional[int] = None
    list_cap: Optional[int] = 1024
    residual: bool = False
    num_shards: int = 2
    kmeans_iters: int = 8
    seed: int = 1

    def index_config(self) -> IVFPQConfig:
        m = self.m if self.m is not None else max(self.dim // 16, 4)
        return IVFPQConfig(dim=self.dim, nlist=self.nlist, m=m,
                           list_cap=self.list_cap or 0,
                           residual=self.residual)

    def build(self, vectors: np.ndarray,
              payload_tokens: Optional[jnp.ndarray] = None,
              chunk_table: Optional[jnp.ndarray] = None,
              train_vectors: Optional[np.ndarray] = None) -> Datastore:
        """Train quantizers (on ``train_vectors`` if given, else on the
        full set) and shard the database over ``num_shards`` memory
        nodes (partition scheme 1: every IVF list striped across all
        shards)."""
        vectors = np.asarray(vectors, np.float32)
        train = vectors if train_vectors is None else np.asarray(
            train_vectors, np.float32)
        icfg = self.index_config()
        params = train_ivfpq(jax.random.PRNGKey(self.seed),
                             jnp.asarray(train), icfg,
                             kmeans_iters=self.kmeans_iters)
        if self.list_cap is None:
            longest = int(list_sizes(params, vectors, icfg.nlist).max())
            cap = -(-longest // self.num_shards)
            icfg = dataclasses.replace(icfg, list_cap=-(-cap // 128) * 128)
        shards = build_shards(params, vectors, icfg,
                              num_shards=self.num_shards)
        return Datastore(
            params=params, shards=shards, index_cfg=icfg,
            payload_tokens=None if payload_tokens is None
            else jnp.asarray(payload_tokens),
            chunk_table=None if chunk_table is None
            else jnp.asarray(chunk_table),
            num_vectors=vectors.shape[0])

    # ------------------------------------------------------------------
    @staticmethod
    def corpus_keys(params, cfg: ModelConfig, corpus: np.ndarray
                    ) -> Tuple[np.ndarray, np.ndarray]:
        """kNN-LM keys: the LM's hidden state at every prefix of
        ``corpus`` [n_docs, doc_len], paired with the next token.
        Returns (keys [N, d_model] f32, next_tokens [N]).

        The corpus runs through one jitted hidden-state forward per
        batch of about ``CORPUS_BATCH_TOKENS`` tokens (the last batch
        padded to the same shape, so it compiles once); no logits are
        formed."""
        corpus = np.asarray(corpus, np.int32)
        n_docs, doc_len = corpus.shape
        rows = max(1, min(n_docs, CORPUS_BATCH_TOKENS // doc_len))
        keys = np.empty((n_docs, doc_len - 1, cfg.d_model), np.float32)
        for s in range(0, n_docs, rows):
            batch = corpus[s:s + rows]
            n = batch.shape[0]
            if n < rows:
                batch = np.pad(batch, ((0, rows - n), (0, 0)))
            keys[s:s + n] = np.asarray(
                _corpus_hidden(params, cfg, jnp.asarray(batch)))[:n]
        nxt = corpus[:, 1:].reshape(-1)
        return keys.reshape(-1, cfg.d_model), nxt

    def from_corpus(self, params, cfg: ModelConfig, corpus: np.ndarray
                    ) -> Datastore:
        """Build the kNN-LM datastore from the model's own hidden states
        over ``corpus`` (the flow every serving entry point used to
        hand-roll)."""
        assert self.dim == cfg.d_model, (self.dim, cfg.d_model)
        keys, nxt = self.corpus_keys(params, cfg, corpus)
        return self.build(keys, payload_tokens=jnp.asarray(nxt))
