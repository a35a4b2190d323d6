"""``repro.serve`` — the unified RALM serving API.

One ``Retriever`` protocol, one generation loop, pluggable
monolithic/disaggregated backends::

    from repro.serve import (DatastoreBuilder, RagConfig, RalmEngine)

    ds = DatastoreBuilder(dim=cfg.d_model).from_corpus(params, cfg, corpus)
    engine = RalmEngine.monolithic(
        params, cfg, rag, retriever=ds.retriever(ds.search_config(k=8)))
    tokens = engine.generate(prompt, steps=8)

See ``docs/serving.md`` for the API tour and the migration table from
the old entry points.
"""
from repro.core.rag import RagConfig
from repro.retrieval.service import (RetrievalService, SearchHandle,
                                     ServiceConfig)
from repro.serve.api import (AsyncRetriever, DistributedRetriever,
                             EngineConfig, LocalRetriever, RalmRequest,
                             RalmResponse, Retriever)
from repro.serve.datastore import Datastore, DatastoreBuilder
from repro.serve.engine import (DisaggregatedBackend, MonolithicBackend,
                                PoolTimes, PrefillStats, RalmEngine,
                                SequenceState)
from repro.serve.gateway import (AdmissionController, DegradeConfig,
                                 DegradePolicy, Gateway, GatewayConfig,
                                 TenantQuota)
from repro.serve.kvpool import KVCachePool, PoolStats
from repro.serve.scheduler import RalmScheduler

__all__ = [
    "AdmissionController", "AsyncRetriever", "Datastore",
    "DatastoreBuilder", "DegradeConfig", "DegradePolicy",
    "DisaggregatedBackend", "DistributedRetriever", "EngineConfig",
    "Gateway", "GatewayConfig", "KVCachePool", "LocalRetriever",
    "MonolithicBackend", "PoolStats", "PoolTimes", "PrefillStats",
    "RagConfig",
    "RalmEngine", "RalmRequest", "RalmResponse", "RalmScheduler",
    "RetrievalService", "Retriever", "SearchHandle", "SequenceState",
    "ServiceConfig", "TenantQuota",
]
