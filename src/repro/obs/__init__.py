"""``repro.obs`` — the unified observability plane (tracing + metrics).

Chameleon's headline results are latency *decompositions* (paper
Fig. 9/10: queue wait vs scan vs merge vs gather; TTFT/TPOT under
disaggregation), and every scheduling/partitioning decision downstream
of this repo (RAGO's LM:retrieval split, the ROADMAP's SLO controller)
keys on exactly that per-stage telemetry. This package is the
measurement substrate, stdlib-only but for the profiler sink
(``jax.profiler``):

  * ``trace`` — a ``Tracer`` with a zero-cost-when-disabled span API,
    thread-safe ring-buffered events, per-request trace IDs, and
    Chrome trace-event JSON export loadable in Perfetto
    (https://ui.perfetto.dev); its spans also land in a JAX profiler
    trace as ``ralm.<name>`` while a profiler session is open;
  * ``metrics`` — a ``MetricsRegistry`` (counters, gauges, fixed-bucket
    histograms with reservoir p50/p95/p99) rendered in Prometheus text
    exposition format (the gateway's ``GET /metricsz``);
  * ``adapters`` — thin collectors that absorb the pre-existing
    scattered stats (``PoolStats``, ``RetrievalStats``, scheduler queue
    depths, kernel-registry fallback counters) into one registry.

See ``docs/observability.md`` for the span taxonomy and the
``/statsz`` -> ``/metricsz`` migration table.
"""
from repro.obs.adapters import bind_engine_metrics, bind_gateway_metrics
from repro.obs.metrics import (DEFAULT_BUCKETS, Counter, Gauge, Histogram,
                               MetricsRegistry, Reservoir)
from repro.obs.trace import (NULL_TRACER, Tracer, validate_chrome_trace)

__all__ = [
    "Counter", "DEFAULT_BUCKETS", "Gauge", "Histogram", "MetricsRegistry",
    "NULL_TRACER", "Reservoir", "Tracer", "validate_chrome_trace",
    "bind_engine_metrics", "bind_gateway_metrics",
]
