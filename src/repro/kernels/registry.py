"""Unified kernel-spec layer for the ChamVS Pallas kernels.

Before this module existed, each kernel package (``ivf_scan``,
``pq_adc``, ``topk``) carried its own copy-pasted frontend with a
``backend=``/``interpret=`` kwarg pair, its own tile heuristics, and —
worst — its own fallback behavior: ``ivf_scan`` kept a module-global
"warned once" flag that leaked across tests, while ``approx_topk``
silently returned the exact reference path on degenerate tiles, so
"pallas" benchmark numbers could quietly be ref numbers.

``KernelSpec`` is now the single description of *how* a kernel should
run, and this module owns the shared policy around it:

  * **tile heuristics** — the `pick_*` methods reproduce (and replace)
    the per-frontend divisor searches, overridable per spec;
  * **fallback accounting** — every time a frontend routes a "pallas"
    request to a reference path it calls :func:`record_fallback`, which
    bumps a per-op counter and (policy permitting) warns once per op.
    Benchmarks read :func:`fallback_count` so ref numbers can never
    masquerade as Pallas numbers;
  * **test-resettable one-time state** — :func:`reset_warnings` clears
    the warned-set and the counters; the test suite installs it as an
    autouse fixture so "warn once per process" becomes "once per test"
    instead of leaking between tests.

NOTE on jit: frontends make their routing decision from *static* shapes
and the (hashable, static) spec. When a frontend is called inside an
outer ``jax.jit`` (e.g. the retrieval service's scan stage), the
decision — and therefore the fallback warning/counter — runs at trace
time, once per traced shape. Counters therefore count *routing
decisions*, not dispatches; the retrieval service's ``scan_dispatches``
counter is the per-dispatch ground truth.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Dict, Optional, Tuple

import jax

_BACKENDS = ("pallas", "ref", "einsum")
_FALLBACK_POLICIES = ("warn", "silent", "error")


class KernelFallbackError(RuntimeError):
    """Raised when ``fallback="error"`` and a Pallas request cannot be
    served by the Pallas kernel (deployment configs that must never
    silently serve reference-path numbers)."""


@dataclasses.dataclass(frozen=True)
class KernelSpec:
    """How a ChamVS kernel call should execute.

    Hashable and frozen, so it can ride through ``jax.jit`` as a static
    argument (``ChamVSConfig`` embeds one per search config, the serve
    ``RalmEngine`` one per deployment for decode attention).

    ``backend="einsum"`` exists for ``decode_attn`` only: the legacy
    full-materialization einsum path kept as the parity oracle. The
    ChamVS frontends treat any non-"pallas" backend as "ref"."""

    backend: str = "pallas"        # "pallas" | "ref" | "einsum"
    interpret: Optional[bool] = None  # Pallas interpret mode; None =
    #                                   derived from the platform (on only
    #                                   where the default backend is CPU)
    tile_q: Optional[int] = None   # query-tile rows (None = heuristic)
    tile_n: Optional[int] = None   # scan-axis tile (None = heuristic)
    tile_c: Optional[int] = None   # centroid-tile cols (None = heuristic)
    fallback: str = "warn"         # "warn" | "silent" | "error"

    def __post_init__(self) -> None:
        if self.backend not in _BACKENDS:
            raise ValueError(f"unknown backend {self.backend!r}; "
                             f"expected one of {_BACKENDS}")
        if self.fallback not in _FALLBACK_POLICIES:
            raise ValueError(f"unknown fallback policy {self.fallback!r}; "
                             f"expected one of {_FALLBACK_POLICIES}")

    def use_interpret(self) -> bool:
        """Interpret mode for this call: the explicit setting, else on
        exactly when JAX's default backend is the CPU (Pallas only
        interprets there; on an accelerator it compiles)."""
        if self.interpret is not None:
            return self.interpret
        return on_cpu()

    # -- tile heuristics (the old per-frontend divisor searches) ------------

    @staticmethod
    def _divisor_at_most(n: int, want: int) -> int:
        """Largest divisor of ``n`` that is <= ``want`` (>= 1). The grid
        kernels require tiles to divide their axis exactly, so explicit
        overrides are rounded down to a legal tile instead of tripping
        the kernels' shape asserts."""
        t = max(1, min(want, n))
        while n % t:
            t -= 1
        return t

    def pick_tile_q(self, nq: int) -> int:
        """Query-tile rows: largest of 8/4/1 dividing the batch."""
        if self.tile_q is not None:
            return self._divisor_at_most(nq, self.tile_q)
        return 8 if nq % 8 == 0 else (4 if nq % 4 == 0 else 1)

    def pick_tile_c(self, nlist: int) -> int:
        """Centroid-tile columns for the IVF scan grid."""
        if self.tile_c is not None:
            return self._divisor_at_most(nlist, self.tile_c)
        return 512 if nlist % 512 == 0 else (128 if nlist % 128 == 0
                                             else nlist)

    def pick_tile_n(self, n: int) -> int:
        """Scan-axis tile for the streaming ADC kernels."""
        tile = self.tile_n if self.tile_n is not None else 512
        return min(tile, max(128, n))

    def pick_block_seq(self, s: int) -> int:
        """KV-block length for the streaming decode-attention kernel:
        the largest divisor of the cache seq axis <= ``tile_n`` (default
        128 — one pool seq-alignment quantum). The grid streams one such
        block per step, so this is also the skip granularity."""
        want = self.tile_n if self.tile_n is not None else 128
        return self._divisor_at_most(s, want)

    def with_overrides(self, backend: Optional[str] = None,
                       interpret: Optional[bool] = None) -> "KernelSpec":
        """Copy with backend/interpret overridden (``None`` keeps)."""
        if backend is None and interpret is None:
            return self
        return dataclasses.replace(
            self,
            backend=backend if backend is not None else self.backend,
            interpret=interpret if interpret is not None else self.interpret)


#: the two specs almost every call site wants
REF = KernelSpec(backend="ref")
PALLAS = KernelSpec(backend="pallas")        # interpret derived per call
PALLAS_INTERPRET = KernelSpec(backend="pallas", interpret=True)


def on_cpu() -> bool:
    """True where JAX's default backend is the CPU (tests, this repo's
    CPU containers). Asked at call time, never at import."""
    return jax.default_backend() == "cpu"


def serving_spec(backend: Optional[str] = None) -> KernelSpec:
    """``KernelSpec`` of one serving-path kernel. With no ``backend``
    override the platform decides: the compiled Pallas kernels with
    ``fallback="error"`` on an accelerator, so a route to a reference
    path raises; the reference paths on a CPU host, where Pallas could
    only interpret (and a requested Pallas route warns on fallback, as
    the tests expect)."""
    cpu = on_cpu()
    return KernelSpec(backend=backend or ("ref" if cpu else "pallas"),
                      fallback="warn" if cpu else "error")


# ---------------------------------------------------------------------------
# one-time warnings + fallback counters (module-level, test-resettable)
# ---------------------------------------------------------------------------

_warned: set = set()
_fallbacks: Dict[str, int] = {}


def reset_warnings() -> None:
    """Clear the warned-once set and the fallback counters. The test
    suite calls this between tests (autouse fixture in conftest), so no
    module-global flag can leak warning state across tests again."""
    _warned.clear()
    _fallbacks.clear()


def fallback_count(op: Optional[str] = None) -> int:
    """Pallas->ref routing decisions recorded since the last reset —
    for one op, or in total. Benchmarks assert this is 0 before tagging
    a number 'pallas'."""
    if op is not None:
        return _fallbacks.get(op, 0)
    return sum(_fallbacks.values())


def fallback_counts() -> Dict[str, int]:
    """Per-op copy of the fallback counters — the gateway's ``/statsz``
    and the ``/metricsz`` adapter export this so degraded kernel routing
    is visible in production, not just under pytest."""
    return dict(_fallbacks)


def warn_once(key: Tuple, message: str, category=RuntimeWarning,
              stacklevel: int = 3) -> None:
    """Emit ``message`` once per ``key`` per process (or per
    ``reset_warnings`` interval)."""
    if key in _warned:
        return
    _warned.add(key)
    warnings.warn(message, category, stacklevel=stacklevel)


def record_fallback(op: str, reason: str,
                    spec: Optional[KernelSpec] = None) -> None:
    """A frontend routed a ``backend="pallas"`` request to a reference
    path. Count it, and warn/raise per the spec's fallback policy."""
    policy = spec.fallback if spec is not None else "warn"
    if policy == "error":
        raise KernelFallbackError(f"{op}: {reason}")
    _fallbacks[op] = _fallbacks.get(op, 0) + 1
    if policy == "warn":
        warn_once(
            (op, "fallback"),
            f"{op}: backend='pallas' requested but {reason}; falling back "
            "to the reference path (benchmark numbers for this shape are "
            "NOT Pallas numbers). Warned once per op per process; see "
            "repro.kernels.registry.fallback_count().",
            RuntimeWarning, stacklevel=4)


def resolve(op: str, spec: Optional[KernelSpec],
            backend: Optional[str] = None,
            interpret: Optional[bool] = None,
            default: KernelSpec = PALLAS) -> KernelSpec:
    """Fold a frontend's arguments into one ``KernelSpec``.

    ``spec`` wins when given; the legacy ``backend=``/``interpret=``
    kwargs are deprecated aliases that override on top of it (warning
    once per op). A bare string in the ``spec`` slot is a legacy
    *positional* backend (the old signatures had ``backend`` where
    ``spec`` now sits) — honored with the same deprecation warning
    rather than crashing on ``'str'.backend`` downstream."""
    if isinstance(spec, str):
        backend = spec if backend is None else backend
        spec = None
    out = spec if spec is not None else default
    if backend is None and interpret is None:
        return out
    warn_once(
        (op, "deprecated-kwargs"),
        f"{op}: the backend=/interpret= kwargs are deprecated; pass "
        "spec=repro.kernels.registry.KernelSpec(...) instead (see "
        "docs/kernels.md for the migration table).",
        DeprecationWarning, stacklevel=4)
    return out.with_overrides(backend, interpret)
