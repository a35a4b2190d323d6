"""Public wrapper for approximate hierarchical top-k selection, routed
through the kernel registry (``repro.kernels.registry``).

Degenerate tiles (``n % num_blocks != 0`` or blocks shorter than the
truncated queue) cannot be served by the hierarchical kernel and route
to the *exact* reference path. That fallback used to be silent — a
benchmark sweeping such shapes reported ref numbers as "pallas" — so it
now goes through ``registry.record_fallback`` like every other
pallas->ref route (counted, warned once, or raised under
``fallback="error"``).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.approx_topk_math import truncated_queue_len
from repro.kernels import registry
from repro.kernels.topk import kernel as _k
from repro.kernels.topk import ref as _ref

_jit_exact = jax.jit(_ref.ref_exact_topk, static_argnames=("k",))
_jit_ref_hier = jax.jit(_ref.ref_hierarchical_topk,
                        static_argnames=("k", "num_blocks", "k_prime"))


def approx_topk(
    d: jnp.ndarray,
    k: int,
    num_blocks: int = 16,
    k_prime: Optional[int] = None,
    eps: float = 0.01,
    spec: Optional[registry.KernelSpec] = None,
    backend: Optional[str] = None,
    interpret: Optional[bool] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """k smallest per row with truncated level-1 queues (paper §4.2.2).

    If ``k_prime`` is None it is sized by the paper's binomial bound so
    that at most ``eps`` of queries differ from exact top-k.
    ``num_blocks`` is the number of level-1 producers (grid blocks).
    ``backend="exact"`` (legacy alias) selects the exact reference path
    directly. ``backend=``/``interpret=`` are deprecated aliases for
    ``spec=KernelSpec(...)``."""
    exact = backend == "exact"
    if exact:
        backend = "ref"
    spec = registry.resolve("approx_topk", spec, backend, interpret)
    B, n = d.shape
    if k_prime is None:
        k_prime = truncated_queue_len(k, num_blocks, eps)
    k_prime = min(max(k_prime, 1), k)
    # degenerate tiles: every level-1 block must hold >= k' candidates
    if n % num_blocks != 0 or n // num_blocks < k_prime:
        if spec.backend == "pallas":
            registry.record_fallback(
                "approx_topk",
                f"degenerate tiling n={n}, num_blocks={num_blocks}, "
                f"k'={k_prime} (need n % num_blocks == 0 and "
                "n // num_blocks >= k')", spec)
        return _jit_exact(d, k=k)
    if exact:
        return _jit_exact(d, k=k)
    if spec.backend == "pallas":
        return _k.hierarchical_topk(d, k, k_prime, num_blocks,
                                    row_tile=spec.pick_tile_q(B),
                                    interpret=spec.use_interpret())
    return _jit_ref_hier(d, k=k, num_blocks=num_blocks, k_prime=k_prime)
