"""Public wrapper for the fused IVF index scan, routed through the
kernel registry (``repro.kernels.registry``).

The routing decision (Pallas vs reference, tile sizes, the small-index
fallback) lives *outside* the jit boundary so the registry's fallback
counter and one-time warning fire per call — or, when this frontend is
traced inside an outer jit, once per traced shape.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import registry
from repro.kernels.common import query_tile
from repro.kernels.ivf_scan import kernel as _k
from repro.kernels.ivf_scan import ref as _ref

# Below this many IVF lists the Pallas kernel cannot tile profitably
# (tile_c would degenerate to the whole centroid table and the grid to a
# single program), so ``backend="pallas"`` transparently routes to the
# reference scan — loudly, via registry.record_fallback, so benchmarks
# that sweep tiny indexes know their "pallas" numbers are ref numbers.
PALLAS_MIN_NLIST = 128

_jit_ref = jax.jit(_ref.ref_ivf_scan, static_argnames=("nprobe",))


def ivf_index_scan(queries, centroids, nprobe: int,
                   spec: Optional[registry.KernelSpec] = None,
                   backend: Optional[str] = None,
                   interpret: Optional[bool] = None
                   ) -> Tuple[jax.Array, jax.Array]:
    """Select the nprobe closest IVF lists per query (ChamVS.idx).

    queries [nq, D], centroids [nlist, D] -> (dists, list_ids)
    [nq, nprobe]. ``backend=``/``interpret=`` are deprecated aliases for
    ``spec=KernelSpec(...)``."""
    spec = registry.resolve("ivf_index_scan", spec, backend, interpret)
    nq = queries.shape[0]
    nlist = centroids.shape[0]
    if spec.backend == "pallas":
        if nlist < PALLAS_MIN_NLIST:
            registry.record_fallback(
                "ivf_index_scan",
                f"nlist={nlist} < PALLAS_MIN_NLIST={PALLAS_MIN_NLIST}",
                spec)
        else:
            tile, nq_pad = query_tile(nq, spec.tile_q or 8)
            q = jnp.pad(queries, ((0, nq_pad - nq), (0, 0)))
            d, i = _k.ivf_scan(q, centroids, nprobe, tile_q=tile,
                               tile_c=spec.pick_tile_c(nlist),
                               interpret=spec.use_interpret())
            return d[:nq], i[:nq]
    return _jit_ref(queries, centroids, nprobe=nprobe)
