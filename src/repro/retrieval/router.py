"""Shard placement + query broadcast / result gather over the retrieval
mesh (paper steps 3-9, the coordinator's network fabric).

This replaces the ad-hoc ``make_distributed_search`` /
``make_distributed_gather`` pair that lived in ``core/chamvs.py``:

  * ``build_search(mesh, cfg, ...)`` — the in-graph distributed search
    (query all-gather -> per-shard scan -> truncated-survivor all-gather
    -> exact merge), unchanged semantics;
  * ``build_gather(mesh, axes)`` — id -> payload conversion against a
    fully sharded table without the full-table all-gather;
  * ``ShardRouter`` — the object form: owns the mesh, the placement of
    quantizers / DB shards / payload tables, and the jitted search and
    gather callables, so callers stop re-deriving shard counts and
    ``PartitionSpec``s at every site.

``core/chamvs.py`` keeps deprecated wrappers for the two builders.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.compat import shard_map, use_mesh
from repro.core.chamvs import (ChamVSConfig, probe_lists, shard_search,
                               stack_shards)
from repro.core.ivfpq import IVFPQParams, IVFPQShard
from repro.kernels.chamvs_scan.ops import fused_shard_scan


def num_db_shards(mesh: Mesh, db_axes: Tuple[str, ...]) -> int:
    """Memory-node count = product of the db mesh axes present."""
    n = 1
    for a in db_axes:
        if a in mesh.axis_names:
            n *= mesh.shape[a]
    return n


def build_search(
    mesh: Mesh,
    cfg: ChamVSConfig,
    db_axes: Tuple[str, ...] = ("data",),
    query_axis: Optional[str] = "model",
    nq: Optional[int] = None,
):
    """Build the in-graph distributed search fn for ``mesh``.

    Returns ``search(params, stacked_shard, queries) -> (dists, ids)`` with
    replicated outputs [nq, K]. ``stacked_shard`` must carry a leading shard
    axis of size prod(mesh[a] for a in db_axes).

    Work split over ``query_axis`` (the TP columns of each DB shard row):
      * query-split — each column searches nq/qsize queries (batch serving);
      * probe-split — when nq is not divisible (e.g. long-context batch 1),
        each column scans nprobe/qsize of every query's probed lists; the
        merge then spans shards x columns (more, shorter L1 queues — the
        paper's Fig. 8 regime).
    """
    db_axes = tuple(a for a in db_axes if a in mesh.axis_names)
    num_shards = num_db_shards(mesh, db_axes)
    qa = query_axis if (query_axis and query_axis in mesh.axis_names) else None
    qsize = mesh.shape[qa] if qa else 1
    probe_split = bool(qa) and nq is not None and (
        nq % qsize != 0 and cfg.nprobe % qsize == 0)
    producers = num_shards * (qsize if probe_split else 1)
    kk = cfg.k_prime(producers)

    def body(params: IVFPQParams, shard: IVFPQShard, queries: jnp.ndarray):
        # shard: leading axis length 1 on this device; queries: [nq_local, D]
        nq_local = queries.shape[0]
        probe_ids = probe_lists(params, queries, cfg)
        if probe_split:
            npl = cfg.nprobe // qsize
            col = jax.lax.axis_index(qa)
            probe_ids = jax.lax.dynamic_slice_in_dim(
                probe_ids, col * npl, npl, axis=1)
        if cfg.fused:
            # the same one-dispatch scan the local pipeline runs, over
            # this memory node's one-shard stack
            d, i = fused_shard_scan(params, shard, queries, probe_ids,
                                    cfg, kk)
            d, i = d[0], i[0]
        else:
            local = jax.tree.map(lambda x: x[0], shard)
            d, i = shard_search(params, local, queries, probe_ids, cfg, kk)
        # aggregate over memory nodes (paper step 7-8): gather the kk
        # survivors of every producer, then exact-merge.
        gather_axes = db_axes + ((qa,) if probe_split else ())
        if gather_axes:
            d = jax.lax.all_gather(d, gather_axes, axis=0, tiled=False)
            i = jax.lax.all_gather(i, gather_axes, axis=0, tiled=False)
            d = d.reshape(producers, nq_local, kk)
            i = i.reshape(producers, nq_local, kk)
            d = d.transpose(1, 0, 2).reshape(nq_local, producers * kk)
            i = i.transpose(1, 0, 2).reshape(nq_local, producers * kk)
        neg, pos = jax.lax.top_k(-d, min(cfg.k, d.shape[-1]))
        out_d = -neg
        out_i = jnp.take_along_axis(i, pos, axis=1)
        # un-split the query batch (it was sharded over the TP axis)
        if qa and not probe_split:
            out_d = jax.lax.all_gather(out_d, qa, axis=0, tiled=True)
            out_i = jax.lax.all_gather(out_i, qa, axis=0, tiled=True)
        return out_d, out_i

    shard_spec = IVFPQShard(
        codes=P(db_axes if db_axes else None),
        ids=P(db_axes if db_axes else None),
        list_len=P(db_axes if db_axes else None),
    )
    q_spec = P(qa) if (qa and not probe_split) else P()
    in_specs = (
        IVFPQParams(P(), P()),    # quantizers replicated (paper: metadata)
        shard_spec,
        q_spec,
    )
    out_specs = (P(), P())

    fn = shard_map(body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                   check_vma=False)

    def search(params: IVFPQParams, stacked: IVFPQShard, queries: jnp.ndarray):
        n = queries.shape[0]
        if qa and not probe_split:
            assert n % qsize == 0, (n, qsize)
        return fn(params, stacked, queries)

    search.query_spec = q_spec      # how the queries enter the mesh
    return search


def build_gather(mesh: Mesh, table_axes: Tuple[str, ...]):
    """ID -> payload conversion against a fully sharded table (paper step 9).

    ``table`` [N, ...] is sharded over ``table_axes``; ``ids`` [B, K] are
    replicated. A naive ``table[ids]`` makes GSPMD all-gather the whole
    table (measured 4 GB/step for the 1e9-entry token table —
    EXPERIMENTS.md §Perf iteration 2); instead each shard gathers the ids
    that fall in its range and a psum of the masked results (KB-scale)
    assembles the answer."""
    axes = tuple(a for a in table_axes if a in mesh.axis_names)

    def body(table, ids):
        # flattened shard index over `axes` (row-major over the mesh dims)
        idx = jnp.zeros((), jnp.int32)
        for a in axes:
            idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
        nloc = table.shape[0]
        lo = idx * nloc
        rel = ids - lo
        hit = (rel >= 0) & (rel < nloc)
        vals = table[jnp.clip(rel, 0, nloc - 1)]
        mask = hit.reshape(hit.shape + (1,) * (vals.ndim - hit.ndim))
        vals = jnp.where(mask, vals, 0)
        return jax.lax.psum(vals, axes)

    return shard_map(
        body, mesh=mesh,
        in_specs=(P(axes), P()), out_specs=P(), check_vma=False)


class ShardRouter:
    """Placement + broadcast/gather for one retrieval mesh.

    Owns what every distributed call site used to re-derive by hand:
    the memory-node count, the ``PartitionSpec`` of each table, and the
    jitted search/gather callables. ``DistributedRetriever`` and the
    distributed ``RetrievalService`` pipeline are thin layers over this.
    """

    def __init__(self, mesh: Mesh, cfg: ChamVSConfig,
                 db_axes: Tuple[str, ...] = ("data",),
                 query_axis: Optional[str] = "model",
                 nq: Optional[int] = None):
        self.mesh = mesh
        self.cfg = cfg
        self.db_axes = tuple(a for a in db_axes if a in mesh.axis_names)
        self.num_shards = num_db_shards(mesh, db_axes)
        # query-split constraint: batches must divide evenly over the TP
        # columns (callers that batch dynamically pad to this multiple)
        qa = query_axis if (query_axis and
                            query_axis in mesh.axis_names) else None
        self.query_size = mesh.shape[qa] if qa else 1
        search = build_search(mesh, cfg, db_axes=db_axes,
                              query_axis=query_axis, nq=nq)
        self._query_sharding = NamedSharding(mesh, search.query_spec)
        self._replicated = NamedSharding(mesh, P())
        self._search = jax.jit(search)
        self._gather = jax.jit(build_gather(mesh, db_axes))

    # -- placement ----------------------------------------------------------

    def place_params(self, params: IVFPQParams) -> IVFPQParams:
        """Quantizers are metadata: replicated on every memory node."""
        return jax.device_put(params, NamedSharding(self.mesh, P()))

    def place_shards(self, shards: List[IVFPQShard]) -> IVFPQShard:
        """One DB shard per memory node along the db axes."""
        if len(shards) != self.num_shards:
            raise ValueError(
                f"one shard per memory node: {len(shards)} shards vs "
                f"{self.num_shards} nodes")
        return jax.device_put(stack_shards(shards),
                              NamedSharding(self.mesh, P(self.db_axes)))

    def place_table(self, table: Optional[jnp.ndarray]
                    ) -> Optional[jnp.ndarray]:
        """Place a payload table across the memory nodes (pad the trailing
        rows so every node holds an equal slice; padded rows are never
        addressed because ids < N)."""
        if table is None:
            return None
        n = table.shape[0]
        rem = (-n) % self.num_shards
        if rem:
            pad = [(0, rem)] + [(0, 0)] * (table.ndim - 1)
            table = jnp.pad(table, pad)
        return jax.device_put(table,
                              NamedSharding(self.mesh, P(self.db_axes)))

    # -- execution ----------------------------------------------------------
    #
    # The retrieval mesh is its own device set (disaggregated serving puts
    # the LM on other chips), so every call hands its operands over
    # explicitly: in to the mesh, and the results back to the devices
    # the caller's array lived on.

    @staticmethod
    def _home(x) -> Optional[jax.sharding.Sharding]:
        return x.sharding if isinstance(x, jax.Array) else None

    def search(self, params: IVFPQParams, stacked: IVFPQShard,
               queries: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
        home = self._home(queries)
        q = jax.device_put(queries, self._query_sharding)
        with use_mesh(self.mesh):
            out = self._search(params, stacked, q)
        return out if home is None else jax.device_put(out, home)

    def gather(self, table: jnp.ndarray, ids: jnp.ndarray) -> jnp.ndarray:
        home = self._home(ids)
        i = jax.device_put(ids, self._replicated)
        with use_mesh(self.mesh):
            out = self._gather(table, i)
        return out if home is None else jax.device_put(out, home)
