"""IVF-PQ in pure JAX — the algorithmic substrate of ChamVS (paper §2.2, §4).

Implements the full index lifecycle:
  * training (coarse k-means quantizer + per-subspace PQ codebooks),
  * encoding (optionally residual, as in Faiss IVFPQ and the paper's
    per-IVF-list lookup tables),
  * the padded-list physical layout the accelerator scans (paper §4.3:
    each memory node holds an equal slice of *every* IVF list, physically
    contiguous, no pointer chasing),
  * a reference search pipeline (`search_ref`) that is the oracle for the
    Pallas kernels and doubles as the paper's CPU-flavor baseline.

All search-time functions are jit-compatible with static shapes; index
construction is host-side (numpy allowed) as in any real system.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.kmeans import kmeans, _pairwise_sq_l2


@dataclasses.dataclass(frozen=True)
class IVFPQConfig:
    """Static description of an IVF-PQ index (paper Table 1 symbols)."""

    dim: int                 # D — vector dimensionality
    nlist: int               # number of IVF lists (clusters)
    m: int                   # PQ sub-spaces (bytes per code at nbits=8)
    nbits: int = 8           # bits per sub-quantizer: 8 (paper) or 4 (fast-scan)
    residual: bool = True    # encode residual to coarse centroid (Faiss default)
    list_cap: int = 128      # per-shard padded capacity of each IVF list

    @property
    def ksub(self) -> int:
        return 1 << self.nbits

    @property
    def dsub(self) -> int:
        assert self.dim % self.m == 0, f"dim {self.dim} % m {self.m} != 0"
        return self.dim // self.m

    def db_bytes_per_vector(self) -> float:
        """PQ code + vector-ID footprint (paper Table 3 'PQ and vec ID')."""
        return self.m * self.nbits / 8 + 4


class IVFPQParams(NamedTuple):
    """Learned quantizers (replicated or model-sharded at serve time)."""

    coarse_centroids: jnp.ndarray   # [nlist, D] f32
    codebooks: jnp.ndarray          # [m, ksub, dsub] f32


class IVFPQShard(NamedTuple):
    """One memory node's slice of the database (paper partition scheme 1).

    Every list is padded to `cap` entries so all shapes are static; `list_len`
    carries the valid prefix length. The flat [nlist, cap, m] layout is the
    physical-address-space analogue of the paper's §4.3 memory management.
    """

    codes: jnp.ndarray      # [nlist, cap, m] uint8 (values < ksub)
    ids: jnp.ndarray        # [nlist, cap] int32 (global vector ids, -1 = pad)
    list_len: jnp.ndarray   # [nlist] int32


def train_ivfpq(
    key: jax.Array,
    train_vecs: jnp.ndarray,
    cfg: IVFPQConfig,
    kmeans_iters: int = 15,
) -> IVFPQParams:
    """Train coarse quantizer + PQ codebooks (host-side, one-off)."""
    kc, kp = jax.random.split(key)
    train_vecs = jnp.asarray(train_vecs, jnp.float32)
    coarse, assign = kmeans(kc, train_vecs, cfg.nlist, iters=kmeans_iters)
    if cfg.residual:
        target = train_vecs - coarse[assign]
    else:
        target = train_vecs
    sub = target.reshape(-1, cfg.m, cfg.dsub)            # [n, m, dsub]
    keys = jax.random.split(kp, cfg.m)
    # vmap over sub-spaces: independent k-means per sub-quantizer.
    cb, _ = jax.vmap(lambda k, x: kmeans(k, x, cfg.ksub, iters=kmeans_iters))(
        keys, jnp.swapaxes(sub, 0, 1)
    )
    return IVFPQParams(coarse_centroids=coarse, codebooks=cb)


@jax.jit
def assign_coarse(params: IVFPQParams, vecs: jnp.ndarray) -> jnp.ndarray:
    return jnp.argmin(_pairwise_sq_l2(vecs, params.coarse_centroids), axis=-1)


def encode(params: IVFPQParams, vecs: jnp.ndarray, cfg: IVFPQConfig,
           assign: Optional[jnp.ndarray] = None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """PQ-encode vectors. Returns (codes [n, m] uint8, coarse assignment [n])."""
    vecs = jnp.asarray(vecs, jnp.float32)
    if assign is None:
        assign = assign_coarse(params, vecs)
    target = vecs - params.coarse_centroids[assign] if cfg.residual else vecs
    sub = jnp.swapaxes(target.reshape(-1, cfg.m, cfg.dsub), 0, 1)  # [m, n, dsub]
    codes = jax.vmap(lambda x, c: jnp.argmin(_pairwise_sq_l2(x, c), axis=-1))(
        sub, params.codebooks
    )                                                    # [m, n]
    return codes.T.astype(jnp.uint8), assign


def list_sizes(params: IVFPQParams, vecs: np.ndarray, nlist: int,
               batch: int = 65536) -> np.ndarray:
    """[nlist] vectors per IVF list under the coarse quantizer."""
    counts = np.zeros((nlist,), np.int64)
    for s in range(0, vecs.shape[0], batch):
        a = np.asarray(assign_coarse(params, jnp.asarray(vecs[s:s + batch])))
        counts += np.bincount(a, minlength=nlist)
    return counts


def build_shards(
    params: IVFPQParams,
    vecs: np.ndarray,
    cfg: IVFPQConfig,
    num_shards: int,
    start_id: int = 0,
    encode_batch: int = 65536,
) -> list[IVFPQShard]:
    """Host-side index build: encode, bucket by list, stripe each list evenly
    across shards (paper's balanced partitioning), pad to `cfg.list_cap`.

    Raises if any per-shard list slice exceeds capacity — capacity is a
    deployment parameter, overflow is a config error, not data loss.
    """
    n = vecs.shape[0]
    all_codes = np.empty((n, cfg.m), np.uint8)
    all_assign = np.empty((n,), np.int64)
    for s in range(0, n, encode_batch):
        e = min(n, s + encode_batch)
        c, a = encode(params, jnp.asarray(vecs[s:e]), cfg)
        all_codes[s:e] = np.asarray(c)
        all_assign[s:e] = np.asarray(a)
    ids = np.arange(start_id, start_id + n, dtype=np.int32)

    order = np.argsort(all_assign, kind="stable")
    sorted_codes, sorted_ids = all_codes[order], ids[order]
    sorted_assign = all_assign[order]
    list_starts = np.searchsorted(sorted_assign, np.arange(cfg.nlist))
    list_ends = np.searchsorted(sorted_assign, np.arange(cfg.nlist) + 1)

    shards = []
    for sh in range(num_shards):
        codes = np.zeros((cfg.nlist, cfg.list_cap, cfg.m), np.uint8)
        sids = np.full((cfg.nlist, cfg.list_cap), -1, np.int32)
        lens = np.zeros((cfg.nlist,), np.int32)
        for li in range(cfg.nlist):
            s, e = list_starts[li], list_ends[li]
            # stripe: shard `sh` takes elements sh, sh+num_shards, ...
            sl = slice(s + sh, e, num_shards)
            chunk_codes = sorted_codes[sl]
            chunk_ids = sorted_ids[sl]
            ln = len(chunk_ids)
            if ln > cfg.list_cap:
                raise ValueError(
                    f"list {li} shard {sh}: {ln} codes > cap {cfg.list_cap}; "
                    f"raise IVFPQConfig.list_cap"
                )
            codes[li, :ln] = chunk_codes
            sids[li, :ln] = chunk_ids
            lens[li] = ln
        shards.append(IVFPQShard(jnp.asarray(codes), jnp.asarray(sids), jnp.asarray(lens)))
    return shards


# ---------------------------------------------------------------------------
# Search-time pieces (jit-compatible, static shapes)
# ---------------------------------------------------------------------------

def scan_ivf_index(params: IVFPQParams, queries: jnp.ndarray, nprobe: int
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """ChamVS.idx — brute-force centroid scan + top-nprobe (paper step 2).

    Returns (probe_dists [nq, nprobe], probe_ids [nq, nprobe])."""
    d = _pairwise_sq_l2(queries, params.coarse_centroids)     # [nq, nlist]
    neg, idx = jax.lax.top_k(-d, nprobe)
    return -neg, idx


def compute_luts(params: IVFPQParams, queries: jnp.ndarray,
                 probe_ids: jnp.ndarray, cfg: IVFPQConfig) -> jnp.ndarray:
    """Distance lookup tables (paper Fig. 2 step 5 / Fig. 4 unit 2).

    Residual PQ -> one LUT per (query, probed list): [nq, nprobe, m, ksub].
    Non-residual -> LUT independent of the list; broadcast to the same shape
    so downstream code is uniform.
    """
    nq, nprobe = probe_ids.shape
    cb = params.codebooks                                     # [m, ksub, dsub]
    cb2 = jnp.sum(cb * cb, axis=-1)                           # [m, ksub]
    if cfg.residual:
        res = queries[:, None, :] - params.coarse_centroids[probe_ids]  # [nq,np,D]
        sub = res.reshape(nq, nprobe, cfg.m, cfg.dsub)
        # ||sub - cb||^2 = ||sub||^2 - 2 sub.cb + ||cb||^2 (matmul form —
        # the broadcast-difference form materializes an [nq,np,m,ksub,dsub]
        # tensor, 8.6 GB/device at serve scale; EXPERIMENTS.md §Perf it. 3)
        x2 = jnp.sum(sub * sub, axis=-1)                      # [nq, np, m]
        xc = jnp.einsum("qpmd,mkd->qpmk", sub, cb)            # MXU
        return x2[..., None] - 2.0 * xc + cb2[None, None]
    sub = queries.reshape(nq, cfg.m, cfg.dsub)
    x2 = jnp.sum(sub * sub, axis=-1)                          # [nq, m]
    xc = jnp.einsum("qmd,mkd->qmk", sub, cb)
    lut = x2[..., None] - 2.0 * xc + cb2[None]                # [nq, m, ksub]
    return jnp.broadcast_to(lut[:, None], (nq, nprobe, cfg.m, cfg.ksub))


def adc_scan_ref(lut: jnp.ndarray, codes: jnp.ndarray) -> jnp.ndarray:
    """Asymmetric distance computation — gather formulation (the oracle).

    lut: [..., m, ksub] f32, codes: [..., n, m] uint8 -> [..., n] f32.
    This is exactly the paper's PQ decoding unit semantics: per byte, use the
    code as an address into the LUT column, then sum across the m sub-spaces.
    """
    gathered = jnp.take_along_axis(
        jnp.moveaxis(lut, -2, -1)[..., None, :, :],           # [..., 1, ksub, m]
        codes[..., None, :].astype(jnp.int32),                # [..., n, 1, m]
        axis=-2,
    )                                                         # [..., n, 1, m]
    return jnp.sum(gathered[..., 0, :], axis=-1)


def search_shard_ref(
    params: IVFPQParams,
    shard: IVFPQShard,
    queries: jnp.ndarray,
    probe_ids: jnp.ndarray,
    cfg: IVFPQConfig,
    k: int,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Reference per-shard search: LUT -> gather probed lists -> ADC -> top-k.

    Returns (dists [nq, k], ids [nq, k]) — this shard's candidates."""
    nq, nprobe = probe_ids.shape
    lut = compute_luts(params, queries, probe_ids, cfg)       # [nq,np,m,ksub]
    codes = shard.codes[probe_ids]                            # [nq,np,cap,m]
    ids = shard.ids[probe_ids]                                # [nq,np,cap]
    valid = (jnp.arange(cfg.list_cap)[None, None, :]
             < shard.list_len[probe_ids][..., None])          # [nq,np,cap]
    d = adc_scan_ref(lut, codes)                              # [nq,np,cap]
    d = jnp.where(valid, d, jnp.inf)
    flat_d = d.reshape(nq, -1)
    flat_i = ids.reshape(nq, -1)
    neg, pos = jax.lax.top_k(-flat_d, k)
    return -neg, jnp.take_along_axis(flat_i, pos, axis=-1)


def merge_topk(dists: jnp.ndarray, ids: jnp.ndarray, k: int
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """K-way merge of per-shard candidates (paper step 8, CPU aggregation).

    dists/ids: [num_shards, nq, kk] -> ([nq, k], [nq, k]). The merge
    itself is first-class in ``repro.retrieval.merge`` (which also has
    the hierarchical tree variant); this delegates to the flat form."""
    from repro.retrieval.merge import flat_merge
    return flat_merge(dists, ids, k)


def exact_search(vecs: jnp.ndarray, queries: jnp.ndarray, k: int
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Exact brute-force nearest neighbors — ground truth for recall@K."""
    d = _pairwise_sq_l2(queries, vecs)
    neg, idx = jax.lax.top_k(-d, k)
    return -neg, idx


def recall_at_k(found_ids: jnp.ndarray, true_ids: jnp.ndarray) -> float:
    """R@K: overlap between returned and exact top-K (paper §2.2)."""
    hits = (found_ids[:, :, None] == true_ids[:, None, :]).any(-1).sum(-1)
    return float(jnp.mean(hits / true_ids.shape[-1]))
