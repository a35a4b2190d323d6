"""The kNN-LM datastore, made on the device from the seed.

A cell's datastore stands for one chip's share of a paper Table 3
deployment (SYN-512: 2^30 vectors over 64 memory nodes; SYN-1024 over
128). Making it by running the model over 2^24 prefixes would cost
minutes of set-up, so it is drawn instead:

1. coarse centroids are the model's own hidden states at ``nlist``
   seeded prefixes, and the PQ codebooks come from a few k-means steps
   over their sub-vectors;
2. list lengths are a fixed set, the quantiles of a lognormal whose
   ``list_sigma`` sets how uneven the lists are, dealt to the lists in an
   order drawn from the seed. Every seed then has the same lengths and
   the same capacity (the longest list rounded up to 128 rows), so the
   same programs and the same memory. (Where the lists fall under this
   random-weight model's own hidden states is no guide: a few
   small-norm centroids are nearest to almost every state, so nearly
   all lists would be empty.);
3. each list's keys are drawn around its centroid with the spread a
   sample of hidden states shows around its nearest centroid, and
   PQ-encoded on the device block by block;
4. the tables are laid out as the program stores a shard: codes
   ``[nlist, cap, m]``, ids ``[nlist, cap]`` (-1 past a list's end),
   lengths ``[nlist]``. Vector ids are list-major.
"""
from __future__ import annotations

import functools
from statistics import NormalDist
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import reference as ref

F32 = jnp.float32
ENCODE_ROWS = 1 << 14        # keys PQ-encoded per block
SAMPLE_DOCS = 16             # documents per hidden-state forward block


def sample_hidden(lm, params, model, key, n_docs: int, doc_len: int
                  ) -> jnp.ndarray:
    """Hidden states [n_docs * doc_len, d] of seeded random documents
    under the LM module ``lm``, in blocks of ``SAMPLE_DOCS`` documents
    (bf16 matmuls)."""
    toks = jax.random.randint(key, (n_docs // SAMPLE_DOCS, SAMPLE_DOCS,
                                    doc_len), 0, model.vocab_size)
    return _sample_hidden(params, toks, model, lm)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _sample_hidden(params, toks, model, lm):
    h = jax.lax.map(lambda t: lm.forward_hidden(params, t, model,
                                                ref.SAMPLE), toks)
    return h.reshape(-1, model.d_model)


@functools.partial(jax.jit, static_argnums=(2, 3))
def train_codebooks(key, x: jnp.ndarray, m: int, ksub: int,
                    iters: int = 8) -> jnp.ndarray:
    """Per-sub-space k-means codebooks [m, ksub, dsub] over rows x [n, d]."""
    n, d = x.shape
    sub = jnp.swapaxes(x.reshape(n, m, d // m), 0, 1)         # [m, n, dsub]
    init = sub[:, jax.random.permutation(key, n)[:ksub]]       # [m, ksub, ds]

    def step(cb, _):
        dist = (jnp.sum(sub * sub, -1)[..., None]
                - 2 * jnp.einsum("mnd,mkd->mnk", sub, cb)
                + jnp.sum(cb * cb, -1)[:, None])
        a = jax.nn.one_hot(jnp.argmin(dist, -1), ksub, dtype=F32)  # [m,n,k]
        tot = jnp.einsum("mnk,mnd->mkd", a, sub)
        cnt = jnp.sum(a, 1)[..., None]
        return jnp.where(cnt > 0, tot / jnp.maximum(cnt, 1.0), cb), None

    cb, _ = jax.lax.scan(step, init, None, length=iters)
    return cb


@jax.jit
def spread(x: jnp.ndarray, centroids: jnp.ndarray) -> jnp.ndarray:
    """Mean squared distance of rows x to their nearest centroid, in
    blocks of 1024 rows."""
    c2 = jnp.sum(centroids * centroids, -1)

    def block(xb):
        d = (jnp.sum(xb * xb, -1, keepdims=True)
             - 2 * jnp.matmul(xb, centroids.T,
                              precision=jax.lax.Precision.HIGHEST) + c2)
        return jnp.min(d, -1)

    dmin = jax.lax.map(block, x.reshape(-1, 1024, x.shape[1]))
    return jnp.mean(jnp.maximum(dmin, 0.0))


def list_lengths(weights: np.ndarray, n: int) -> np.ndarray:
    """Split ``n`` vectors over the lists in proportion to ``weights``
    (largest remainders), so the lengths sum to ``n`` exactly."""
    share = weights.astype(np.float64) * n / weights.sum()
    lens = np.floor(share).astype(np.int64)
    rest = n - lens.sum()
    lens[np.argsort(-(share - lens), kind="stable")[:rest]] += 1
    return lens


def lognormal_lengths(nlist: int, n: int, sigma: float) -> np.ndarray:
    """Lengths of ``nlist`` lists holding ``n`` vectors, in proportion to
    the evenly spaced quantiles of a lognormal of shape ``sigma``."""
    z = np.array([NormalDist().inv_cdf((i + 0.5) / nlist)
                  for i in range(nlist)])
    return list_lengths(np.exp(sigma * z), n)


@functools.partial(jax.jit, static_argnums=(6, 7))
def encode_lists(key, centroids, codebooks, offsets, lens, sigma,
                 n: int, cap: int):
    """Draw every list's keys around its centroid and PQ-encode them.
    Returns codes [nlist, cap, m] uint8 and ids [nlist, cap] int32."""
    m, ksub, dsub = codebooks.shape
    d = centroids.shape[1]
    rows = -(-n // ENCODE_ROWS) * ENCODE_ROWS
    owner = jnp.clip(jnp.searchsorted(offsets, jnp.arange(rows),
                                      side="right") - 1, 0, None)
    cb2 = jnp.sum(codebooks * codebooks, -1)                    # [m, ksub]

    def block(i):
        lst = jax.lax.dynamic_slice(owner, (i * ENCODE_ROWS,), (ENCODE_ROWS,))
        noise = jax.random.normal(jax.random.fold_in(key, i),
                                  (ENCODE_ROWS, d), F32)
        x = (centroids[lst] + sigma * noise).reshape(ENCODE_ROWS, m, dsub)
        dist = cb2[None] - 2 * jnp.einsum("nmd,mkd->nmk", x, codebooks)
        return jnp.argmin(dist, -1).astype(jnp.uint8)           # [rows, m]

    packed = jax.lax.map(block, jnp.arange(rows // ENCODE_ROWS))
    packed = packed.reshape(rows, m)
    slot = jnp.arange(cap)[None, :]
    valid = slot < lens[:, None]
    idx = offsets[:, None] + slot
    codes = jnp.where(valid[..., None], packed[jnp.where(valid, idx, 0)], 0)
    ids = jnp.where(valid, idx, -1).astype(jnp.int32)
    return codes.astype(jnp.uint8), ids


def build(lm, params, model, ds: Dict, key) -> Tuple[ref.Tables, Dict]:
    """Make the cell's datastore from ``key``, sampling hidden states with
    the LM module ``lm`` (its ``Model`` is ``model``). ``ds`` is the
    configuration's ``datastore`` block. Returns the tables and a summary
    (sizes, capacity, skew) for the log."""
    k_cent, k_cb, k_len, k_keys, k_pay = jax.random.split(key, 5)
    nlist, n, m = ds["nlist"], ds["vectors"], ds["m"]
    doc_len = ds["sample_doc_len"]
    centroids = sample_hidden(lm, params, model, k_cent, nlist // doc_len,
                              doc_len)
    codebooks = train_codebooks(k_cb, centroids, m, ds["ksub"])
    sample = sample_hidden(lm, params, model, k_len,
                           ds["spread_sample_tokens"] // doc_len, doc_len)
    sigma = float(np.sqrt(float(spread(sample, centroids)) / model.d_model))
    del sample
    rng = np.random.default_rng(np.asarray(k_len).tolist())
    lens = rng.permutation(lognormal_lengths(nlist, n, ds["list_sigma"]))
    cap = max(128, -(-int(lens.max()) // 128) * 128)
    offsets = np.concatenate([[0], np.cumsum(lens)[:-1]]).astype(np.int32)
    codes, ids = encode_lists(k_keys, centroids, codebooks,
                              jnp.asarray(offsets),
                              jnp.asarray(lens, jnp.int32), sigma, n, cap)
    payload = jax.random.randint(k_pay, (n,), 0, model.vocab_size, jnp.int32)
    tables = ref.Tables(centroids=centroids, codebooks=codebooks,
                        codes=codes, ids=ids,
                        lens=jnp.asarray(lens, jnp.int32),
                        offsets=jnp.asarray(offsets), payload=payload)
    summary = dict(vectors=n, nlist=nlist, m=m, ksub=ds["ksub"],
                   list_cap=cap, mean_list=n / nlist,
                   longest_list=int(lens.max()),
                   empty_lists=int((lens == 0).sum()), key_sigma=sigma)
    return tables, summary
