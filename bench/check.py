"""Whether what the timed path served is correct: the served requests
against the plain reference (the configuration's LM module under
``bench/lm/``, and ``reference.py``'s search and mixture), once the
window has closed.

What the timed path produced, per served token of a sample of finished
requests (drawn from the seed, with the longest among them):

* the token streamed to the client;
* the query the retrieval tier received for it (the LM's last hidden
  state, after prefill and decode through the KV pool and the
  decode-attention kernel) and the (distances, ids) it answered with
  (the IVF probe and the fused PQ scan), taken by ``Recorder`` under the
  request and the step it was searched for.

The reference runs the model in float32 at ``highest`` over each prompt
with its served tokens, searches the same tables exactly from each
served query, and forms the kNN-LM mixture. The numbers compared, each the worst over the sample:

``query_err``
    relative L2 distance between the served query and the reference's
    hidden state at that position;
``dist_err``
    relative error of each served distance against the reference's ADC
    distance of the same id for the served query;
``scan_gap``
    how much farther, in summed reference distance from the served
    query, the served neighbours lie than the reference's own top-K from
    that query over the same lists (those the served neighbours come
    from): the scan and the K-selection. The probe's choice of lists is
    not judged: it turns at the margin between lists on rounding alone;
``mix_gap``
    the best log-probability minus that of the served token, under the
    kNN-LM mixture of the reference's float32 LM logits with the
    neighbours the program served (its distances and ids). The served
    token is the program's greedy pick of its own mixture, so this holds
    the LM head, the interpolation (``lam``, temperature, payload) and the
    emitted token to the reference, while a search that probed other
    lists than the exact one cannot move it;
``missing``
    sampled positions with no recorded search or with a neighbour slot
    left empty; it has to be 0.

``control_readings`` reads the same numbers for the reference itself
computed a precision lower than the configuration states (fp8 matmuls in
the LM, bf16 distance tables in the search), put in the program's
place.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import reference as ref

REF_ROWS = 4           # sequences per reference forward block
SEARCH_ROWS = 16       # queries per reference search block


class Recorder:
    """Stands between the engine and its ``AsyncRetriever`` and keeps
    each query row the engine searched in the window with the answer it
    got, under the (request, step) it was searched for. Only references
    to device arrays are kept; nothing is copied to the host while the
    window runs."""

    def __init__(self, inner):
        self.inner = inner
        self.rows: List[Tuple] = []     # (key, queries, dists, ids)
        self.on = False

    def __getattr__(self, name):
        return getattr(self.inner, name)

    def attach(self, engine) -> None:
        """Tag every search ``engine`` issues with its (request, step)."""
        wave = engine.dispatch_search_wave
        one = engine.dispatch_search

        def dispatch_wave(seqs, decoded):
            searches = wave(seqs, decoded)
            for seq, handle in zip(seqs, searches):
                if isinstance(handle, _Handle):
                    handle.key = (seq.request, seq.step)
            return searches

        def dispatch_one(seq, hidden):
            handle = one(seq, hidden)
            if isinstance(handle, _Handle):
                handle.key = (seq.request, seq.step)
            return handle
        engine.dispatch_search_wave = dispatch_wave
        engine.dispatch_search = dispatch_one

    def search_async(self, queries):
        return _Handle(self.inner.search_async(queries), queries, self)

    def flush(self) -> None:
        self.inner.flush()

    def resolve(self, ids, kind: str = "tokens"):
        return self.inner.resolve(ids, kind)


class _Handle:
    """A ``SearchHandle`` that records its query with its answer."""

    def __init__(self, inner, queries, recorder: Recorder):
        self.inner, self.queries, self.recorder = inner, queries, recorder
        self.key = None

    def __getattr__(self, name):
        return getattr(self.inner, name)

    def result(self):
        dists, ids = self.inner.result()
        if self.recorder.on:
            self.recorder.rows.append((self.key, self.queries, dists, ids))
        return dists, ids


def served_rows(rows: List[Tuple]) -> Dict[Tuple, Tuple]:
    """The recorded rows on the host, by (prompt, step): the query, the
    distances and the ids searched for that request's token ``step``."""
    host = jax.device_get([r[1:] for r in rows])
    prompts: Dict[int, Tuple] = {}
    out = {}
    for (key, _, _, _), (q, d, i) in zip(rows, host):
        if key is None:
            continue
        req, step = key
        if id(req) not in prompts:
            prompts[id(req)] = tuple(np.asarray(req.prompt)[0].tolist())
        out[(prompts[id(req)], step)] = (q[0], d[0], i[0])
    return out


def pick_sample(results: Sequence, n: int, seed: int) -> List:
    """``n`` finished requests drawn from the seed, the longest first."""
    done = [r for r in results if r.complete]
    if not done:
        return []
    longest = max(range(len(done)), key=lambda i: len(done[i].tokens))
    rest = [i for i in range(len(done)) if i != longest]
    rng = np.random.default_rng(seed + 7)
    pick = rng.permutation(rest)[:max(0, n - 1)].tolist()
    return [done[longest]] + [done[i] for i in pick]


def _positions(sample: Sequence, max_seq: int):
    """Token matrix [S, max_seq] of prompt + served tokens (all but the
    last); per served token, (sequence, position) of the hidden state
    that produced it, and its (prompt, step) key in ``served_rows``."""
    rows = -(-len(sample) // REF_ROWS) * REF_ROWS
    toks = np.zeros((rows, max_seq), np.int32)
    where, keys = [], []
    for s, r in enumerate(sample):
        seq = list(r.request.prompt) + list(r.tokens[:-1])
        toks[s, :len(seq)] = seq
        t0 = len(r.request.prompt)
        where += [(s, t0 - 1 + j) for j in range(len(r.tokens))]
        keys += [(tuple(r.request.prompt), j) for j in range(len(r.tokens))]
    return toks, np.asarray(where, np.int32), keys


def hidden_at(lm, params, m, toks: np.ndarray, where: np.ndarray,
              prec: str) -> jnp.ndarray:
    """Hidden states [P, d] at ``where`` of the LM module ``lm``'s model
    ``m`` over ``toks``."""
    out = []
    for s in range(0, toks.shape[0], REF_ROWS):
        h = lm.forward_hidden(params, jnp.asarray(toks[s:s + REF_ROWS]), m,
                              prec)
        sel = where[(where[:, 0] >= s) & (where[:, 0] < s + REF_ROWS)]
        out.append(h[sel[:, 0] - s, sel[:, 1]])
    return jnp.concatenate(out)


def _blocks(fn, arrays: Tuple, rows: int = SEARCH_ROWS) -> List:
    """``fn`` over blocks of ``rows`` leading rows of ``arrays`` (the last
    block padded by repeating a row, so every block has one shape), the
    outputs joined and cropped to the input length."""
    n = arrays[0].shape[0]
    pad = -n % rows
    arrays = [jnp.concatenate([a, jnp.repeat(a[-1:], pad, 0)]) if pad else a
              for a in arrays]
    outs = [fn(*(a[s:s + rows] for a in arrays)) for s in range(0, n + pad,
                                                                 rows)]
    return [jnp.concatenate(parts)[:n] for parts in zip(*outs)]


def _search(tables, q, nprobe, k, prec):
    d, i, _ = _blocks(lambda x: ref.search(tables, x, nprobe, k, prec), (q,))
    return d, i


def _lists_of(tables, ids, n):
    """The distinct lists [R, n] (-1 past them) that ids [R, K] lie in,
    at most ``n`` of them."""
    big = tables.offsets.shape[0]
    lst, _ = ref.locate(tables, jnp.maximum(ids, 0))
    s = jnp.sort(jnp.where(ids >= 0, lst, big), -1)
    new = jnp.concatenate([jnp.ones_like(s[:, :1], bool),
                           s[:, 1:] != s[:, :-1]], -1)
    u = jnp.sort(jnp.where(new, s, big), -1)[:, :n]
    return jnp.where(u < big, u, -1)


def _scan_gap(tables, q, i, d_ref, nprobe, k):
    """``_sum_gap`` of the served neighbours (ids ``i``, reference
    distances ``d_ref`` from queries ``q``) against the reference's top-K
    over the lists they lie in."""
    lists = _lists_of(tables, i, nprobe)
    d_opt, _ = _blocks(lambda x, y: ref.search_lists(
        tables, x, y, k, ref.REFERENCE), (q, lists))
    return _sum_gap(d_ref, d_opt)


def _dists(tables, q, ids, prec):
    (d,) = _blocks(lambda x, y: (ref.distances_of(tables, x, y, prec),),
                   (q, ids))
    return d


def _mix(tables, logits, d, i, rag):
    (mx,) = _blocks(lambda a, b, c: (ref.mixture(
        a, b, c, tables.payload, rag["lam"], rag["temperature"]),),
        (logits, d, i), 64)
    return mx


def _logits(lm, params, h, m, prec):
    (lg,) = _blocks(lambda x: (lm.lm_logits(params, x, m, prec),), (h,), 64)
    return lg


def _rel(a, b):
    return jnp.linalg.norm(a - b, axis=-1) / jnp.maximum(
        jnp.linalg.norm(b, axis=-1), 1e-30)


def _dist_err(d, d_ref):
    scale = jnp.max(jnp.where(jnp.isfinite(d_ref), jnp.abs(d_ref), 0.0), -1,
                    keepdims=True)
    ok = jnp.isfinite(d) & jnp.isfinite(d_ref)
    return jnp.max(jnp.where(ok, jnp.abs(d - d_ref), 0.0)
                   / jnp.maximum(scale, 1e-30), -1)


def _sum_gap(d_served_at_ref, d_opt):
    return (jnp.sum(d_served_at_ref, -1) - jnp.sum(d_opt, -1)) \
        / jnp.maximum(jnp.sum(d_opt, -1), 1e-30)


def _gap(mix, tok):
    return jnp.max(mix, -1) - jnp.take_along_axis(mix, tok[:, None], -1)[:, 0]


class Reference:
    """The reference's view of one run's sample: hidden states, logits,
    top-K and mixture at every served position. ``lm`` is the
    configuration's LM module and ``m`` its ``Model``."""

    def __init__(self, lm, params, m, tables, rag: Dict,
                 sample: Sequence, max_seq: int):
        self.lm, self.params, self.m = lm, params, m
        self.tables, self.rag = tables, rag
        self.toks, self.where, self.keys = _positions(sample, max_seq)
        self.served = jnp.asarray(np.concatenate(
            [np.asarray(r.tokens, np.int32) for r in sample]))
        self.h = hidden_at(lm, params, m, self.toks, self.where,
                           ref.REFERENCE)
        self.logits = _logits(lm, params, self.h, m, ref.REFERENCE)


def readings(reference: Reference, rows: List[Tuple]) -> Dict[str, float]:
    """The compared numbers for the program's served sample."""
    r = reference
    served = served_rows(rows)
    found = [served.get(k) for k in r.keys]
    at = np.asarray([j for j, g in enumerate(found) if g is not None],
                    np.int32)
    unrecorded = len(found) - len(at)
    if not len(at):
        return dict(missing=float(unrecorded))
    q, d, i = (jnp.asarray(np.stack([found[j][n] for j in at]).astype(t))
               for n, t in enumerate((np.float32, np.float32, np.int32)))
    h, logits, tok = r.h[at], r.logits[at], r.served[at]
    d_ref = _dists(r.tables, q, i, ref.REFERENCE)
    missing = unrecorded + jnp.sum(jnp.any((i < 0) | ~jnp.isfinite(d), -1))
    out = dict(
        query_err=jnp.max(_rel(q, h)),
        dist_err=jnp.max(_dist_err(d, d_ref)),
        scan_gap=jnp.max(_scan_gap(r.tables, q, i, d_ref, r.rag["nprobe"],
                                   r.rag["k"])),
        mix_gap=jnp.max(_gap(_mix(r.tables, logits, d, i, r.rag), tok)),
        missing=missing)
    return {k: float(v) for k, v in out.items()}


def control_readings(reference: Reference) -> Dict[str, float]:
    """The same numbers for the reference computed a precision lower than
    the configuration states, in the program's place: fp8 matmuls in the
    LM, bf16 distance tables and probe in the search."""
    r = reference
    h = hidden_at(r.lm, r.params, r.m, r.toks, r.where, ref.CONTROL)
    logits = _logits(r.lm, r.params, h, r.m, ref.CONTROL)
    d, i = _search(r.tables, h, r.rag["nprobe"], r.rag["k"], ref.CONTROL)
    d_ref = _dists(r.tables, h, i, ref.REFERENCE)
    # the control's greedy token, judged as the program's is: under the
    # reference's LM mixed with the neighbours the control found
    tok = jnp.argmax(_mix(r.tables, logits, d, i, r.rag), -1)
    out = dict(
        query_err=jnp.max(_rel(h, r.h)),
        dist_err=jnp.max(_dist_err(d, d_ref)),
        scan_gap=jnp.max(_scan_gap(r.tables, h, i, d_ref, r.rag["nprobe"],
                                   r.rag["k"])),
        mix_gap=jnp.max(_gap(_mix(r.tables, r.logits, d, i, r.rag), tok)))
    return {k: float(v) for k, v in out.items()}


def judge(values: Dict[str, float], limits: Dict[str, float]
          ) -> Tuple[bool, Dict[str, Dict[str, float]]]:
    """Each compared number beside its limit; correct when none exceeds
    it. A number that could not be read counts as exceeding."""
    checks, ok = {}, True
    for name, limit in limits.items():
        v: Optional[float] = values.get(name)
        checks[name] = {"value": v, "limit": limit}
        if v is None or not np.isfinite(v) or v > limit:
            ok = False
    return ok, checks
