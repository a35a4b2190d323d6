"""The fused streaming ChamVS scan (kernels/chamvs_scan) vs the staged
reference pipeline — the parity contract of this repo's §4 dataflow.

Three layers:
  * hypothesis property test at the kernel level: fused ``chamvs_scan``
    (Pallas interpret AND the vectorized ref backend) must equal the
    staged per-shard ADC -> mask -> exact top-k pipeline — dists and
    ids — over random (shards, queries, probes, cap, m, ksub, kk),
    including empty/short lists (``lens`` padding) and the ``idx == -1``
    sentinel;
  * end-to-end: ``search_single`` with ``fused=True`` vs ``fused=False``
    on a real trained index, both kernel backends;
  * the serving claim: the retrieval service's ``scan_dispatches``
    counter shows ONE scan dispatch per flushed wave regardless of
    shard count (the staged oracle shows one per shard).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, strategies as st

from repro.core.chamvs import ChamVSConfig, search_single
from repro.core.ivfpq import (IVFPQConfig, adc_scan_ref, build_shards,
                              train_ivfpq)
from repro.kernels.chamvs_scan.kernel import fused_scan
from repro.kernels.chamvs_scan.ops import chamvs_scan
from repro.kernels.chamvs_scan.ref import ref_chamvs_scan
from repro.kernels.registry import REF, PALLAS_INTERPRET
from repro.retrieval.service import RetrievalService, ServiceConfig


# ---------------------------------------------------------------------------
# the staged reference pipeline (per-shard ADC -> mask -> exact top-k),
# the oracle the fused kernel must reproduce bit-for-bit on ids
# ---------------------------------------------------------------------------

def _staged_pipeline(luts, codes, gids, lens, kk):
    S, nq, nprobe, cap, _ = codes.shape
    out_d, out_i = [], []
    for s in range(S):
        d = adc_scan_ref(luts, codes[s])                  # [nq, np, cap]
        valid = jnp.arange(cap)[None, None, :] < lens[s][..., None]
        d = jnp.where(valid, d, jnp.inf)
        flat_d = d.reshape(nq, -1)
        flat_i = gids[s].reshape(nq, -1)
        keep = min(kk, flat_d.shape[-1])
        neg, pos = jax.lax.top_k(-flat_d, keep)
        dd = -neg
        ii = jnp.take_along_axis(flat_i, pos, axis=-1)
        ii = jnp.where(jnp.isinf(dd), -1, ii)
        if keep < kk:
            dd = jnp.pad(dd, ((0, 0), (0, kk - keep)),
                         constant_values=jnp.inf)
            ii = jnp.pad(ii, ((0, 0), (0, kk - keep)), constant_values=-1)
        out_d.append(dd)
        out_i.append(ii)
    return jnp.stack(out_d), jnp.stack(out_i)


def _random_case(seed, S, nq, nprobe, cap, m, ksub, zero_lens=False):
    rng = np.random.default_rng(seed)
    luts = jnp.asarray(rng.normal(size=(nq, nprobe, m, ksub)), jnp.float32)
    codes = jnp.asarray(rng.integers(0, ksub, size=(S, nq, nprobe, cap, m)),
                        jnp.uint8)
    if zero_lens:
        lens = np.zeros((S, nq, nprobe), np.int64)
    else:
        # include empty (0) and full (cap) lists in the draw
        lens = rng.integers(0, cap + 1, size=(S, nq, nprobe))
    gids = rng.integers(0, 100_000, size=(S, nq, nprobe, cap))
    gids = np.where(np.arange(cap)[None, None, None] < lens[..., None],
                    gids, -1)
    return (luts, codes, jnp.asarray(gids, jnp.int32),
            jnp.asarray(lens, jnp.int32))


def _assert_parity(got, want):
    gd, gi = np.asarray(got[0]), np.asarray(got[1])
    wd, wi = np.asarray(want[0]), np.asarray(want[1])
    np.testing.assert_array_equal(gi, wi)
    assert (np.isinf(gd) == np.isinf(wd)).all()
    finite = np.isfinite(wd)
    np.testing.assert_allclose(gd[finite], wd[finite], rtol=1e-5, atol=1e-5)


@given(st.integers(1, 3),          # S — shard count
       st.integers(1, 6),          # nq
       st.integers(1, 3),          # nprobe
       st.integers(1, 24),         # cap — probed-list slice length
       st.integers(1, 4),          # m — PQ sub-spaces
       st.sampled_from([4, 16]),   # ksub
       st.integers(1, 8),          # kk — truncated queue length
       st.integers(0, 2 ** 31 - 1))
def test_fused_equals_staged_property(S, nq, nprobe, cap, m, ksub, kk, seed):
    """Property: fused chamvs_scan == staged ref pipeline, dists AND
    ids, for every backend, over random shapes incl. short lists."""
    case = _random_case(seed, S, nq, nprobe, cap, m, ksub)
    want = _staged_pipeline(*case, kk)
    _assert_parity(chamvs_scan(*case, kk, spec=REF), want)
    _assert_parity(chamvs_scan(*case, kk, spec=PALLAS_INTERPRET), want)


def test_fused_all_empty_lists_returns_sentinels():
    """Every list empty -> every slot is the (+inf, -1) sentinel."""
    case = _random_case(0, 2, 4, 2, 8, 2, 16, zero_lens=True)
    for spec in (REF, PALLAS_INTERPRET):
        d, i = chamvs_scan(*case, 5, spec=spec)
        assert np.isinf(np.asarray(d)).all()
        assert (np.asarray(i) == -1).all()


def test_fused_kk_exceeds_candidates_pads():
    """kk larger than the whole candidate pool pads with (+inf, -1) —
    the kernel's queue does this naturally, the ref path explicitly."""
    case = _random_case(1, 1, 2, 1, 3, 2, 4)
    want = _staged_pipeline(*case, 9)
    for spec in (REF, PALLAS_INTERPRET):
        got = chamvs_scan(*case, 9, spec=spec)
        _assert_parity(got, want)
    # the pool is 1 probe x cap 3 = 3 < kk = 9: the tail must be padded
    assert (np.asarray(want[1])[..., 3:] == -1).all()


def test_fused_tile_q_sweep():
    """The query-tile heuristic must not change results (tile_q divides
    nq at 8/4/1; sweep all three explicitly)."""
    case = _random_case(2, 2, 8, 2, 12, 2, 16)
    want = _staged_pipeline(*case, 4)
    for tile_q in (8, 4, 1):
        got = fused_scan(*case, 4, tile_q=tile_q, interpret=True)
        _assert_parity(got, want)


def test_ref_fused_matches_kernel_module_ref():
    case = _random_case(3, 2, 3, 2, 10, 3, 16)
    _assert_parity(ref_chamvs_scan(*case, 6), _staged_pipeline(*case, 6))


# ---------------------------------------------------------------------------
# end-to-end over a real trained index
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def small_index():
    key = jax.random.PRNGKey(0)
    icfg = IVFPQConfig(dim=32, nlist=16, m=8, list_cap=256)
    vecs = jax.random.normal(key, (2048, 32))
    params = train_ivfpq(key, vecs[:1024], icfg, kmeans_iters=4)
    shards = build_shards(params, np.asarray(vecs), icfg, num_shards=4)
    queries = jax.random.normal(jax.random.PRNGKey(1), (6, 32))
    return icfg, params, shards, queries


def test_search_single_memoizes_service(small_index):
    """Repeated one-shot searches over the same index reuse one
    service — the fused shard stack is packed once, not per call."""
    from repro.core import chamvs

    icfg, params, shards, q = small_index
    cfg = ChamVSConfig(ivfpq=icfg, nprobe=4, k=8, backend="ref")
    chamvs._SERVICE_MEMO.clear()
    search_single(params, shards, q, cfg)
    assert len(chamvs._SERVICE_MEMO) == 1
    svc = next(iter(chamvs._SERVICE_MEMO.values()))
    search_single(params, shards, q[:2], cfg)
    assert len(chamvs._SERVICE_MEMO) == 1
    assert next(iter(chamvs._SERVICE_MEMO.values())) is svc
    # a different config is a different service
    import dataclasses
    search_single(params, shards, q, dataclasses.replace(cfg, nprobe=8))
    assert len(chamvs._SERVICE_MEMO) == 2


@pytest.mark.parametrize("backend", ["ref", "pallas"])
def test_search_single_fused_equals_staged(small_index, backend):
    icfg, params, shards, q = small_index
    mk = lambda fused: ChamVSConfig(ivfpq=icfg, nprobe=8, k=10,
                                    backend=backend, fused=fused)
    df, i_f = search_single(params, shards, q, mk(True))
    ds, i_s = search_single(params, shards, q, mk(False))
    assert (np.asarray(i_f) == np.asarray(i_s)).all()
    finite = np.isfinite(np.asarray(ds))
    np.testing.assert_allclose(np.asarray(df)[finite],
                               np.asarray(ds)[finite], rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# the serving claim: one scan dispatch per flushed wave, any shard count
# ---------------------------------------------------------------------------

def _count_pallas_calls(jaxpr) -> int:
    """Recursively count pallas_call primitives in a (closed) jaxpr."""
    from jax.extend.core import ClosedJaxpr, Jaxpr

    def walk(j):
        n = 0
        for eqn in j.eqns:
            if eqn.primitive.name == "pallas_call":
                n += 1
            for v in eqn.params.values():
                vs = v if isinstance(v, (list, tuple)) else (v,)
                for x in vs:
                    if isinstance(x, ClosedJaxpr):
                        n += walk(x.jaxpr)
                    elif isinstance(x, Jaxpr):
                        n += walk(x)
        return n

    return walk(jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr)


@pytest.mark.parametrize("num_shards", [1, 2, 4])
def test_fused_graph_contains_single_scan_kernel(small_index, num_shards):
    """The structural ground truth behind ``stats.scan_dispatches``:
    the fused scan stage's traced graph contains exactly ONE
    ``pallas_call`` no matter the shard count, while the staged oracle
    contains one per shard. (The service counter is derived from the
    pipeline's shape; this test pins the shape itself, so a regression
    that sneaks a per-shard loop back into the fused path fails here.)
    """
    from repro.core.chamvs import stack_shards
    from repro.retrieval.service import _scan_stage, _scan_stage_fused

    icfg, params, _, q = small_index
    vecs = jax.random.normal(jax.random.PRNGKey(3), (1024, 32))
    shards = build_shards(params, np.asarray(vecs), icfg,
                          num_shards=num_shards)
    # nlist=16 < PALLAS_MIN_NLIST: the probe stage routes to ref, so
    # every pallas_call in the graph is a chamvs scan kernel
    cfg = ChamVSConfig(ivfpq=icfg, nprobe=4, k=8, backend="pallas")
    kk = cfg.k_prime(num_shards)
    fused = jax.make_jaxpr(
        lambda qq: _scan_stage_fused(params, stack_shards(shards), qq,
                                     cfg=cfg, kk=kk))(q)
    staged = jax.make_jaxpr(
        lambda qq: _scan_stage(params, tuple(shards), qq,
                               cfg=cfg, kk=kk))(q)
    assert _count_pallas_calls(fused) == 1
    assert _count_pallas_calls(staged) == num_shards


@pytest.mark.parametrize("num_shards", [1, 2, 4])
def test_one_scan_dispatch_per_wave(small_index, num_shards):
    icfg, params, _, q = small_index
    vecs = jax.random.normal(jax.random.PRNGKey(2), (2048, 32))
    shards = build_shards(params, np.asarray(vecs), icfg,
                          num_shards=num_shards)
    cfg = ChamVSConfig(ivfpq=icfg, nprobe=4, k=8, backend="ref")
    svc = RetrievalService.local(params, shards, cfg,
                                 ServiceConfig(measure=False))
    for _ in range(3):              # three waves: submit + submit + flush
        svc.submit(q[:2])
        svc.submit(q[2:4])
        svc.flush()
    assert svc.stats.num_batches == 3
    assert svc.stats.scan_dispatches == 3      # == waves, NOT shards*waves
    snap = svc.stats.snapshot()
    assert snap["scan_dispatches"] == 3

    staged = RetrievalService.local(
        params, shards, cfg, ServiceConfig(measure=False,
                                           kernel_fused=False))
    staged.submit(q[:2])
    staged.flush()
    assert staged.stats.scan_dispatches == num_shards
