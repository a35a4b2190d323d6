"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (interpret mode)."""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, strategies as st

from repro.kernels import registry
from repro.kernels.registry import PALLAS_INTERPRET, REF, KernelSpec
from repro.kernels.pq_adc.ops import pq_adc_topk, pq_shared_scan
from repro.kernels.pq_adc.ref import ref_adc
from repro.kernels.ivf_scan.ops import ivf_index_scan
from repro.kernels.ivf_scan.ref import ref_ivf_scan


# ---------------------------------------------------------------------------
# pq_adc
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("nbits", [4, 8])
@pytest.mark.parametrize("m", [4, 16, 32])
@pytest.mark.parametrize("n", [128, 1000, 2048])
def test_adc_topk_shape_sweep(nbits, m, n):
    ksub = 1 << nbits
    B, k = 3, 10
    key = jax.random.PRNGKey(m * n + nbits)
    luts = jax.random.normal(key, (B, m, ksub), jnp.float32)
    codes = jax.random.randint(jax.random.PRNGKey(1), (B, n, m), 0, ksub,
                               jnp.uint8)
    lens = jnp.array([n, max(n // 2, 1), min(k - 1, n)], jnp.int32)
    dp, ip = pq_adc_topk(luts, codes, lens, k, tile_n=256,
                         spec=PALLAS_INTERPRET)
    dr, ir = pq_adc_topk(luts, codes, lens, k, tile_n=256, spec=REF)
    finite = np.isfinite(np.asarray(dr))
    np.testing.assert_allclose(np.asarray(dp)[finite], np.asarray(dr)[finite],
                               rtol=1e-5, atol=1e-5)
    assert (np.asarray(ip) == np.asarray(ir)).all()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_adc_dtype(dtype):
    B, n, m, ksub, k = 2, 512, 8, 16, 5
    luts = jax.random.normal(jax.random.PRNGKey(0), (B, m, ksub), dtype)
    codes = jax.random.randint(jax.random.PRNGKey(1), (B, n, m), 0, ksub,
                               jnp.uint8)
    lens = jnp.full((B,), n, jnp.int32)
    dp, _ = pq_adc_topk(luts, codes, lens, k, spec=PALLAS_INTERPRET)
    dr, _ = pq_adc_topk(luts, codes, lens, k, spec=REF)
    np.testing.assert_allclose(np.asarray(dp, np.float32),
                               np.asarray(dr, np.float32),
                               rtol=2e-2 if dtype == jnp.bfloat16 else 1e-5,
                               atol=1e-2)


@given(st.integers(1, 64), st.integers(0, 100))
def test_adc_single_matches_manual(n_rows, seed):
    """Tiny-case oracle vs hand-rolled python loop."""
    m, ksub = 4, 16
    rng = np.random.default_rng(seed)
    lut = rng.normal(size=(m, ksub)).astype(np.float32)
    codes = rng.integers(0, ksub, size=(n_rows, m)).astype(np.uint8)
    want = np.array([sum(lut[j, codes[i, j]] for j in range(m))
                     for i in range(n_rows)])
    got = ref_adc(jnp.asarray(lut), jnp.asarray(codes))
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5)


@pytest.mark.parametrize("q,n,m,ksub", [(4, 512, 8, 16), (16, 300, 16, 16),
                                        (2, 128, 4, 256)])
def test_shared_scan_sweep(q, n, m, ksub):
    luts = jax.random.normal(jax.random.PRNGKey(0), (q, m, ksub), jnp.float32)
    codes = jax.random.randint(jax.random.PRNGKey(1), (n, m), 0, ksub,
                               jnp.uint8)
    sp = pq_shared_scan(luts, codes, tile_n=128, spec=PALLAS_INTERPRET)
    sr = pq_shared_scan(luts, codes, tile_n=128, spec=REF)
    np.testing.assert_allclose(np.asarray(sp), np.asarray(sr), rtol=1e-4,
                               atol=1e-4)


# ---------------------------------------------------------------------------
# ivf_scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("nq,nlist,d,nprobe", [
    (8, 512, 64, 16), (16, 1024, 128, 32), (4, 128, 32, 8)])
def test_ivf_scan_sweep(nq, nlist, d, nprobe):
    q = jax.random.normal(jax.random.PRNGKey(0), (nq, d))
    c = jax.random.normal(jax.random.PRNGKey(1), (nlist, d))
    dp, ip = ivf_index_scan(q, c, nprobe, spec=PALLAS_INTERPRET)
    dr, ir = ref_ivf_scan(q, c, nprobe)
    np.testing.assert_allclose(np.asarray(dp), np.asarray(dr), rtol=1e-4,
                               atol=1e-4)
    assert (np.asarray(ip) == np.asarray(ir)).all()


def test_ivf_scan_returns_true_l2():
    q = jax.random.normal(jax.random.PRNGKey(2), (4, 16))
    c = jax.random.normal(jax.random.PRNGKey(3), (128, 16))
    dp, ip = ivf_index_scan(q, c, 4, spec=PALLAS_INTERPRET)
    manual = np.sum((np.asarray(q)[:, None] - np.asarray(c)[None]) ** 2, -1)
    want = np.sort(manual, axis=1)[:, :4]
    np.testing.assert_allclose(np.asarray(dp), want, rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# the kernel registry: fallback accounting + deprecated aliases
# ---------------------------------------------------------------------------

def test_ivf_scan_small_nlist_fallback_warns_once():
    """spec.backend="pallas" with nlist < PALLAS_MIN_NLIST routes to the
    ref scan — loudly, exactly once per registry-reset interval, counted
    in the registry, with correct results."""
    from repro.kernels.ivf_scan import ops

    q = jax.random.normal(jax.random.PRNGKey(4), (3, 16))
    c = jax.random.normal(jax.random.PRNGKey(5), (ops.PALLAS_MIN_NLIST // 2,
                                                  16))
    assert registry.fallback_count("ivf_index_scan") == 0
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        dp, ip = ivf_index_scan(q, c, 4, spec=PALLAS_INTERPRET)
        # second call with a fresh shape re-decides; still only one warning
        ivf_index_scan(q[:2], c, 4, spec=PALLAS_INTERPRET)
    msgs = [w for w in caught if "PALLAS_MIN_NLIST" in str(w.message)]
    assert len(msgs) == 1 and issubclass(msgs[0].category, RuntimeWarning)
    # ...but every routing decision is counted
    assert registry.fallback_count("ivf_index_scan") == 2
    assert registry.fallback_count() == 2
    dr, ir = ref_ivf_scan(q, c, 4)
    np.testing.assert_allclose(np.asarray(dp), np.asarray(dr), rtol=1e-5,
                               atol=1e-5)
    assert (np.asarray(ip) == np.asarray(ir)).all()


def test_registry_reset_rearms_warning():
    """reset_warnings() re-arms the one-time warning and zeroes the
    counters (the conftest fixture calls it around every test, so the
    old module-global 'warned once per process' flag can't leak)."""
    from repro.kernels.ivf_scan import ops

    q = jax.random.normal(jax.random.PRNGKey(6), (2, 8))
    c = jax.random.normal(jax.random.PRNGKey(7), (ops.PALLAS_MIN_NLIST // 4,
                                                  8))
    for _ in range(2):
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            ivf_index_scan(q, c, 2, spec=PALLAS_INTERPRET)
        assert sum("PALLAS_MIN_NLIST" in str(w.message) for w in caught) == 1
        assert registry.fallback_count("ivf_index_scan") == 1
        registry.reset_warnings()
    assert registry.fallback_count() == 0


def test_fallback_error_policy_raises():
    """fallback="error" turns a silent ref detour into a hard failure —
    deployment configs that must never serve ref numbers as pallas."""
    from repro.kernels.ivf_scan import ops

    q = jax.random.normal(jax.random.PRNGKey(8), (2, 8))
    c = jax.random.normal(jax.random.PRNGKey(9), (ops.PALLAS_MIN_NLIST // 4,
                                                  8))
    strict = KernelSpec(backend="pallas", fallback="error")
    with pytest.raises(registry.KernelFallbackError):
        ivf_index_scan(q, c, 2, spec=strict)


def test_deprecated_backend_kwargs_still_route():
    """The legacy backend=/interpret= kwargs keep working as deprecated
    aliases (warning once per op) and return identical results."""
    q = jax.random.normal(jax.random.PRNGKey(10), (4, 16))
    c = jax.random.normal(jax.random.PRNGKey(11), (128, 16))
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        d_old, i_old = ivf_index_scan(q, c, 4, backend="pallas",
                                      interpret=True)
        ivf_index_scan(q, c, 4, backend="pallas")   # second: no new warning
    deps = [w for w in caught if issubclass(w.category, DeprecationWarning)]
    assert len(deps) == 1 and "spec=" in str(deps[0].message)
    d_new, i_new = ivf_index_scan(q, c, 4, spec=PALLAS_INTERPRET)
    np.testing.assert_array_equal(np.asarray(i_old), np.asarray(i_new))
    np.testing.assert_allclose(np.asarray(d_old), np.asarray(d_new))


def test_legacy_positional_backend_string_still_routes():
    """The old signatures had ``backend`` where ``spec`` now sits; a
    bare string in that slot must behave as the deprecated alias, not
    crash with AttributeError downstream."""
    q = jax.random.normal(jax.random.PRNGKey(14), (4, 16))
    c = jax.random.normal(jax.random.PRNGKey(15), (128, 16))
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        d_pos, i_pos = ivf_index_scan(q, c, 4, "pallas")
    assert any(issubclass(w.category, DeprecationWarning) for w in caught)
    d_new, i_new = ivf_index_scan(q, c, 4, spec=PALLAS_INTERPRET)
    np.testing.assert_array_equal(np.asarray(i_pos), np.asarray(i_new))
    np.testing.assert_allclose(np.asarray(d_pos), np.asarray(d_new))


def test_kernel_spec_validation_and_tiles():
    with pytest.raises(ValueError):
        KernelSpec(backend="cuda")
    with pytest.raises(ValueError):
        KernelSpec(fallback="whatever")
    s = KernelSpec()
    assert s.pick_tile_q(16) == 8 and s.pick_tile_q(12) == 4 \
        and s.pick_tile_q(7) == 1
    assert s.pick_tile_c(1024) == 512 and s.pick_tile_c(256) == 128 \
        and s.pick_tile_c(96) == 96
    assert s.pick_tile_n(4096) == 512 and s.pick_tile_n(64) == 128
    assert KernelSpec(tile_q=4).pick_tile_q(16) == 4
    # explicit overrides that don't divide the axis round DOWN to a
    # legal tile instead of tripping the kernels' grid asserts
    assert KernelSpec(tile_q=8).pick_tile_q(12) == 6
    assert KernelSpec(tile_q=5).pick_tile_q(7) == 1
    assert KernelSpec(tile_c=100).pick_tile_c(128) == 64


def test_serving_spec_follows_platform(monkeypatch):
    """Serving kernels: the reference paths on a CPU host (a Pallas
    request interprets there); the compiled Pallas kernels on an
    accelerator, where a route to a reference path raises."""
    from repro.kernels import registry

    s = registry.serving_spec()
    assert (s.backend, s.fallback, s.use_interpret()) == ("ref", "warn", True)
    assert registry.serving_spec("pallas").use_interpret()
    monkeypatch.setattr(registry, "on_cpu", lambda: False)
    s = registry.serving_spec()
    assert (s.backend, s.fallback, s.use_interpret()) == \
        ("pallas", "error", False)
    q = jax.random.normal(jax.random.PRNGKey(14), (4, 16))
    c = jax.random.normal(jax.random.PRNGKey(15), (16, 16))   # < 128 lists
    with pytest.raises(registry.KernelFallbackError):
        ivf_index_scan(q, c, 4, spec=s)
    assert registry.fallback_count() == 0


def test_explicit_nondivisor_tile_override_still_runs():
    q = jax.random.normal(jax.random.PRNGKey(12), (12, 16))
    c = jax.random.normal(jax.random.PRNGKey(13), (128, 16))
    spec = KernelSpec(backend="pallas", tile_q=8, tile_c=100)
    dp, ip = ivf_index_scan(q, c, 4, spec=spec)
    dr, ir = ref_ivf_scan(q, c, 4)
    np.testing.assert_allclose(np.asarray(dp), np.asarray(dr), rtol=1e-4,
                               atol=1e-4)
    assert (np.asarray(ip) == np.asarray(ir)).all()
