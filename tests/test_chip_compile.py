"""The serving kernels compile for a TPU v5e at real widths.

Interpret-mode parity (test_chamvs_scan.py, test_decode_attn.py) cannot
see Mosaic's tiling rules or VMEM limits; these tests hand each kernel
to the chip's compiler for a *described* v5e (no chip attached) with
``interpret=False``:

  * ``chamvs_scan`` at paper Table 3's SYN-512 code shape (m 32, ksub
    256) over 1 and 3 shards, nq 64, nprobe 32, list capacities 1024 and
    1408, and the truncated queue length k' of K = 100;
  * ``ivf_scan`` over 32768 lists of 512-d centroids, nprobe 32;
  * ``decode_attn`` at Dec-S widths (8 KV heads of 64) over a 2048-slot
    cache, for waves of 8 and 5 rows;
  * one whole Dec-S ``decode_wave`` step with the Pallas attention.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_arch
from repro.core.chamvs import ChamVSConfig
from repro.core.ivfpq import IVFPQConfig
from repro.kernels.chamvs_scan.kernel import fused_scan
from repro.kernels.decode_attn.kernel import fused_decode_attention
from repro.kernels.ivf_scan.kernel import ivf_scan
from repro.kernels.registry import KernelSpec
from repro.models import transformer as tf

SYN512 = IVFPQConfig(dim=512, nlist=32768, m=32, list_cap=1024)


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with the persistent compilation
    cache off (an entry written for a described chip cannot be read
    back without one)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text        # the Pallas kernel is there


@pytest.mark.parametrize("shards,cap", [(1, 1024), (3, 1408)])
def test_chamvs_scan_compiles(one_chip, shards, cap):
    """cap 1408 is not a multiple of the kernel's 512-lane decode chunk
    (a ragged last chunk crashed the chip's compiler)."""
    nq, nprobe, m, ksub = 64, 32, SYN512.m, 256
    kk = ChamVSConfig(ivfpq=SYN512, nprobe=nprobe, k=100).k_prime(shards)
    _compile(lambda a, b, c, d: fused_scan(a, b, c, d, kk=kk, tile_q=8,
                                           interpret=False), one_chip,
             ((nq, nprobe, m, ksub), jnp.float32),
             ((shards, nq, nprobe, cap, m), jnp.uint8),
             ((shards, nq, nprobe, cap), jnp.int32),
             ((shards, nq, nprobe), jnp.int32))


def test_ivf_scan_compiles(one_chip):
    _compile(lambda q, c: ivf_scan(q, c, 32, tile_q=8, tile_c=512,
                                   interpret=False), one_chip,
             ((64, SYN512.dim), jnp.float32),
             ((SYN512.nlist, SYN512.dim), jnp.float32))


@pytest.mark.parametrize("wave,tile_b", [(8, 8), (5, 1)])
def test_decode_attn_compiles(one_chip, wave, tile_b):
    cfg = get_arch("dec_s").model
    H, KV, D, S = cfg.n_heads, cfg.n_kv_heads, cfg.d_head, 2048
    _compile(lambda q, k, v, p: fused_decode_attention(
        q, k, v, p, tile_b=tile_b, blk=128, interpret=False), one_chip,
        ((wave, 1, H, D), jnp.bfloat16), ((wave, S, KV, D), jnp.bfloat16),
        ((wave, S, KV, D), jnp.bfloat16), ((wave,), jnp.int32))


def test_dec_s_decode_wave_compiles(one_chip):
    """A whole serving step: 24 layers at d_model 512 over a pooled KV
    cache, with the streaming attention kernel in every layer."""
    cfg = get_arch("dec_s").model
    spec = KernelSpec(backend="pallas", interpret=False, fallback="error")
    params = jax.eval_shape(lambda: tf.init_params(jax.random.PRNGKey(0),
                                                   cfg))
    caches = jax.eval_shape(lambda: tf.init_cache(cfg, 8, max_seq=256))
    place = lambda t: jax.tree.map(                       # noqa: E731
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        t)
    wave = [jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
            for s in ((4, 1), (4,), (4,))]
    step = jax.jit(lambda p, c, t, s, pos: tf.decode_wave(
        p, cfg, c, t, s, pos, kv_len=128, attn_spec=spec,
        return_hidden=True))
    text = step.lower(place(params), place(caches), *wave).compile().as_text()
    assert "tpu_custom_call" in text
