"""The model stack: decoder-only / encoder-decoder transformers over four
block families (dense, moe, hybrid attn∥mamba, rwkv6), with heterogeneous
layer patterns (gemma3 local:global), KV / ring / SSM caches, and three
execution modes:

  * ``train``   — full sequence, no cache, flash attention
  * ``prefill`` — full sequence, builds the cache (serving step 1)
  * ``decode``  — one token against the cache (serving steady state)

Compile economy (DESIGN.md §8): layers are stacked per layer-class and the
stack is applied by a ``lax.scan`` over groups of ``period`` layers, so HLO
size is O(period), independent of depth — required to compile llama3-405b's
126 layers on one host core with 512 fake devices.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib
from repro.models.attention import (decode_attention, flash_attention,
                                    prefill_cache, update_cache)
from repro.models.config import ModelConfig
from repro.models.ctx import constrain
from repro.models.layers import positional_rotate, rms_norm, swiglu

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _dense_init(key, shape, scale=0.02, dtype=jnp.bfloat16):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def _init_block_class(key, cfg: ModelConfig, n: int, cross: bool) -> Params:
    """Stacked params for `n` layers of one class."""
    d, f = cfg.d_model, cfg.d_ff
    H, KV, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    dt = jnp.dtype(cfg.dtype)
    ks = iter(jax.random.split(key, 64))
    p: Params = {}
    if cfg.block == "rwkv6":
        D = H * dh
        def v(shape, scale=0.02):
            return _dense_init(next(ks), (n,) + shape, scale, dt)
        p = dict(
            ln1=jnp.ones((n, d), dt), ln2=jnp.ones((n, d), dt),
            mu_r=v((d,), 0.5), mu_k=v((d,), 0.5), mu_v=v((d,), 0.5),
            mu_g=v((d,), 0.5), mu_w=v((d,), 0.5),
            w_r=v((d, D)), w_k=v((d, D)), w_v=v((d, D)), w_g=v((d, D)),
            w_o=v((D, d)),
            w0=v((D,), 0.5), w_lora_a=v((d, 64)), w_lora_b=v((64, D)),
            bonus_u=v((H, dh), 0.5), ln_x=jnp.ones((n, D), dt),
            mu_ck=v((d,), 0.5), mu_cr=v((d,), 0.5),
            w_ck=v((d, f)), w_cv=v((f, d)), w_cr=v((d, d)),
        )
        return p

    p["ln1"] = jnp.ones((n, d), dt)
    p["wq"] = _dense_init(next(ks), (n, d, H * dh), dtype=dt)
    p["wk"] = _dense_init(next(ks), (n, d, KV * dh), dtype=dt)
    p["wv"] = _dense_init(next(ks), (n, d, KV * dh), dtype=dt)
    p["wo"] = _dense_init(next(ks), (n, H * dh, d), dtype=dt)
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((n, H * dh), dt)
        p["bk"] = jnp.zeros((n, KV * dh), dt)
        p["bv"] = jnp.zeros((n, KV * dh), dt)
    p["ln2"] = jnp.ones((n, d), dt)
    if cfg.block == "moe":
        E = cfg.n_experts
        p["router"] = _dense_init(next(ks), (n, d, E), dtype=jnp.float32)
        p["wg"] = _dense_init(next(ks), (n, E, d, f), dtype=dt)
        p["wu"] = _dense_init(next(ks), (n, E, d, f), dtype=dt)
        p["wd"] = _dense_init(next(ks), (n, E, f, d), dtype=dt)
    else:
        p["wg"] = _dense_init(next(ks), (n, d, f), dtype=dt)
        p["wu"] = _dense_init(next(ks), (n, d, f), dtype=dt)
        p["wd"] = _dense_init(next(ks), (n, f, d), dtype=dt)
    if cfg.block == "hybrid":
        d_in = H * dh
        ds, cw = cfg.ssm_state, cfg.conv_width
        p["mamba"] = ssm_lib.MambaParams(
            w_in=_dense_init(next(ks), (n, d, 2 * d_in), dtype=dt),
            conv_w=_dense_init(next(ks), (n, cw, d_in), 0.2, dt),
            w_bcdt=_dense_init(next(ks), (n, d_in, 2 * ds + H), dtype=dt),
            a_log=jnp.zeros((n, H, ds), jnp.float32),
            dt_bias=jnp.zeros((n, H), jnp.float32),
            d_skip=jnp.ones((n, H), jnp.float32),
            w_out=_dense_init(next(ks), (n, d_in, d), dtype=dt),
        )
        p["ln_attn_out"] = jnp.ones((n, d), dt)
        p["ln_ssm_out"] = jnp.ones((n, d), dt)
    if cross:
        p["lnx"] = jnp.ones((n, d), dt)
        p["xwq"] = _dense_init(next(ks), (n, d, H * dh), dtype=dt)
        p["xwk"] = _dense_init(next(ks), (n, d, KV * dh), dtype=dt)
        p["xwv"] = _dense_init(next(ks), (n, d, KV * dh), dtype=dt)
        p["xwo"] = _dense_init(next(ks), (n, H * dh, d), dtype=dt)
    return p


def init_params(key: jax.Array, cfg: ModelConfig) -> Params:
    dt = jnp.dtype(cfg.dtype)
    k_emb, k_head, k_cls, k_enc = jax.random.split(key, 4)
    params: Params = {
        "embed": _dense_init(k_emb, (cfg.vocab_size, cfg.d_model), dtype=dt),
        "final_norm": jnp.ones((cfg.d_model,), dt),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = _dense_init(
            k_head, (cfg.d_model, cfg.vocab_size), dtype=dt)
    classes = {}
    for i, cls in enumerate(cfg.pattern_classes()):
        n = len(cfg.class_layers(cls))
        classes[cls] = _init_block_class(
            jax.random.fold_in(k_cls, i), cfg, n, cross=(cfg.arch == "encdec"))
    params["classes"] = classes
    if cfg.arch == "encdec":
        enc_cfg = ModelConfig(
            name=cfg.name + "-enc", n_layers=cfg.n_enc_layers,
            d_model=cfg.d_model, n_heads=cfg.n_heads,
            n_kv_heads=cfg.n_kv_heads, d_ff=cfg.d_ff,
            vocab_size=cfg.vocab_size, d_head=cfg.d_head, block="dense",
            qkv_bias=cfg.qkv_bias, rope_theta=cfg.rope_theta,
            norm_eps=cfg.norm_eps, act=cfg.act, dtype=cfg.dtype)
        params["encoder"] = {
            "classes": {"global": _init_block_class(
                k_enc, enc_cfg, cfg.n_enc_layers, cross=False)},
            "final_norm": jnp.ones((cfg.d_model,), dt),
        }
    return params


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, B: int, max_seq: int,
               enc_len: int = 0) -> Params:
    """Per-class decode caches. Local (sliding) classes get ring buffers of
    size ``cfg.window``; global classes get full-length buffers."""
    dt = jnp.dtype(cfg.dtype)
    H, KV, dh, d = cfg.n_heads, cfg.n_kv_heads, cfg.d_head, cfg.d_model
    caches: Params = {"classes": {}}
    for cls in cfg.pattern_classes():
        n = len(cfg.class_layers(cls))
        c: Params = {}
        if cfg.block == "rwkv6":
            c["wkv"] = jnp.zeros((n, B, H, dh, dh), jnp.float32)
            c["st"] = jnp.zeros((n, B, d), dt)
            c["sc"] = jnp.zeros((n, B, d), dt)
        else:
            S = cfg.window if (cls == "local" and cfg.window > 0) else max_seq
            c["k"] = jnp.zeros((n, B, S, KV, dh), dt)
            c["v"] = jnp.zeros((n, B, S, KV, dh), dt)
            if cfg.block == "hybrid":
                d_in = H * dh
                c["ssm"] = jnp.zeros((n, B, H, dh, cfg.ssm_state), jnp.float32)
                c["conv"] = jnp.zeros((n, B, cfg.conv_width - 1, d_in), dt)
            if cfg.arch == "encdec" and enc_len > 0:
                # cross-KV cache; enc_len=0 -> cross K/V recomputed from
                # enc_states every step (RALM re-encoding path)
                c["xk"] = jnp.zeros((n, B, enc_len, KV, dh), dt)
                c["xv"] = jnp.zeros((n, B, enc_len, KV, dh), dt)
        caches["classes"][cls] = c
    return caches


# ---------------------------------------------------------------------------
# blocks
# ---------------------------------------------------------------------------

def _proj_qkv(cfg, p, x):
    B, T, _ = x.shape
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, T, cfg.n_heads, cfg.d_head)
    k = k.reshape(B, T, cfg.n_kv_heads, cfg.d_head)
    v = v.reshape(B, T, cfg.n_kv_heads, cfg.d_head)
    return q, k, v


def _self_attention(cfg, p, h, positions, mode, cache, window, slots=None,
                    kv_len=None, attn_spec=None):
    """Returns (attn_out [B,T,d], new_cache).

    ``slots`` enables the batched-slot (KV-pool) decode path: the cache
    carries ``P`` pooled rows, ``h`` carries a wave of ``W`` active rows,
    and row ``w`` reads/writes pool row ``slots[w]``. New K/V are written
    at O(W) scatter cost; attention reads gather each wave row's slot.

    ``kv_len`` (static int) crops the attention read of full-length
    caches to the wave's block-aligned valid prefix — the engine derives
    it from the wave's max position on the host, so a ragged wave stops
    paying for the pool's ``max_seq`` padding (slots past every row's
    position carry exactly-zero weight, so cropping them is a no-op on
    the math). Ring caches are window-sized already and are never
    cropped. ``attn_spec`` picks the decode-attention kernel flavor."""
    B, T, _ = h.shape
    q, k, v = _proj_qkv(cfg, p, h)
    pos1d = positions[0] if positions.ndim == 3 else positions
    q = positional_rotate(q, positions, cfg)
    k = positional_rotate(k, positions, cfg)
    ring = window > 0
    new_cache = cache
    if mode == "decode":
        kc, vc = update_cache(cache["k"], cache["v"], k, v,
                              pos1d[:, 0], ring=ring, slots=slots)
        # crop BEFORE the slot gather: the gather then copies only the
        # valid-prefix blocks, not the pool's full padded seq axis —
        # at long max_seq the full-S gather dominates the whole step
        kc_r, vc_r = kc, vc
        if kv_len is not None and not ring and kv_len < kc.shape[1]:
            kc_r, vc_r = kc[:, :kv_len], vc[:, :kv_len]
        k_att = kc_r if slots is None else kc_r[slots]
        v_att = vc_r if slots is None else vc_r[slots]
        out = decode_attention(q, k_att, v_att, pos1d[:, 0], window=window,
                               ring=ring, spec=attn_spec)
        new_cache = dict(cache, k=kc, v=vc)
    else:
        out = flash_attention(q, k, v, pos1d, pos1d, causal=True,
                              window=window)
        if mode == "prefill":
            # bulk build (positions are 0..T-1 in prefill) — no scatter
            kc, vc = prefill_cache(cache["k"], cache["v"], k, v, ring=ring)
            new_cache = dict(cache, k=kc, v=vc)
    out = out.reshape(B, T, cfg.n_heads * cfg.d_head)
    return out @ p["wo"], new_cache


def _cross_attention(cfg, p, h, enc_states, mode, cache, slots=None):
    """Decoder cross-attention over encoder states (RETRO/EncDec path)."""
    B, T, _ = h.shape
    hn = rms_norm(h, p["lnx"], cfg.norm_eps)
    q = (hn @ p["xwq"]).reshape(B, T, cfg.n_heads, cfg.d_head)
    if mode == "decode" and cache is not None and "xk" in cache:
        xk, xv = cache["xk"], cache["xv"]
        if slots is not None:           # pooled cross-KV: gather wave rows
            xk, xv = xk[slots], xv[slots]
    else:
        S = enc_states.shape[1]
        xk = (enc_states @ p["xwk"]).reshape(B, S, cfg.n_kv_heads, cfg.d_head)
        xv = (enc_states @ p["xwv"]).reshape(B, S, cfg.n_kv_heads, cfg.d_head)
    S = xk.shape[1]
    qpos = jnp.zeros((B, T), jnp.int32)
    kpos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    out = flash_attention(q, xk, xv, qpos, kpos, causal=False)
    out = out.reshape(B, T, cfg.n_heads * cfg.d_head)
    new_cache = cache
    if mode == "prefill" and cache is not None and "xk" in cache:
        new_cache = dict(cache, xk=xk.astype(cache["xk"].dtype),
                         xv=xv.astype(cache["xv"].dtype))
    return h + out @ p["xwo"], new_cache


def _ffn(cfg, p, x):
    if cfg.block == "moe":
        B, T, d = x.shape
        flat = x.reshape(B * T, d)
        out = moe_lib.moe_ffn(flat, p["router"], p["wg"], p["wu"], p["wd"],
                              cfg.top_k, act=cfg.act)
        return out.reshape(B, T, d).astype(x.dtype)
    return swiglu(x, p["wg"], p["wu"], p["wd"], cfg.act)


def apply_block(cfg: ModelConfig, p: Params, h: jnp.ndarray,
                positions: jnp.ndarray, mode: str, cache: Optional[Params],
                window: int, enc_states=None, slots=None, kv_len=None,
                attn_spec=None):
    """One layer. Returns (h, new_cache).

    With ``slots`` (batched-slot decode over a KV-cache pool) the cache
    leaves keep their pooled batch dim ``P``; recurrent states (SSM /
    conv / RWKV) are gathered to the wave rows for the step and scattered
    back, while attention K/V use the O(W)-write path in
    ``_self_attention``."""
    if cfg.block == "rwkv6":
        rp = ssm_lib.RWKV6Params(**{f: p[f] for f in
                                    ssm_lib.RWKV6Params._fields})
        if cache is not None:
            c = cache if slots is None else jax.tree.map(
                lambda a: a[slots], cache)
            st = ssm_lib.RWKVState(wkv=c["wkv"], shift_t=c["st"],
                                   shift_c=c["sc"])
        else:
            st = ssm_lib.rwkv6_init_state(h.shape[0], cfg.n_heads,
                                          cfg.d_head, cfg.d_model, h.dtype)
        y, wkv, sh_t = ssm_lib.rwkv6_time_mix_chunked(
            rp, rms_norm(h, p["ln1"], cfg.norm_eps), st, cfg.n_heads)
        h = h + y
        y2, sh_c = ssm_lib.rwkv6_channel_mix(
            rp, rms_norm(h, p["ln2"], cfg.norm_eps), st.shift_c)
        h = h + y2
        if cache is None:
            return h, None
        rows = dict(wkv=wkv, st=sh_t, sc=sh_c)
        if slots is None:
            return h, rows
        return h, {key: cache[key].at[slots].set(
            rows[key].astype(cache[key].dtype)) for key in rows}

    hn = rms_norm(h, p["ln1"], cfg.norm_eps)
    attn_out, new_cache = _self_attention(cfg, p, hn, positions, mode,
                                          cache if cache is not None else
                                          dict(k=None, v=None), window,
                                          slots=slots, kv_len=kv_len,
                                          attn_spec=attn_spec)
    if cache is None:
        new_cache = None
    if cfg.block == "hybrid":
        mp = jax.tree.map(lambda x: x, p["mamba"])
        sstate = None
        if cache is not None:
            sstate = ((cache["ssm"], cache["conv"]) if slots is None
                      else (cache["ssm"][slots], cache["conv"][slots]))
        ssm_out, (ssm_s, conv_s) = ssm_lib.mamba_scan(mp, hn, sstate)
        attn_out = 0.5 * (rms_norm(attn_out, p["ln_attn_out"], cfg.norm_eps)
                          + rms_norm(ssm_out, p["ln_ssm_out"], cfg.norm_eps))
        if cache is not None:
            if slots is None:
                new_cache = dict(new_cache, ssm=ssm_s,
                                 conv=conv_s.astype(cache["conv"].dtype))
            else:
                new_cache = dict(
                    new_cache,
                    ssm=cache["ssm"].at[slots].set(ssm_s),
                    conv=cache["conv"].at[slots].set(
                        conv_s.astype(cache["conv"].dtype)))
    h = h + attn_out
    if enc_states is not None and "xwq" in p:
        h, new_cache = _cross_attention(cfg, p, h, enc_states, mode,
                                        new_cache, slots=slots)
    h = h + _ffn(cfg, p, rms_norm(h, p["ln2"], cfg.norm_eps))
    return h, new_cache


# ---------------------------------------------------------------------------
# stack: scan over layer groups
# ---------------------------------------------------------------------------

def apply_stack(cfg: ModelConfig, classes_params: Params, h: jnp.ndarray,
                positions: jnp.ndarray, mode: str,
                caches: Optional[Params] = None, enc_states=None,
                remat: bool = False, slots=None, kv_len=None,
                attn_spec=None) -> Tuple[jnp.ndarray, Optional[Params]]:
    """Apply all n_layers in order. Layers are grouped by the static
    ``layer_pattern`` cycle; a lax.scan over whole cycles keeps HLO small.

    ``slots`` (decode only): the caches are a KV-cache pool of ``P`` slot
    rows while ``h`` is one wave of ``W`` active rows — see
    ``decode_wave``. The scan carry stays pool-shaped throughout.
    ``kv_len``/``attn_spec`` are static decode-attention knobs (see
    ``_self_attention``) applied uniformly to every full-cache layer."""
    pattern = cfg.layer_pattern
    period = len(pattern)
    n_full, tail = divmod(cfg.n_layers, period)
    # per pattern-slot: (class name, #layers of that class per full cycle,
    #                    offset of this slot within the cycle's class layers)
    cnt = {c: pattern.count(c) for c in set(pattern)}
    off = []
    seen: Dict[str, int] = {}
    for c in pattern:
        off.append(seen.get(c, 0))
        seen[c] = seen.get(c, 0) + 1

    def layer_at(params_c, idx):
        return jax.tree.map(lambda a: a[idx], params_c)

    def apply_cycle(carry, g):
        h, caches_ = carry
        # pin activations to batch-over-dp: without this hint the SPMD
        # partitioner follows the FSDP weight sharding and replicates the
        # batch while splitting d — measured 34 TB/layer of activation
        # traffic for llama3-405b bwd (EXPERIMENTS.md §Perf iteration 5)
        h = constrain(h, "dp", None, None)
        for s, cls in enumerate(pattern):
            idx = g * cnt[cls] + off[s]
            p = layer_at(classes_params[cls], idx)
            window = cfg.window if cls == "local" else 0
            cache = (jax.tree.map(lambda a: a[idx], caches_["classes"][cls])
                     if caches_ is not None else None)
            h, new_cache = apply_block(cfg, p, h, positions, mode, cache,
                                       window, enc_states, slots=slots,
                                       kv_len=kv_len, attn_spec=attn_spec)
            if caches_ is not None:
                upd = jax.tree.map(
                    lambda a, nc: jax.lax.dynamic_update_index_in_dim(
                        a, nc.astype(a.dtype), idx, 0),
                    caches_["classes"][cls], new_cache)
                caches_ = dict(caches_,
                               classes=dict(caches_["classes"], **{cls: upd}))
        return (h, caches_), None

    body = jax.checkpoint(apply_cycle) if remat else apply_cycle
    if n_full > 0:
        (h, caches), _ = jax.lax.scan(body, (h, caches),
                                      jnp.arange(n_full))
    for t in range(tail):  # remainder layers, unrolled (< period of them)
        cls = pattern[t]
        idx = n_full * cnt[cls] + off[t]
        p = layer_at(classes_params[cls], idx)
        window = cfg.window if cls == "local" else 0
        cache = (jax.tree.map(lambda a: a[idx], caches["classes"][cls])
                 if caches is not None else None)
        h, new_cache = apply_block(cfg, p, h, positions, mode, cache, window,
                                   enc_states, slots=slots, kv_len=kv_len,
                                   attn_spec=attn_spec)
        if caches is not None:
            upd = jax.tree.map(
                lambda a, nc: jax.lax.dynamic_update_index_in_dim(
                    a, nc.astype(a.dtype), idx, 0),
                caches["classes"][cls], new_cache)
            caches = dict(caches, classes=dict(caches["classes"],
                                               **{cls: upd}))
    return h, caches


# ---------------------------------------------------------------------------
# full model entry points
# ---------------------------------------------------------------------------

def encode(params: Params, cfg: ModelConfig, enc_embeds: jnp.ndarray
           ) -> jnp.ndarray:
    """Encoder forward (bidirectional dense stack). enc_embeds [B, S, d]."""
    enc_cfg = ModelConfig(
        name=cfg.name + "-enc", n_layers=cfg.n_enc_layers,
        d_model=cfg.d_model, n_heads=cfg.n_heads,
        n_kv_heads=cfg.n_kv_heads, d_ff=cfg.d_ff, vocab_size=cfg.vocab_size,
        d_head=cfg.d_head, block="dense", qkv_bias=cfg.qkv_bias,
        rope_theta=cfg.rope_theta, norm_eps=cfg.norm_eps, act=cfg.act,
        dtype=cfg.dtype)
    B, S, _ = enc_embeds.shape
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    h = enc_embeds

    # bidirectional: reuse the stack in train mode but patch causality by
    # running attention non-causally — encoder blocks are dense/global only.
    classes = params["encoder"]["classes"]
    p_all = classes["global"]

    def body(h, idx):
        p = jax.tree.map(lambda a: a[idx], p_all)
        hn = rms_norm(h, p["ln1"], cfg.norm_eps)
        q, k, v = _proj_qkv(enc_cfg, p, hn)
        q = positional_rotate(q, pos, enc_cfg)
        k = positional_rotate(k, pos, enc_cfg)
        out = flash_attention(q, k, v, pos, pos, causal=False)
        out = out.reshape(B, S, cfg.n_heads * cfg.d_head)
        h = h + out @ p["wo"]
        h = h + swiglu(rms_norm(h, p["ln2"], cfg.norm_eps),
                       p["wg"], p["wu"], p["wd"], cfg.act)
        return h, None

    h, _ = jax.lax.scan(body, h, jnp.arange(cfg.n_enc_layers))
    return rms_norm(h, params["encoder"]["final_norm"], cfg.norm_eps)


def embed_tokens(params: Params, tokens: jnp.ndarray) -> jnp.ndarray:
    return params["embed"][tokens]


def unembed(params: Params, cfg: ModelConfig, h: jnp.ndarray) -> jnp.ndarray:
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    head = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
    return h @ head


def forward(params: Params, cfg: ModelConfig,
            tokens: Optional[jnp.ndarray] = None,
            embeds: Optional[jnp.ndarray] = None,
            positions: Optional[jnp.ndarray] = None,
            mode: str = "train",
            caches: Optional[Params] = None,
            enc_states: Optional[jnp.ndarray] = None,
            remat: bool = False, return_hidden: bool = False, slots=None,
            kv_len=None, attn_spec=None):
    """Full forward. Provide `tokens` [B,T] or `embeds` [B,T,d] (modality
    stubs). Returns (logits [B,T,V], caches[, hidden])."""
    h = embed_tokens(params, tokens) if embeds is None else embeds
    h = constrain(h, "dp", None, None)
    B, T = h.shape[:2]
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
    h, caches = apply_stack(cfg, params["classes"], h, positions, mode,
                            caches, enc_states, remat=remat, slots=slots,
                            kv_len=kv_len, attn_spec=attn_spec)
    h = constrain(h, "dp", None, None)
    logits = unembed(params, cfg, h)
    if return_hidden:
        return logits, caches, h
    return logits, caches


def hidden_states(params: Params, cfg: ModelConfig, tokens: jnp.ndarray
                  ) -> jnp.ndarray:
    """Last-layer hidden states [B, T, d] of a causal forward over
    ``tokens`` [B, T] — the ``return_hidden`` output of ``forward``
    without the unembedding, so a corpus pass never materializes
    [B, T, vocab] logits."""
    h = embed_tokens(params, tokens)
    B, T = h.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
    h, _ = apply_stack(cfg, params["classes"], h, positions, "train", None,
                       None)
    return h


def prefill(params: Params, cfg: ModelConfig, caches: Params,
            tokens: jnp.ndarray, length, enc_states=None):
    """Serving prefill. tokens [B,T] hold the prompt at positions
    < ``length`` (an int32 scalar, may be traced) and anything after it:
    causal attention keeps the tail out of every prompt row, and its K/V
    land at cache positions >= ``length``. Returns (logits [B,V], caches,
    hidden [B,d]) at position ``length - 1``; no other position is
    unembedded."""
    B, T = tokens.shape
    pos = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
    if cfg.rope_mode == "mrope":
        pos = jnp.broadcast_to(pos[None], (3, B, T))
    h = constrain(embed_tokens(params, tokens), "dp", None, None)
    h, caches = apply_stack(cfg, params["classes"], h, pos, "prefill",
                            caches, enc_states)
    h = constrain(h, "dp", None, None)
    last = jax.lax.dynamic_index_in_dim(h, length - 1, axis=1,
                                        keepdims=False)
    return unembed(params, cfg, last), caches, last


def decode_step(params: Params, cfg: ModelConfig, caches: Params,
                token: jnp.ndarray, position: jnp.ndarray,
                enc_states: Optional[jnp.ndarray] = None,
                return_hidden: bool = False, attn_spec=None):
    """One serving step. token [B,1] int32; position [B] int32.
    Returns (logits [B,V], new caches[, hidden [B,d]]). The hidden state is
    the RALM retrieval query (paper step 1, kNN-LM style)."""
    B = token.shape[0]
    pos = position[:, None]
    if cfg.rope_mode == "mrope":
        pos = jnp.broadcast_to(pos[None], (3, B, 1))
    out = forward(params, cfg, tokens=token, positions=pos, mode="decode",
                  caches=caches, enc_states=enc_states,
                  return_hidden=return_hidden, attn_spec=attn_spec)
    if return_hidden:
        logits, caches, h = out
        return logits[:, 0], caches, h[:, 0]
    logits, caches = out
    return logits[:, 0], caches


def decode_wave(params: Params, cfg: ModelConfig, caches: Params,
                token: jnp.ndarray, slots: jnp.ndarray,
                position: jnp.ndarray,
                enc_states: Optional[jnp.ndarray] = None,
                return_hidden: bool = False, kv_len=None, attn_spec=None):
    """One serving step for a whole wave over a slotted KV-cache pool.

    ``caches`` hold ``P`` pooled slot rows (built with
    ``init_cache(cfg, P, ...)``); ``token`` [W,1] / ``slots`` [W] /
    ``position`` [W] describe the wave: row ``w`` advances the sequence
    living in pool slot ``slots[w]`` at absolute position ``position[w]``.
    ``enc_states`` (encdec) is already gathered to wave rows [W, S, d].

    ``kv_len`` (static) crops every full-cache attention read to the
    wave's block-aligned valid prefix; ``attn_spec`` selects the
    decode-attention kernel (grouped ref / streaming Pallas / legacy
    einsum oracle) — see ``models/attention.decode_attention``.

    Returns (logits [W,V], new pool caches[, hidden [W,d]]). One call =
    one LM dispatch for every active sequence, regardless of how many
    requests the wave spans — the ChamLM analogue of the retrieval
    service's coalesced batch (paper §5 batched GPU pool)."""
    W = token.shape[0]
    pos = position[:, None]
    if cfg.rope_mode == "mrope":
        pos = jnp.broadcast_to(pos[None], (3, W, 1))
    out = forward(params, cfg, tokens=token, positions=pos, mode="decode",
                  caches=caches, enc_states=enc_states, slots=slots,
                  return_hidden=return_hidden, kv_len=kv_len,
                  attn_spec=attn_spec)
    if return_hidden:
        logits, caches, h = out
        return logits[:, 0], caches, h[:, 0]
    logits, caches = out
    return logits[:, 0], caches


def lm_loss(params: Params, cfg: ModelConfig, batch: Dict[str, jnp.ndarray],
            remat: bool = True) -> jnp.ndarray:
    """Next-token cross-entropy (mean over tokens; labels < 0 = ignore).

    batch keys: "tokens" [B,T] or "embeds" [B,T,d] (modality stubs);
    "labels" [B,T]; optional "positions" ([B,T] or [3,B,T] for mrope);
    optional "enc_embeds" [B,S,d] (encdec: retrieved-chunk embeddings)."""
    enc_states = (encode(params, cfg, batch["enc_embeds"])
                  if "enc_embeds" in batch else None)
    logits, _ = forward(params, cfg, tokens=batch.get("tokens"),
                        embeds=batch.get("embeds"),
                        positions=batch.get("positions"), mode="train",
                        enc_states=enc_states, remat=remat)
    labels = batch["labels"]
    logits = logits.astype(jnp.float32)
    mask = labels >= 0
    safe = jnp.maximum(labels, 0)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
    nll = (lse - ll) * mask
    return nll.sum() / jnp.maximum(mask.sum(), 1)
