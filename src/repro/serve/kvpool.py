"""``KVCachePool`` — one preallocated, slotted KV cache for every active
sequence.

Before this pool, each ``SequenceState`` owned a private per-sequence
cache pytree and the scheduler paid one LM dispatch *per sequence* per
wave. The pool makes the whole wave one batch: every cache leaf carries
a pooled batch dim of ``capacity + 1`` slot rows (the extra row is a
scratch slot that absorbs wave padding), admission assigns a sequence's
prompt rows to free slots, prefill scatters its ragged-length KV into
them, and completion frees them for reuse. ``transformer.decode_wave``
then advances any subset of slots as a single dispatch.

Wave sizes are bucketed to powers of two (the same shape-bucketing the
``RetrievalService`` applies to query batches) so continuous batching —
where the active row count changes every step — compiles O(log capacity)
decode graphs instead of one per wave size. Padding rows all point at
the scratch slot: they gather/scatter only don't-care state and their
outputs are dropped, so they never perturb live slots.

The pool grows on demand (slot rows double; the sequence axis extends to
the longest admitted request) unless constructed with a fixed capacity,
in which case admission defers until completions free slots — the
admission-control behavior the scheduler exposes as ``max_active`` does
for request counts, here in units of KV slot rows.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import transformer as tf
from repro.models.config import ModelConfig
from repro.obs.trace import NULL_TRACER
from repro.retrieval.service import next_pow2

__all__ = ["KVCachePool", "PoolStats", "next_pow2"]


@dataclasses.dataclass
class PoolStats:
    """Slot-lifecycle accounting (benchmarks + tests)."""
    allocs: int = 0              # slot rows handed out
    releases: int = 0            # slot rows returned
    high_water: int = 0          # max slot rows in use at once
    slot_grows: int = 0          # capacity doublings
    seq_grows: int = 0           # sequence-axis extensions
    waves: int = 0               # decode waves dispatched
    wave_rows: int = 0           # live rows across all waves
    rewinds: int = 0             # speculation rollbacks (slot-row groups)
    rewound_tokens: int = 0      # KV positions logically discarded
    buckets: set = dataclasses.field(default_factory=set)  # compiled W's
    # length-aware decode attention (ragged-wave savings + jit churn)
    blocks_total: int = 0        # seq blocks a full-pool read would touch
    blocks_skipped: int = 0      # blocks cropped past the wave's max pos
    compiled: set = dataclasses.field(default_factory=set)
    #                            # distinct (wave bucket, kv_len,
    #                            # capacity, max_seq) decode graphs —
    #                            # the recompile observable; pool shape
    #                            # is part of the key because growth
    #                            # events retrace every bucket

    def mean_wave(self) -> float:
        return self.wave_rows / self.waves if self.waves else 0.0

    @property
    def decode_compiles(self) -> int:
        """Distinct decode-wave graph keys traced so far. Continuous
        batching must keep this O(log capacity * max_seq/seq_block),
        not O(waves) — asserted in tests/test_decode_attn.py."""
        return len(self.compiled)

    def skip_fraction(self) -> float:
        return (self.blocks_skipped / self.blocks_total
                if self.blocks_total else 0.0)


@functools.partial(jax.jit, donate_argnums=(0,))
def _scatter_rows(pool: Any, rows: Any, slots: jnp.ndarray) -> Any:
    """Write per-request cache rows (batch dim B) into pool slot rows.

    Leaves are [n_layers, P, ...] vs [n_layers, B, ...]; the pool arg is
    donated so XLA updates the slots in place."""
    return jax.tree.map(
        lambda p, r: p.at[:, slots].set(r.astype(p.dtype)), pool, rows)


class KVCachePool:
    """Slotted decode-cache pool owned by the engine (one per deployment).

    Slot ids are stable for a sequence's lifetime: ``alloc`` hands out the
    lowest free ids (deterministic reuse, which the tests rely on),
    ``release`` returns them. Index ``capacity`` is the scratch slot."""

    def __init__(self, cfg: ModelConfig, capacity: int, max_seq: int,
                 enc_len: int = 0, fixed: bool = False, seq_block: int = 1):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        if seq_block < 1:
            raise ValueError(f"seq_block must be >= 1, got {seq_block}")
        self.cfg = cfg
        self.capacity = capacity
        self.seq_block = seq_block           # seq-axis alignment quantum:
        #                                      attention reads are cropped
        #                                      to multiples of this, so the
        #                                      axis itself must be aligned
        self.max_seq = self._align(max_seq)
        self.enc_len = enc_len
        self.fixed = fixed                   # no auto-grow when True
        self.caches = tf.init_cache(cfg, capacity + 1, self.max_seq,
                                    enc_len=enc_len)
        self.enc: Optional[jnp.ndarray] = None   # [P+1, S_enc, d], lazy
        self._free: List[int] = list(range(capacity))
        self.stats = PoolStats()
        self.tracer = NULL_TRACER    # engine.set_tracer swaps a live one in

    # -- slot lifecycle -----------------------------------------------------

    @property
    def scratch(self) -> int:
        return self.capacity

    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def num_used(self) -> int:
        return self.capacity - len(self._free)

    def alloc(self, n: int) -> np.ndarray:
        """Claim ``n`` slot rows (lowest free ids first)."""
        if n > len(self._free):
            raise RuntimeError(
                f"KVCachePool exhausted: want {n} rows, {len(self._free)} "
                f"free of {self.capacity} (admission should have deferred)")
        self._free.sort()
        slots, self._free = self._free[:n], self._free[n:]
        self.stats.allocs += n
        self.stats.high_water = max(self.stats.high_water, self.num_used)
        if self.tracer.enabled:
            self.tracer.instant("kvpool.alloc", "kvpool",
                                args={"rows": n, "used": self.num_used,
                                      "capacity": self.capacity})
        return np.asarray(slots, np.int32)

    def release(self, slots: np.ndarray) -> None:
        self._free.extend(int(s) for s in slots)
        self.stats.releases += len(slots)
        if self.tracer.enabled:
            self.tracer.instant("kvpool.release", "kvpool",
                                args={"rows": len(slots),
                                      "used": self.num_used,
                                      "capacity": self.capacity})

    def rewind(self, slots: np.ndarray, keep_len: int,
               old_len: int) -> None:
        """Logically rewind ``slots`` from ``old_len`` valid KV
        positions back to ``keep_len`` (speculation rollback) — WITHOUT
        touching device memory.

        For full-length (linear) caches this is free by construction:
        decode attention derives validity from the row's *position*
        (slot index i is read iff i < kv_len and i <= pos — see
        ``kernels.decode_attn.ref.decode_validity``), so the stale
        suffix above ``keep_len`` is never read once the sequence's
        position moves back, and the replayed decodes overwrite it
        index-for-index. Ring (sliding-window) caches alias positions
        modulo the window, so a rewind deeper than one step would leave
        stale entries *inside* the live window where validity cannot
        mask them — rejected here; the engine caps speculation depth at
        1 for windowed models. Recurrent state (RWKV/SSM blocks) cannot
        be rewound at all: the state update is not invertible and old
        states are not retained.
        """
        if not (0 < keep_len <= old_len <= self.max_seq):
            raise ValueError(
                f"rewind wants 0 < keep_len <= old_len <= max_seq, got "
                f"keep_len={keep_len} old_len={old_len} "
                f"max_seq={self.max_seq}")
        dropped = old_len - keep_len
        if self.cfg.ssm_state > 0 or self.cfg.block in ("rwkv6", "hybrid"):
            raise ValueError(
                "KV rewind is undefined for recurrent-state blocks "
                f"(block={self.cfg.block!r}, ssm_state="
                f"{self.cfg.ssm_state}) — gate speculation off for "
                "this model")
        if dropped > 1 and self.cfg.window > 0 and \
                "local" in self.cfg.pattern_classes():
            raise ValueError(
                f"ring (window={self.cfg.window}) caches alias positions "
                f"modulo the window: rewinding {dropped} steps would "
                "leave stale rows inside the live window — speculation "
                "depth must be 1 for windowed models")
        self.stats.rewinds += 1
        self.stats.rewound_tokens += dropped * len(slots)
        if self.tracer.enabled:
            self.tracer.instant("kvpool.rewind", "kvpool",
                                args={"rows": len(slots),
                                      "keep_len": keep_len,
                                      "dropped": dropped})

    # -- wave shape bucketing ----------------------------------------------

    def _align(self, n: int) -> int:
        """Round ``n`` up to the pool's seq-block quantum."""
        b = self.seq_block
        return -(-n // b) * b

    def attn_len(self, max_pos: int, bucket: int) -> int:
        """Static attention length for one wave: the block-aligned valid
        prefix covering every row's position. The engine passes it into
        the jitted ``decode_wave`` so full-cache attention reads crop to
        ``kv_len`` instead of the pool's padded ``max_seq`` — the
        length-aware half of the decode-attention kernel's contract
        (the kernel's per-row-tile skip refines it further inside one
        dispatch). Also the bookkeeping point for the ragged-wave
        savings (``blocks_skipped``) and the jit-churn observable
        (``compiled`` keys are (wave bucket, kv_len) pairs)."""
        kv_len = min(self._align(max_pos + 1), self.max_seq)
        nb_full = self.max_seq // self.seq_block
        self.stats.blocks_total += nb_full
        self.stats.blocks_skipped += nb_full - kv_len // self.seq_block
        self.stats.compiled.add((bucket, kv_len, self.capacity,
                                 self.max_seq))
        return kv_len

    def bucket(self, n: int) -> int:
        """Pow2 wave-size bucket: bounds jit recompiles under continuous
        batching to O(log capacity) decode graphs."""
        b = next_pow2(n)
        self.stats.buckets.add(b)
        return b

    def pad_wave(self, tokens: jnp.ndarray, slots: np.ndarray,
                 positions: np.ndarray):
        """Pad a W-row wave to its pow2 bucket. Pad rows carry token 0 at
        position 0 against the scratch slot — they compute garbage that is
        sliced off and scatter only into the scratch row. ``tokens`` stays
        on device (no host sync); slots/positions are host arrays."""
        w = len(slots)
        self.stats.waves += 1
        self.stats.wave_rows += w
        pad = self.bucket(w) - w
        if pad:
            tokens = jnp.pad(tokens,
                             [(0, pad)] + [(0, 0)] * (tokens.ndim - 1))
            slots = np.concatenate(
                [slots, np.full((pad,), self.scratch, np.int32)])
            positions = np.concatenate(
                [positions, np.zeros((pad,), positions.dtype)])
        return tokens, slots, positions

    # -- prefill / encoder-state rows --------------------------------------

    def write_prefill(self, slots: np.ndarray, caches: Any) -> None:
        """Scatter a prefilled request's cache rows into its slots. The
        request cache must be built with the pool's ``max_seq`` so leaf
        shapes line up (the engine's ``start`` guarantees this)."""
        self.caches = _scatter_rows(self.caches, caches,
                                    jnp.asarray(slots))

    def write_enc(self, slots: np.ndarray, rows: jnp.ndarray) -> None:
        """Per-slot encoder states (encdec/RETRO): [B, S_enc, d] rows.

        All slots share one pooled enc buffer, so every write must keep
        the row shape of the first one — a silent reinit here would wipe
        other live slots' states. Widths diverge only in the degenerate
        RETRO config ``rag.k * rag.chunk_len < 8`` (prefill's neutral
        encoder floor is 8 tokens); that config needs ``wave=False``."""
        if self.enc is None:
            self.enc = jnp.zeros((self.capacity + 1,) + rows.shape[1:],
                                 rows.dtype)
        elif self.enc.shape[1:] != rows.shape[1:]:
            raise ValueError(
                f"pooled enc rows must keep shape {self.enc.shape[1:]}, "
                f"got {rows.shape[1:]} — heterogeneous encoder widths "
                "(rag.k * rag.chunk_len < 8) need the per-sequence path "
                "(wave=False)")
        self.enc = self.enc.at[jnp.asarray(slots)].set(rows)

    def gather_enc(self, slots: np.ndarray) -> Optional[jnp.ndarray]:
        return None if self.enc is None else self.enc[jnp.asarray(slots)]

    # -- growth -------------------------------------------------------------

    def grow_slots(self, new_capacity: int) -> None:
        """Double-style capacity growth: pad every leaf's slot axis. The
        old scratch row becomes a normal (garbage, free) slot — harmless,
        prefill rewrites whole rows at admission."""
        if self.fixed:
            raise RuntimeError("fixed-capacity pool cannot grow")
        if new_capacity <= self.capacity:
            return
        delta = new_capacity - self.capacity

        def pad_slots(a):
            widths = [(0, 0)] * a.ndim
            widths[1] = (0, delta)
            return jnp.pad(a, widths)

        self.caches = jax.tree.map(pad_slots, self.caches)
        if self.enc is not None:
            self.enc = jnp.pad(self.enc,
                               [(0, delta)] + [(0, 0)] * (self.enc.ndim - 1))
        self._free.extend(range(self.capacity, new_capacity))
        self.capacity = new_capacity
        self.stats.slot_grows += 1

    def grow_seq(self, new_max_seq: int) -> None:
        """Extend the sequence axis of full-length (non-ring) K/V leaves
        so longer requests fit. Written prefixes keep their positions
        (slot i of a full cache always holds absolute position i). The
        new length stays seq-block aligned."""
        new_max_seq = self._align(new_max_seq)
        if new_max_seq <= self.max_seq:
            return
        delta = new_max_seq - self.max_seq
        for cls, c in self.caches["classes"].items():
            ring = (cls == "local" and self.cfg.window > 0)
            if ring or "k" not in c:
                continue
            for key in ("k", "v"):
                a = c[key]
                widths = [(0, 0)] * a.ndim
                widths[2] = (0, delta)
                c[key] = jnp.pad(a, widths)
        self.max_seq = new_max_seq
        self.stats.seq_grows += 1
