"""The operations each token served in the window requires, from the
model's and the datastore's shapes (``step_mfu_pct``).

These count what the algorithm needs, not what a kernel happens to do:
no one-hot expansion, no padded rows or blocks. A later program that does
the same work another way is measured against the same numbers. An
operation is a multiply or an add (a multiply-add is two).
"""
from __future__ import annotations

from typing import Dict


def _block_params(model: Dict) -> int:
    d, f = model["d_model"], model["d_ff"]
    H, KV, dh = model["n_heads"], model["n_kv_heads"], model["d_head"]
    return d * H * dh + 2 * d * KV * dh + H * dh * d + 3 * d * f


def lm_token(model: Dict, kv_len: int) -> float:
    """Operations of one decode step at ``kv_len`` cached positions: the
    weight matmuls, attention, and the tied-embedding logits."""
    L = model["n_layers"]
    return (2 * L * _block_params(model)
            + 2 * model["d_model"] * model["vocab_size"]
            + L * 4 * kv_len * model["n_heads"] * model["d_head"])


def prefill(model: Dict, tokens: int) -> float:
    """Operations of a causal prefill of ``tokens`` positions, with the
    logits of the last position only (the one the first token needs)."""
    L = model["n_layers"]
    attn = (L * 4 * model["n_heads"] * model["d_head"]
            * tokens * (tokens + 1) / 2)
    return (2 * L * _block_params(model) * tokens + attn
            + 2 * model["d_model"] * model["vocab_size"])


def retrieval_token(ds: Dict, dim: int) -> float:
    """Operations of one query's search that the MXU-shaped work counts:
    the probe against every centroid and the distance tables (non-residual
    PQ: one table per query, not per probed list)."""
    return 2 * ds["nlist"] * dim + 2 * ds["m"] * ds["ksub"] * (dim // ds["m"])
