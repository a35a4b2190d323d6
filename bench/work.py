"""The operations of one query's search, from the datastore's shapes
(``step_mfu_pct``). An LM's operations per token are its LM module's
``lm_token`` and ``prefill`` (``bench/lm/<name>.py``).

These count what the algorithm needs, not what a kernel happens to do:
no one-hot expansion, no padded rows or blocks. A later program that does
the same work another way is measured against the same numbers. An
operation is a multiply or an add (a multiply-add is two).
"""
from __future__ import annotations

from typing import Dict


def retrieval_token(ds: Dict, dim: int) -> float:
    """Operations of one query's search that the MXU-shaped work counts:
    the probe against every centroid and the distance tables (non-residual
    PQ: one table per query, not per probed list)."""
    return 2 * ds["nlist"] * dim + 2 * ds["m"] * ds["ksub"] * (dim // ds["m"])
