"""95th percentile of every gap between consecutive streamed tokens of
the requests due in the window."""
import numpy as np


def read(ctx):
    gaps = [g for r in ctx.results for g in np.diff(r.token_times) * 1e3]
    return float(np.percentile(gaps, 95)) if gaps else None
