"""95th percentile, over every request due in the window, of the first
token's arrival at the client minus the request's scheduled send time.
A request that never got a token counts as infinite."""
import numpy as np


def read(ctx):
    ttft = [(r.token_times[0] - r.due) * 1e3 if r.token_times else np.inf
            for r in ctx.results]
    return float(np.percentile(ttft, 95)) if ttft else None
