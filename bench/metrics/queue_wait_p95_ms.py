"""95th percentile of the time a request waited between its arrival at
the gateway and its admission to the KV pool, as the server stamped it
(``RequestTiming``, streamed back in the last chunk), over the requests
due in the window."""
import numpy as np


def read(ctx):
    waits = [r.summary.get("queue_wait_ms") for r in ctx.results]
    waits = [w for w in waits if w is not None]
    return float(np.percentile(waits, 95)) if waits else None
