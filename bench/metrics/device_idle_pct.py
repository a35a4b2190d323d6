"""Share of the traced window in which no operation ran on the chip
(mean over the cell's chips)."""
import devtrace


def read(ctx):
    if not ctx.win or not ctx.planes:
        return None
    lo, hi = ctx.win
    busy = [devtrace.busy_ns(ctx.trace, p, lo, hi) for p in ctx.planes]
    return 100.0 * (1.0 - sum(busy) / len(busy) / (hi - lo))
