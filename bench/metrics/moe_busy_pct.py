"""Share of the traced window (mean over the cell's chips) in which the
routed experts' grouped matmuls ran on the chip: the device operations of
the megablox ``gmm`` kernel (gate, up and down projections of every MoE
layer, in decode waves and prefills), by the name the chip's trace gives
them. None where the trace holds no such operation."""
import spans


def read(ctx):
    return spans.kernel_busy(ctx, ("gmm",))
