"""Share of the traced window (mean over the cell's chips) in which no
operation ran on the chip while the engine prefilled an admitted
request: the program's spans ``ralm.prefill`` (the prompt's forward
pass) and ``ralm.prefill.scatter`` (its KV rows into the pool)."""
import spans


def read(ctx):
    return spans.idle_under(ctx, ("ralm.prefill", "ralm.prefill.scatter"))
