"""Share of the traced window (mean over the cell's chips) in which no
operation ran on the chip while the engine did a wave's host work after
its search: the program's spans ``ralm.wave.mix`` (the search results,
their payloads and the kNN-LM mixture), ``ralm.wave.sample`` (the next
tokens) and ``ralm.wave.stream`` (the emit loop, with the wave's host
sync and the streaming hook)."""
import spans


def read(ctx):
    return spans.idle_under(ctx, ("ralm.wave.mix", "ralm.wave.sample",
                                  "ralm.wave.stream"))
