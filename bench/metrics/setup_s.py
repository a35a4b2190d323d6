"""Set-up time: process start to the first measured request (weights,
datastore, engine, warm-up, and in a run that compiles, compilation)."""


def read(ctx):
    return ctx.setup_s
