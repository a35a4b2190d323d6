"""Programs JAX built inside the window, compiled or loaded from its
persistent cache (its backend-compile events over the window): work the
host does per request or per wave that a warm-up cannot take out of the
window, such as a program traced anew on every admission."""


def read(ctx):
    return float(ctx.programs_built)
