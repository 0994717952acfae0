"""From process start to the start of the window: JAX, weights, the
per-token program (compiled, or loaded from the compile cache), warm-up
steps and the slots filled."""


def read(ctx):
    return ctx.setup_s
