"""Arguments plus temporaries of the engine's compiled per-token program,
per chip, from its compile-time memory analysis."""


def read(ctx):
    p = ctx.program
    return (p["argument_bytes"] + p["temp_bytes"]) * 1e-9
