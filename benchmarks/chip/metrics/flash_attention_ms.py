"""Device time per step of the ``flash_attention`` kernel, averaged over
the chips."""


def read(ctx):
    tr = ctx.trace
    if tr is None or not tr.steps:
        return None
    ns = tr.kernel_ns(("flash_attention",))
    return None if ns is None else ns / len(tr.steps) * 1e-6
