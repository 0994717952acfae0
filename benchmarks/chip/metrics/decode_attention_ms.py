"""Device time per step of the ``decode_attention`` kernel, averaged over
the chips."""


def read(ctx):
    tr = ctx.trace
    if tr is None or not tr.steps:
        return None
    ns = tr.kernel_ns(("decode_attention",))
    return None if ns is None else ns / len(tr.steps) * 1e-6
