"""Device time per step of the search-stage kernels (``search_wave*`` and
``uct_select*``), averaged over the chips."""

PREFIXES = ("search_wave", "uct_select")


def read(ctx):
    tr = ctx.trace
    if tr is None or not tr.steps:
        return None
    ns = tr.kernel_ns(PREFIXES)
    return None if ns is None else ns / len(tr.steps) * 1e-6
