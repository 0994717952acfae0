"""Device time of the per-token search program per step: the union of op
intervals inside its executions (its module as the compile names it)."""


def read(ctx):
    tr = ctx.trace
    if tr is None or not tr.steps or not ctx.program["module"]:
        return None
    ns = tr.module_busy_ns(ctx.program["module"])
    return None if ns is None else ns / len(tr.steps) * 1e-6
