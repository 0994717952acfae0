"""95th percentile of every gap between consecutive tokens of one request
that ends inside the window, on the benchmark's own clock (stamped when
``ServingEngine.step`` returns)."""
from chipbench.stats import percentile


def read(ctx):
    return percentile(ctx.gaps_s, 95) * 1e3 if ctx.gaps_s else None
