"""The model operations the window's work needs (``chipbench/work.py``),
over the window's length times the chips times the chip's bf16 peak, in %."""


def read(ctx):
    peak = ctx.peak["bf16_flops_per_s"] * ctx.chips * ctx.window_s
    return 100.0 * ctx.flops / peak if peak > 0 and ctx.flops else None
