"""1 - (union of device-op intervals / traced window), in %, averaged over
the chips."""


def read(ctx):
    tr = ctx.trace
    if tr is None or tr.window_ns <= 0 or not tr.chips:
        return None
    return 100.0 * (1.0 - tr.busy_ns() / tr.window_ns)
