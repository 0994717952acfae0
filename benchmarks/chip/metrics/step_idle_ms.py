"""Device-idle time inside the benchmark's span around each
``ServingEngine.step`` (admission, prefix upload, token sync on the host),
in ms per step, averaged over the chips."""


def read(ctx):
    tr = ctx.trace
    if tr is None or not tr.steps or not tr.chips:
        return None
    return tr.idle_in_steps_ns() / len(tr.steps) * 1e-6
