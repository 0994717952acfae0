"""Output tokens committed inside the measured window, over its length."""


def read(ctx):
    return ctx.tokens / ctx.window_s if ctx.window_s > 0 else None
