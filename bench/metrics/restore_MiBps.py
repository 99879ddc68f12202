"""User bytes restored as verified device arrays in the window, over the window."""


def read(ctx):
    return ctx.user_bytes / (1 << 20) / ctx.elapsed_s if ctx.requests else None
