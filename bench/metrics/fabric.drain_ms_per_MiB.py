"""Host time spent fetching compressed rows out of the write output, per
user MiB of the traced requests: the program's `compress.drain` spans."""


def read(ctx):
    s = ctx.span_s("compress.drain")
    return 1e3 * s / (ctx.user_bytes / (1 << 20)) if s and ctx.user_bytes else None
