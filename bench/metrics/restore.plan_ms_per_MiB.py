"""Host planning of the device decode per restored MiB (span `decode.plan`)."""


def read(ctx):
    if not ctx.user_bytes or not ctx.spans:
        return None
    return 1e3 * ctx.span_s("decode.plan") / (ctx.user_bytes / (1 << 20))
