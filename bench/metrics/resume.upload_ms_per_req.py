"""Host time per page-resume request putting blocks onto the device (span
`decode.upload`: the host -> device put of each raw block a page sits in)."""


def read(ctx):
    if not ctx.requests or not any(s["name"] == "decode.upload" for s in ctx.spans):
        return None
    return 1e3 * ctx.span_s("decode.upload") / ctx.requests
