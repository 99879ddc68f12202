"""Integrity checks per page-resume request (span `decode.verify`: the host
CRC of a raw block, or the sync on an in-graph CRC)."""


def read(ctx):
    if not ctx.requests or not ctx.spans:
        return None
    return 1e3 * ctx.span_s("decode.verify") / ctx.requests
