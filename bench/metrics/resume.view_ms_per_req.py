"""Host time per page-resume request issuing the eager device programs that
turn an uploaded block into the page: spans `decode.slice` (concatenate and
slice) and `serving.view` (reshape and bitcast)."""

SPANS = ("decode.slice", "serving.view")


def read(ctx):
    if not ctx.requests or not any(s["name"] in SPANS for s in ctx.spans):
        return None
    return 1e3 * sum(ctx.span_s(n) for n in SPANS) / ctx.requests
