"""Device programs launched per page-resume request (trace's program events)."""


def read(ctx):
    if ctx.trace is None or not ctx.requests:
        return None
    return sum(d["launches"] for d in ctx.trace["devices"]) / ctx.requests
