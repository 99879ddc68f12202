"""KiB put host -> device per page-resume request: the program's counter
`decode.upload_bytes` over the traced requests (telemetry is on, and its
registry reset, for exactly those)."""


def read(ctx):
    from repro import obs

    n = obs.registry().snapshot()["counters"].get("decode.upload_bytes")
    if n is None or not ctx.requests:
        return None
    return n / 1024 / ctx.requests
