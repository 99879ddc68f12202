"""Device time of the read graphs per restored MiB: the decode graph
(`jit_decode_gather`) and the in-graph CRC (`jit_crc32_bytes`)."""
READ_GRAPHS = ("jit_decode_gather", "jit_crc32_bytes")


def read(ctx):
    if ctx.trace is None or not ctx.user_bytes:
        return None
    s = ctx.program_s(*READ_GRAPHS)
    return 1e3 * s / (ctx.user_bytes / (1 << 20)) if s else None
