"""Device time of the write graph per user MiB of the traced requests.

The write graph is `LZ4Engine`'s jitted vmap of `compress_block_bytes`; its
program is named `jit_compress_block_bytes` in the trace.
"""
WRITE_GRAPH = ("jit_compress_block_bytes",)


def read(ctx):
    if ctx.trace is None or not ctx.user_bytes:
        return None
    s = ctx.program_s(*WRITE_GRAPH)
    return 1e3 * s / (ctx.user_bytes / (1 << 20)) if s else None
