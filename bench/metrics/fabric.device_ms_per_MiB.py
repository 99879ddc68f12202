"""Device time of the sharded write program, summed over the chips, per user
MiB of the traced requests.

The program is the fabric's jit of `shard_map(vmap(compress_block_bytes))`,
named `jit_fabric_compress` in the trace; before it had a name of its own it
carried the one-chip write graph's, `jit_compress_block_bytes`, and the
sharded cell runs no other write graph in its window.  Comparable with
`write.device_ms_per_MiB`, the one-chip write graph's.
"""
FABRIC_WRITE = ("jit_fabric_compress", "jit_compress_block_bytes")


def read(ctx):
    if ctx.trace is None or not ctx.user_bytes:
        return None
    s = ctx.program_s(*FABRIC_WRITE)
    return 1e3 * s / (ctx.user_bytes / (1 << 20)) if s else None
