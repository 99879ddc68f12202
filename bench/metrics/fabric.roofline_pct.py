"""The sharded write program's share of its roofline: the least time the
chips' HBM needs to move the algorithm's data, over the program's device
time summed over the chips.

The data is each block's input bytes and its emitted payload bytes, read
from the version-4 block tables (`bench/work_v4.py`); the bound is
bandwidth, as for `write.roofline_pct`.
"""
from bench.work_v4 import write_graph_bytes

FABRIC_WRITE = ("jit_fabric_compress", "jit_compress_block_bytes")


def read(ctx):
    if ctx.trace is None:
        return None
    s = ctx.program_s(*FABRIC_WRITE)
    if not s:
        return None
    return 100 * write_graph_bytes(ctx.op.done) / ctx.peaks["hbm_bytes_per_s"] / s
