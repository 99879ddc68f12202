"""The write graph's share of its roofline: the least time the chip's HBM
needs to move the algorithm's data, over the write graph's device time.

The data is each block's input bytes and its emitted payload bytes
(`bench/work.py`); the bound is bandwidth, as the scheme does no
arithmetic to speak of.
"""
from bench.work import write_graph_bytes

WRITE_GRAPH = ("jit_compress_block_bytes",)


def read(ctx):
    if ctx.trace is None:
        return None
    s = ctx.program_s(*WRITE_GRAPH)
    if not s:
        return None
    return 100 * write_graph_bytes(ctx.op.done) / ctx.peaks["hbm_bytes_per_s"] / s
