"""Intrinsic work of a kernel, counted from shapes and sizes.

The count is of the algorithm's data in and out, not of any
implementation's intermediates, so a change that removes a stage of the
program cannot make it stale.
"""
from __future__ import annotations

from bench.reference import parse_frame


def write_graph_bytes(done: list[tuple[int, bytes]]) -> int:
    """Bytes the paper's writer must read and emit for the written frames:
    each block's input bytes plus its payload bytes."""
    total = 0
    for _, frame in done:
        for b in parse_frame(frame)["blocks"]:
            total += b["usize"] + b["csize"]
    return total
