"""Intrinsic work of the sharded write graph, counted from the version-4
block tables: the same count as `bench/work.py` makes from version-3 frames."""
from __future__ import annotations

from bench.reference_v4 import parse_frame


def write_graph_bytes(done: list[tuple[int, bytes]]) -> int:
    """Bytes the paper's writer must read and emit for the written frames:
    each block's input bytes plus its payload bytes."""
    return sum(b["usize"] + b["csize"] for _, frame in done
               for b in parse_frame(frame)["blocks"])
