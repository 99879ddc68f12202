"""The plain reference for sharded frames: the version-4 container.

It imports nothing of the system under test.  A version-4 frame is the
version-3 frame (`bench/reference.py`) with a ``shard_count`` after the
content size and a producing-shard id in each table entry
(docs/frame-format.md):

    frame := "LZ4R" | 4 | block_count u32 | content_size u64 | shard_count u32
             | block_count x (usize u32 | csize_flag u32 | crc32 u32 | shard u32)
             | payloads

The partition rule is stated here too: ``S`` shards take contiguous runs of
``n`` blocks in order, the first ``n % S`` shards one block longer.  Blocks
are encoded and decoded by `bench/reference.py` unchanged.
"""
from __future__ import annotations

import struct

from bench.reference import MAGIC, RAW_FLAG, FormatError

VERSION = 4


def parse_frame(frame: bytes) -> dict:
    """Header and block table of a version-4 frame.

    Returns ``{"version", "content_size", "shard_count", "blocks"}``; each
    block is ``{"usize", "csize", "raw", "crc", "shard", "offset", "payload"}``.
    The shard column is returned as written, for the caller to compare with
    `shard_ids`.
    """
    if len(frame) < 21 or frame[:4] != MAGIC:
        raise FormatError("not a frame header")
    version, count = frame[4], struct.unpack_from("<I", frame, 5)[0]
    if version != VERSION:
        raise FormatError(f"version {version}")
    content_size, shard_count = struct.unpack_from("<QI", frame, 9)
    table, pos = 21, 21 + count * 16
    if pos > len(frame):
        raise FormatError("truncated block table")
    blocks = []
    for k in range(count):
        usize, cf, crc, shard = struct.unpack_from("<IIII", frame, table + k * 16)
        csize = cf & ~RAW_FLAG
        blocks.append({"usize": usize, "csize": csize, "raw": bool(cf & RAW_FLAG),
                       "crc": crc, "shard": shard, "offset": pos,
                       "payload": frame[pos: pos + csize]})
        pos += csize
    if pos != len(frame):
        raise FormatError(f"frame is {len(frame)} bytes, table accounts for {pos}")
    if sum(b["usize"] for b in blocks) != content_size:
        raise FormatError("content size differs from the table")
    return {"version": version, "content_size": content_size,
            "shard_count": shard_count, "blocks": blocks}


def shard_ids(n_blocks: int, shards: int) -> list[int]:
    """The shard of each of ``n_blocks`` blocks under the partition rule."""
    base, longer = divmod(n_blocks, shards)
    out = []
    for s in range(shards):
        out += [s] * (base + (s < longer))
    return out
