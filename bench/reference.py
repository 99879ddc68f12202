"""The plain reference the benchmark holds the system to.

It imports nothing of the system under test.  Three pieces:

* `encode_paper_block`: the paper's windowed scheme (arXiv:2409.12433,
  sections III-A and III-B: one match per parallel window of ``pws`` bytes,
  a hash table of ``2**hash_bits`` entries read before the window writes it,
  matches capped at ``max_match``) written out as LZ4 block bytes;
* `decode_block`: a byte-at-a-time LZ4 block decoder;
* `parse_frame`: the ``LZ4R`` frame header and block table
  (docs/frame-format.md, version 3).

A frame written by the system at the paper's settings must equal, block for
block, what these give.
"""
from __future__ import annotations

import struct
import zlib

import numpy as np

MIN_MATCH, MF_LIMIT, LAST_LITERALS = 4, 12, 5
HASH_PRIME = 2654435761
MAX_BLOCK = 65536
RAW_FLAG = 0x80000000
MAGIC = b"LZ4R"


# -- the paper's writer ------------------------------------------------------

def _candidates(buf: np.ndarray, hash_bits: int, pws: int):
    """Per position: its 4-byte word, and the latest earlier position in an
    earlier window with the same hash (-1 if none)."""
    d = buf.astype(np.uint32)
    words = d[:-3] | (d[1:-2] << 8) | (d[2:-1] << 16) | (d[3:] << 24)
    hashes = ((words * np.uint32(HASH_PRIME)) >> np.uint32(32 - hash_bits)).astype(np.int64)
    n = len(hashes)
    win = np.arange(n) // pws
    order = np.lexsort((np.arange(n), hashes))
    h, w = hashes[order], win[order]
    head = np.ones(n, bool)
    head[1:] = (h[1:] != h[:-1]) | (w[1:] != w[:-1])
    heads = np.nonzero(head)[0]
    before = np.full(len(heads), -1, np.int64)
    ok = heads > 0
    same = h[heads[ok] - 1] == h[heads[ok]]
    before[ok] = np.where(same, order[heads[ok] - 1], -1)
    cand = np.empty(n, np.int64)
    cand[order] = before[np.cumsum(head) - 1]
    return words, cand


def paper_sequences(chunk: bytes, hash_bits: int, pws: int, max_match: int):
    """(lit_start, lit_len, match_len, offset) tuples of the scheme's plan."""
    buf = np.frombuffer(chunk, np.uint8)
    n = len(buf)
    seqs, anchor = [], 0
    if n >= 4:
        words, cand = _candidates(buf, hash_bits, pws)
        valid = np.zeros(n, bool)
        idx = np.nonzero(cand >= 0)[0]
        valid[idx] = words[idx] == words[cand[idx]]
        valid[max(0, n - MF_LIMIT + 1):] = False
        free = 0
        for ws in range(0, n, pws):
            start = max(ws, free)
            hits = np.nonzero(valid[start: ws + pws])[0]
            if start >= ws + pws or not len(hits):
                continue
            p = start + int(hits[0])
            q = int(cand[p])
            cap = min(n - LAST_LITERALS - p, max_match)
            if cap < MIN_MATCH:
                continue
            a = buf[p + MIN_MATCH: p + cap]
            b = buf[q + MIN_MATCH: q + cap]
            neq = np.nonzero(a != b[: len(a)])[0]
            mlen = MIN_MATCH + (int(neq[0]) if len(neq) else len(a))
            seqs.append((anchor, p - anchor, mlen, p - q))
            anchor = free = p + mlen
    seqs.append((anchor, n - anchor, 0, 0))
    return seqs


def _length_ext(out: bytearray, rem: int) -> None:
    while rem >= 255:
        out.append(255)
        rem -= 255
    out.append(rem)


def encode_paper_block(chunk: bytes, hash_bits: int, pws: int, max_match: int) -> bytes:
    """The LZ4 block bytes of the paper's scheme for one <= 64 KiB chunk."""
    out = bytearray()
    for lit_start, lit, mlen, off in paper_sequences(chunk, hash_bits, pws, max_match):
        ml = mlen - MIN_MATCH if mlen else 0
        out.append((min(lit, 15) << 4) | min(ml, 15))
        if lit >= 15:
            _length_ext(out, lit - 15)
        out += chunk[lit_start: lit_start + lit]
        if mlen:
            out += bytes((off & 0xFF, off >> 8))
            if ml >= 15:
                _length_ext(out, ml - 15)
    return bytes(out)


# -- reader ------------------------------------------------------------------

class FormatError(ValueError):
    pass


def decode_block(payload: bytes, usize: int) -> bytes:
    """Decode one LZ4 block that must produce exactly ``usize`` bytes."""
    out = bytearray()
    i, n = 0, len(payload)
    while True:
        if i >= n:
            raise FormatError("missing token")
        token = payload[i]
        i += 1
        lit = token >> 4
        if lit == 15:
            while True:
                if i >= n:
                    raise FormatError("truncated literal length")
                b = payload[i]
                i += 1
                lit += b
                if b != 255:
                    break
        if i + lit > n:
            raise FormatError("literals run past the block")
        out += payload[i: i + lit]
        i += lit
        if i == n:
            break
        if i + 2 > n:
            raise FormatError("truncated offset")
        off = payload[i] | (payload[i + 1] << 8)
        i += 2
        ml = token & 15
        if ml == 15:
            while True:
                if i >= n:
                    raise FormatError("truncated match length")
                b = payload[i]
                i += 1
                ml += b
                if b != 255:
                    break
        ml += MIN_MATCH
        if off == 0 or off > len(out):
            raise FormatError("offset outside the block")
        for _ in range(ml):
            out.append(out[-off])
        if len(out) > usize:
            raise FormatError("block longer than its table entry")
    if len(out) != usize:
        raise FormatError(f"decoded {len(out)} bytes, table says {usize}")
    return bytes(out)


def parse_frame(frame: bytes) -> dict:
    """Header and block table of a version-3 frame, the version the engine
    writes on one chip.

    Returns ``{"version", "content_size", "blocks"}``; each block is
    ``{"usize", "csize", "raw", "crc", "offset", "payload"}``.
    """
    if len(frame) < 17 or frame[:4] != MAGIC:
        raise FormatError("not a frame header")
    version, count = frame[4], struct.unpack_from("<I", frame, 5)[0]
    if version != 3:
        raise FormatError(f"version {version}")
    (content_size,) = struct.unpack_from("<Q", frame, 9)
    table, pos = 17, 17 + count * 12
    if pos > len(frame):
        raise FormatError("truncated block table")
    blocks = []
    for k in range(count):
        usize, cf, crc = struct.unpack_from("<III", frame, table + k * 12)
        csize = cf & ~RAW_FLAG
        blocks.append({"usize": usize, "csize": csize, "raw": bool(cf & RAW_FLAG),
                       "crc": crc, "offset": pos, "payload": frame[pos: pos + csize]})
        pos += csize
    if pos != len(frame):
        raise FormatError(f"frame is {len(frame)} bytes, table accounts for {pos}")
    if sum(b["usize"] for b in blocks) != content_size:
        raise FormatError("content size differs from the table")
    return {"version": version, "content_size": content_size, "blocks": blocks}


def crc32(data: bytes) -> int:
    return zlib.crc32(data) & 0xFFFFFFFF
