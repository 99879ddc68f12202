"""The device stages' jnp paths vs independent oracles: shape/dtype sweeps,
exact equality.  The hash against a NumPy hash, the match extension against
a byte-at-a-time loop, the in-graph CRC-32 against `binascii.crc32`."""
import binascii
import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.jax_compressor import _PAD
from repro.core.lz4_types import HASH_PRIME, LAST_LITERALS, MAX_BLOCK, MF_LIMIT, MIN_MATCH
from repro.kernels import ops


def _np_hash(words: np.ndarray, bits: int) -> np.ndarray:
    return (((words.astype(np.uint64) * HASH_PRIME) & 0xFFFFFFFF) >> (32 - bits)).astype(np.int64)


@pytest.mark.parametrize("n", [2048, 4096, 65536, 3000, 5555])
@pytest.mark.parametrize("bits", [6, 8, 12, 13])
def test_fibhash_pallas_vs_ref(n, bits):
    """`ops.hash_positions` equals a NumPy word build + Fibonacci hash."""
    rng = np.random.default_rng(n * 31 + bits)
    block = rng.integers(0, 256, n + 3, dtype=np.int32)
    w, h = ops.hash_positions(jnp.asarray(block), hash_bits=bits)
    d = block.astype(np.uint64)
    words = (d[:n] | (d[1 : n + 1] << 8) | (d[2 : n + 2] << 16) | (d[3 : n + 3] << 24)) & 0xFFFFFFFF
    np.testing.assert_array_equal(np.asarray(w).astype(np.uint32), words.astype(np.uint32))
    np.testing.assert_array_equal(np.asarray(h), _np_hash(words, bits))


@pytest.mark.parametrize("n", [1024, 2048, 65536, 2500])
@pytest.mark.parametrize("max_match", [12, 20, 36, 68])
def test_match_extend_pallas_vs_ref(n, max_match):
    """`ops.match_lengths` on random candidates equals the byte loop."""
    rng = np.random.default_rng(n * 7 + max_match)
    # low-entropy data so real matches occur
    block = rng.integers(0, 4, n + max_match, dtype=np.int32)
    cand = rng.integers(0, np.maximum(1, n - 64), n, dtype=np.int32)
    valid = rng.random(n) < 0.5
    args = (jnp.asarray(block), jnp.asarray(cand), jnp.asarray(valid), n)
    out = np.asarray(ops.match_lengths(*args, max_match=max_match))
    want = np.asarray(_match_extend_bytes(*args, max_match=max_match))
    np.testing.assert_array_equal(out, want)
    assert out[valid].min() >= 4
    assert out.max() <= max_match
    assert (out[~valid] == 0).all()


def test_match_extend_against_python_oracle():
    """Check the bounded prefix semantics against a dead-simple python loop."""
    rng = np.random.default_rng(0)
    n = 2048
    max_match = 36
    block = rng.integers(0, 3, n + max_match, dtype=np.int32)
    cand = rng.integers(0, n - 64, n, dtype=np.int32)
    valid = np.ones(n, dtype=bool)
    out = np.asarray(
        ops.match_lengths(
            jnp.asarray(block), jnp.asarray(cand), jnp.asarray(valid), n,
            max_match=max_match,
        )
    )
    for p in rng.integers(0, n, 200):
        q = cand[p]
        cap = min(max_match - 4, n - 5 - (p + 4))
        cap = max(cap, 0)
        l = 0
        while l < cap and block[p + 4 + l] == block[q + 4 + l]:
            l += 1
        assert out[p] == 4 + l, (p, q, out[p], 4 + l)


def test_match_extend_end_of_block_cap():
    """Match end must respect the last-5-literals rule."""
    n = 2048
    block = np.zeros(n + 36, dtype=np.int32)  # all zeros -> max-length matches
    cand = np.zeros(n, dtype=np.int32)
    valid = np.ones(n, dtype=bool)
    out = np.asarray(
        ops.match_lengths(
            jnp.asarray(block), jnp.asarray(cand), jnp.asarray(valid), n,
            max_match=36,
        )
    )
    p = np.arange(n)
    expected = 4 + np.clip(n - 5 - (p + 4), 0, 32)
    np.testing.assert_array_equal(out, expected)


# --- word-wide extension vs the byte-at-a-time definition --------------------

@functools.partial(jax.jit, static_argnames="max_match")
def _match_extend_bytes(block, cand, valid, n, max_match):
    """The bounded extension one byte per step: the definition the word-wide
    `ops.match_lengths` and `ops.confirmed_match_lengths` must equal."""
    P = cand.shape[0]
    p = jnp.arange(P, dtype=jnp.int32)
    max_extra = jnp.clip(n - LAST_LITERALS - (p + MIN_MATCH), 0, max_match - MIN_MATCH)
    prefix = jnp.ones(P, dtype=bool)
    length = jnp.zeros(P, dtype=jnp.int32)
    for j in range(max_match - MIN_MATCH):
        cur = block[jnp.clip(p + MIN_MATCH + j, 0, block.shape[0] - 1)]
        cnd = block[jnp.clip(cand + MIN_MATCH + j, 0, block.shape[0] - 1)]
        prefix = prefix & (cur == cnd) & (j < max_extra)
        length = length + prefix.astype(jnp.int32)
    return jnp.where(valid, MIN_MATCH + length, 0)


def _extension_case(kind: str, max_match: int):
    """(block, cand, valid, n): one block, or a stack of 32 for "vmapped"."""
    rng = np.random.default_rng(max_match * 100 + len(kind))
    if kind == "lanes":
        # One (candidate, position) pair per byte offset 4 .. max_match + 1
        # from the match start, each with its first mismatch there: every
        # byte lane of every compared word, the cap, and one byte past it.
        n = 256 * (max_match - 1)
        block = rng.integers(0, 256, n + max_match, dtype=np.int32)
        cand = rng.integers(0, n, n, dtype=np.int32)
        valid = rng.random(n) < 0.5
        for i, d in enumerate(range(MIN_MATCH, max_match + 2)):
            q, p = 256 * i, 256 * i + 128
            block[p: p + max_match + 8] = block[q: q + max_match + 8]
            block[p + d] ^= 0x5A
            cand[p], valid[p] = q, True
        return block, cand, valid, n
    if kind == "end_of_block":
        # n of 12-16 and MAX_BLOCK, all zeros: the end-of-block rule caps
        # every length (stacked below as one case per n).
        out = []
        for n in (12, 13, 14, 15, 16, MAX_BLOCK):
            P = n
            block = np.zeros(n + max_match, np.int32)
            p = np.arange(P, dtype=np.int32)
            out.append((block, np.maximum(p - 1 - (p % 3), 0).astype(np.int32),
                        np.ones(P, bool), n))
        return out
    if kind == "rle":
        # cand = p - 1: the candidate overlaps the current bytes (runs).
        n = 8192
        runs = np.repeat(rng.integers(0, 4, 256), rng.integers(1, 80, 256))
        block = np.zeros(n + max_match, np.int32)
        block[:n] = np.resize(runs, n)
        p = np.arange(n, dtype=np.int32)
        return block, p - 1, p >= 1, n
    if kind == "no_cand":
        n = 2048
        block = rng.integers(0, 2, n + max_match, dtype=np.int32)
        return block, np.full(n, -1, np.int32), np.zeros(n, bool), n
    assert kind == "vmapped"
    # The write graph's shape: 32 padded blocks, zeroed past n, nearby
    # candidates (-1 where none).
    ns = rng.integers(MAX_BLOCK - 4096, MAX_BLOCK + 1, 32).astype(np.int32)
    ns[:3] = (MAX_BLOCK, 13, 0)
    block = rng.integers(0, 4, (32, MAX_BLOCK + _PAD), dtype=np.int32)
    block[np.arange(MAX_BLOCK + _PAD)[None, :] >= ns[:, None]] = 0
    p = np.arange(MAX_BLOCK, dtype=np.int32)
    cand = p[None, :] - 1 - rng.integers(0, 2048, (32, MAX_BLOCK))
    cand = np.where(cand < 0, -1, cand).astype(np.int32)
    return block, cand, cand >= 0, ns


def _confirmed(block, cand, n):
    """Where a match starts: a candidate whose first 4 bytes equal p's."""
    d = block.astype(np.int64)
    word = lambda q: d[q] | d[q + 1] << 8 | d[q + 2] << 16 | d[q + 3] << 24  # noqa: E731
    p = np.arange(cand.shape[0])
    c = np.clip(cand, 0, len(p) - 1)
    return (cand >= 0) & (word(c) == word(p)) & (p <= n - MF_LIMIT)


@pytest.mark.parametrize("max_match", [12, 20, 36, 68])
@pytest.mark.parametrize("kind", ["lanes", "end_of_block", "rle", "no_cand", "vmapped"])
def test_match_lengths_words_equal_byte_loop(kind, max_match):
    """The word-wide extension (`ops.match_lengths`, jnp path) equals the
    byte-at-a-time definition, and the write graph's confirm + extension
    (`ops.confirmed_match_lengths`) equals it at the confirmed positions."""
    case = _extension_case(kind, max_match)
    if kind == "vmapped":
        block, cand, valid, n = (jnp.asarray(a) for a in case)
        words = jax.vmap(functools.partial(ops.match_lengths, max_match=max_match))
        bytes_ = jax.vmap(functools.partial(_match_extend_bytes, max_match=max_match))
        got, want = words(block, cand, valid, n), bytes_(block, cand, valid, n)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        start = np.stack([_confirmed(*a) for a in zip(case[0], case[1], case[3])])
        conf = jax.vmap(functools.partial(ops.confirmed_match_lengths, max_match=max_match))
        np.testing.assert_array_equal(np.asarray(conf(block, cand, n)),
                                      np.asarray(bytes_(block, cand, jnp.asarray(start), n)))
        return
    for block, cand, valid, n in (case if kind == "end_of_block" else [case]):
        args = (jnp.asarray(block), jnp.asarray(cand))
        got = ops.match_lengths(*args, jnp.asarray(valid), n, max_match=max_match)
        want = _match_extend_bytes(*args, jnp.asarray(valid), n, max_match=max_match)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want), err_msg=f"n={n}")
        start = jnp.asarray(_confirmed(block, cand, n))
        np.testing.assert_array_equal(
            np.asarray(ops.confirmed_match_lengths(*args, n, max_match=max_match)),
            np.asarray(_match_extend_bytes(*args, start, n, max_match=max_match)),
            err_msg=f"n={n}")
    if kind == "lanes":
        # the planted pairs: the first mismatch at offset d gives length d,
        # up to the cap
        for i, d in enumerate(range(MIN_MATCH, max_match + 2)):
            assert int(got[256 * i + 128]) == min(d, max_match), d


# --- in-graph CRC-32 (the device-side verify) --------------------------------

def _rng():
    return np.random.default_rng(20260729)


def _crc(buf, n):
    return int(ops.crc32_bytes(jnp.asarray(buf), jnp.int32(n)))


def _crc_lengths(K, rng):
    """n at 0, 1, the old corner list, every chunk and piece boundary +-1
    (above 64 KiB: the first and last four chunk boundaries, those around
    each piece boundary and 16 drawn at random), K - 1 and K."""
    chunk, piece = ops._CRC_CHUNK, ops._CRC_PIECE
    edges = list(range(chunk, K + 1, chunk))
    if len(edges) > 64:
        drawn = rng.choice(edges, 16, replace=False).tolist()
        around = [p + d for p in range(piece, K + 1, piece)
                  for d in (-chunk, 0, chunk)]
        edges = edges[:4] + edges[-4:] + drawn + around
    ns = {0, 1, 3, 7, 8, 9, 15, 16, 70, 255, 256, 257, 1000, K - 1, K}
    ns |= {e + d for e in edges for d in (-1, 0, 1)}
    return sorted(n for n in ns if 0 <= n <= K)


@pytest.mark.parametrize("K", [1, 7, 8, 9, 1000, 4096, MAX_BLOCK, 1 << 21])
def test_crc32_bytes_matches_binascii(K):
    rng = _rng()
    buf = rng.integers(0, 256, K, np.uint8)
    lengths = _crc_lengths(K, rng)
    for n in lengths:
        assert _crc(buf, n) == binascii.crc32(buf[:n].tobytes()), n
    # All-0x00 (e.g. 70 zero bytes of a 64 KB row) and all-0xFF data.
    for fill in (0x00, 0xFF):
        const = np.full(K, fill, np.uint8)
        for n in lengths:
            assert _crc(const, n) == binascii.crc32(const[:n].tobytes()), \
                (fill, n)
    # Content past n must not leak into the checksum.
    cut = min(100, K // 2)
    buf2 = buf.copy()
    buf2[cut:] ^= 0xFF
    assert _crc(buf2, cut) == _crc(buf, cut)


def test_crc32_bytes_compiles_once_per_length():
    """`n` is traced: many lengths share one compiled graph per size."""
    rng = _rng()
    for K in (3000, MAX_BLOCK):
        buf = rng.integers(0, 256, K, np.uint8)
        _crc(buf, K)
        graphs = ops.crc32_bytes._cache_size()
        for n in range(0, K + 1, max(1, K // 17)):
            assert _crc(buf, n) == binascii.crc32(buf[:n].tobytes()), (K, n)
        assert ops.crc32_bytes._cache_size() == graphs, K


@pytest.mark.parametrize("K", [4096, MAX_BLOCK])
def test_crc32_bytes_vmapped_rows(K):
    """The `plan_decode` use: one CRC per row of a vmapped micro-batch."""
    rng = _rng()
    rows = rng.integers(0, 256, (6, K), np.uint8)
    ns = np.array([0, 1, 1023, 1025, K - 1, K], np.int32)
    got = jax.jit(jax.vmap(ops.crc32_bytes))(jnp.asarray(rows),
                                             jnp.asarray(ns))
    want = [binascii.crc32(r[:n].tobytes()) for r, n in zip(rows, ns)]
    assert [int(g) for g in got] == want
