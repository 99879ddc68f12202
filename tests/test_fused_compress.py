"""Fused single-pass compression datapath (PR-5 acceptance surface).

  * `candidate_impl="fused"` (ONE kernel for hash -> LVT candidate -> word
    compare -> bounded extension, kernels/fused_compress.py) produces match
    records bit-identical to the staged `"sort"` oracle on random and
    adversarial corpora — RLE runs, extension-byte boundaries,
    incompressible noise, all-zero blocks, tile-straddling matches — and
    frames byte-identical through the engine;
  * the interpret-mode Pallas kernel equals the jnp twin (`ref.fused_ref`)
    ELEMENTWISE (cand and lengths, not just records), and both equal the
    staged `_candidates` + `match_lengths` oracle chain;
  * the sweep holds across (hash_bits, max_match, pws) corners;
  * a seed-construction guard (like test_device_emit.py): fused/auto
    engine frames must equal the frame built by hand from the sort-path
    records + host emitter + encode_frame;
  * `candidate_impl="auto"` resolves per backend (sortkey on CPU — the
    measured CPU ranking, see BENCH_engine_batched.json; scatter on
    GPU/TPU-without-Pallas, fused on TPU with use_pallas — the expected
    accelerator shapes), rejects unknown names, and the RESOLVED choice
    lands in `EngineStats.candidate_impl`;
  * `kernels.ops.crc32_bytes` (in-graph CRC-32 as GF(2) matmuls, the
    device-side verify) equals `binascii.crc32` across buffer sizes, chunk
    and piece boundaries, under `vmap`, with one compiled graph per size.
"""
import binascii

import numpy as np
import pytest

from repro.core import (
    CANDIDATE_IMPLS,
    LZ4Engine,
    decode_frame,
    encode_frame,
    resolve_candidate_impl,
)
from repro.core.emitter import emit_block
from repro.core.frame import block_crc
from repro.core.jax_compressor import (
    _candidates,
    compress_block_bytes,
    compress_block_records,
    pad_block,
)
from repro.core.lz4_types import MAX_BLOCK, MF_LIMIT, MIN_MATCH
from repro.kernels import ops
from repro.kernels.fused_compress import TILE


def _rng():
    return np.random.default_rng(20260729)


def _adversarial_corpus() -> dict[str, bytes]:
    """Blocks aimed at the fused datapath's edge cases: RLE chains, token
    nibble / extension-byte boundaries, incompressible noise, and matches
    whose candidates live in earlier kernel tiles."""
    rng = _rng()
    seed64 = bytes(rng.integers(0, 16, 64, np.uint8))
    return {
        "empty": b"",
        "one_byte": b"\x07",
        "all_zero_block": b"\x00" * MAX_BLOCK,
        "all_zero_short": b"\x00" * 1000,
        "incompressible": rng.integers(0, 256, MAX_BLOCK, np.uint8).tobytes(),
        "incompressible_short": rng.integers(0, 256, 4096, np.uint8).tobytes(),
        "rle_runs": b"\xaa" * 13 + b"\xbb" * 300 + b"\xaa" * 5000,
        "rle_to_boundary": b"\xcd" * MAX_BLOCK,
        "lit_nibble_edge": bytes(rng.integers(0, 256, 14, np.uint8)) + b"Z" * 64,
        "lit_ext_edge": bytes(rng.integers(0, 256, 269, np.uint8)) + b"Z" * 64,
        "lit_ext_edge2": bytes(rng.integers(0, 256, 270, np.uint8)) + b"Z" * 64,
        "text": b"the quick brown fox jumps over the lazy dog. " * 1000,
        "low_entropy": rng.integers(0, 4, MAX_BLOCK, np.uint8).tobytes(),
        # Candidates always in earlier tiles: the 64-byte seed repeats
        # across all 32 position tiles, so cross-tile LVT reads dominate.
        "structured": seed64 * (MAX_BLOCK // 64),
        # A long match STRADDLING a tile boundary, whose candidate sits
        # right before the previous boundary: exercises both the in-tile
        # exclusive cummax and the persistent-table handoff at TILE.
        "tile_straddle": (bytes(rng.integers(0, 256, TILE - 30, np.uint8))
                          + seed64 + bytes(rng.integers(0, 256, TILE - 80,
                                                        np.uint8)) + seed64),
    }


def _records(data: bytes, impl: str, use_pallas: bool = False, **kw):
    import jax.numpy as jnp

    buf, n = pad_block(data)
    return compress_block_records(jnp.asarray(buf), jnp.int32(n),
                                  candidate_impl=impl,
                                  use_pallas=use_pallas, **kw)


def _assert_records_equal(a, b, msg=""):
    np.testing.assert_array_equal(np.asarray(a.emit), np.asarray(b.emit), msg)
    np.testing.assert_array_equal(np.asarray(a.pos), np.asarray(b.pos), msg)
    np.testing.assert_array_equal(np.asarray(a.length), np.asarray(b.length), msg)
    np.testing.assert_array_equal(np.asarray(a.offset), np.asarray(b.offset), msg)
    assert int(a.size) == int(b.size), msg


# ---------------------------------------------------------------------------
# Record-level bit-identity vs the sort oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", list(_adversarial_corpus().keys()))
def test_fused_records_equal_sort_oracle(name):
    data = _adversarial_corpus()[name]
    _assert_records_equal(_records(data, "sort"), _records(data, "fused"), name)


@pytest.mark.parametrize("hash_bits,max_match,pws",
                         [(6, 12, 8), (10, 68, 4), (8, 36, 16), (12, 36, 8)])
def test_fused_param_sweep(hash_bits, max_match, pws):
    for name in ("text", "low_entropy", "all_zero_short", "tile_straddle"):
        data = _adversarial_corpus()[name]
        kw = dict(hash_bits=hash_bits, max_match=max_match, pws=pws)
        _assert_records_equal(_records(data, "sort", **kw),
                              _records(data, "fused", **kw),
                              (name, hash_bits, max_match, pws))


# ---------------------------------------------------------------------------
# Kernel == jnp twin == staged oracle chain, ELEMENTWISE
# ---------------------------------------------------------------------------

def _staged_oracle(blk, n, hash_bits=8, pws=8, max_match=36):
    """The pre-fusion pipeline, stage by stage: the bit-identity reference
    for the fused kernel's (cand, lengths) outputs."""
    import jax.numpy as jnp

    words, hashes = ops.hash_positions(blk[: MAX_BLOCK + 3], hash_bits)
    cand = _candidates(hashes, n, hash_bits, pws)
    p = jnp.arange(MAX_BLOCK, dtype=jnp.int32)
    wc = jnp.take(words, jnp.clip(cand, 0, MAX_BLOCK - 1))
    valid4 = (cand >= 0) & (wc == words) & (p <= n - MF_LIMIT)
    lengths = ops.match_lengths(blk, cand, valid4, n, max_match=max_match)
    return lengths


@pytest.mark.parametrize("name", ["text", "all_zero_block", "structured",
                                  "tile_straddle", "incompressible_short",
                                  "rle_runs", "empty"])
def test_fused_pallas_equals_twin_elementwise(name):
    import jax.numpy as jnp

    data = _adversarial_corpus()[name]
    buf, n = pad_block(data)
    blk = jnp.where(jnp.arange(buf.shape[0]) < n,
                    jnp.asarray(buf, jnp.int32), 0)
    c_ref, l_ref = ops.fused_match_candidates(blk, jnp.int32(n),
                                              positions=MAX_BLOCK)
    c_pl, l_pl = ops.fused_match_candidates(blk, jnp.int32(n),
                                            positions=MAX_BLOCK,
                                            use_pallas=True)
    np.testing.assert_array_equal(np.asarray(c_ref), np.asarray(c_pl), name)
    np.testing.assert_array_equal(np.asarray(l_ref), np.asarray(l_pl), name)
    # Lengths must equal the staged sort-oracle chain exactly (0 where no
    # valid match, including every masked invalid-position corner).
    np.testing.assert_array_equal(
        np.asarray(l_ref), np.asarray(_staged_oracle(blk, jnp.int32(n))), name)
    lengths = np.asarray(l_ref)
    assert ((lengths == 0) | (lengths >= MIN_MATCH)).all()
    # Every reported candidate really is an earlier-window position.
    cand = np.asarray(c_ref)
    live = lengths > 0
    assert (cand[live] >= 0).all()
    assert (cand[live] // 8 < np.nonzero(live)[0] // 8).all()


# ---------------------------------------------------------------------------
# Bytes path + engine frames + the seed guard
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("use_pallas", [False, True])
def test_fused_bytes_path_roundtrip(use_pallas):
    import jax.numpy as jnp

    for name in ("text", "rle_runs", "all_zero_short"):
        data = _adversarial_corpus()[name]
        buf, n = pad_block(data)
        out_s, sz_s = compress_block_bytes(jnp.asarray(buf), jnp.int32(n),
                                           candidate_impl="sort")
        out_f, sz_f = compress_block_bytes(jnp.asarray(buf), jnp.int32(n),
                                           candidate_impl="fused",
                                           use_pallas=use_pallas)
        assert int(sz_f) == int(sz_s), name
        assert np.asarray(out_f).tobytes() == np.asarray(out_s).tobytes(), name


def _multiblock_corpus() -> bytes:
    rng = _rng()
    return (b"fused datapath corpus " * 9000
            + rng.integers(0, 256, MAX_BLOCK + 333, np.uint8).tobytes()
            + b"\x00" * (MAX_BLOCK + 17))


def test_engine_fused_frames_bit_identical():
    data = _multiblock_corpus()
    frames = {}
    for impl in ("sort", "scatter", "fused"):
        eng = LZ4Engine(micro_batch=2, candidate_impl=impl)
        frames[impl] = eng.compress(data)
        assert eng.stats.candidate_impl == impl
    assert frames["sort"] == frames["scatter"] == frames["fused"]
    assert decode_frame(frames["fused"]) == data
    # The Pallas kernel through the vmapped engine path, too.
    pl = LZ4Engine(micro_batch=2, candidate_impl="fused", use_pallas=True)
    assert pl.compress(data) == frames["sort"]
    # Composes with the records path and both device-emit drains.
    assert LZ4Engine(micro_batch=2, candidate_impl="fused",
                     device_emit=False).compress(data) == frames["sort"]
    assert LZ4Engine(micro_batch=2, candidate_impl="fused",
                     drain="full").compress(data) == frames["sort"]


def test_fused_guard_unchanged_from_seed():
    """Fused/auto engine frames must equal the seed-constructed frame.

    Reconstructs the frame exactly as the seed write path did — per-block
    `emit_block` of records fetched from the SORT path, raw passthrough
    when the in-graph size does not beat raw, checksums of the original
    chunk — so the new candidate impls can never silently drift the frame
    bytes while the datapath evolves.
    """
    import jax.numpy as jnp

    data = _multiblock_corpus()
    payloads, usizes, raws, crcs = [], [], [], []
    for i in range(0, len(data), MAX_BLOCK):
        chunk = data[i: i + MAX_BLOCK]
        buf, n = pad_block(chunk)
        rec = compress_block_records(jnp.asarray(buf), jnp.int32(n),
                                     candidate_impl="sort")
        if int(rec.size) >= n:
            payloads.append(chunk)
            raws.append(True)
        else:
            payloads.append(emit_block(chunk, np.asarray(rec.emit),
                                       np.asarray(rec.pos),
                                       np.asarray(rec.length),
                                       np.asarray(rec.offset), n))
            raws.append(False)
        usizes.append(n)
        crcs.append(block_crc(chunk))
    seed_frame = encode_frame(payloads, usizes, raws, checksums=crcs)
    assert LZ4Engine(candidate_impl="fused").compress(data) == seed_frame
    assert LZ4Engine(candidate_impl="auto").compress(data) == seed_frame
    assert LZ4Engine().compress(data) == seed_frame


# ---------------------------------------------------------------------------
# "auto" resolution
# ---------------------------------------------------------------------------

def test_resolve_candidate_impl():
    import jax

    assert resolve_candidate_impl("auto", backend="cpu") == "sortkey"
    assert resolve_candidate_impl("auto", backend="gpu") == "scatter"
    # "fused" is only auto-picked where the Pallas kernel actually runs:
    # TPU with use_pallas; without it the jnp twin would just be a slower
    # scatter, so auto falls back to scatter.
    assert resolve_candidate_impl("auto", backend="tpu",
                                  use_pallas=True) == "fused"
    assert resolve_candidate_impl("auto", backend="tpu") == "scatter"
    for impl in CANDIDATE_IMPLS:
        assert resolve_candidate_impl(impl, backend="cpu") == impl
        assert resolve_candidate_impl(impl, backend="tpu",
                                      use_pallas=True) == impl
    with pytest.raises(ValueError):
        resolve_candidate_impl("bogus")
    with pytest.raises(ValueError):
        LZ4Engine(candidate_impl="bogus")
    # The engine resolves ONCE at construction and records what ran.
    eng = LZ4Engine(micro_batch=1)
    assert eng.candidate_impl == resolve_candidate_impl(
        "auto", backend=jax.default_backend())
    eng.compress(b"auto resolution " * 500)
    assert eng.stats.candidate_impl == eng.candidate_impl
    assert eng.stats.candidate_impl != "auto"
    # Default records ("auto") match the explicit resolved impl's records.
    data = _adversarial_corpus()["text"]
    _assert_records_equal(_records(data, "auto"),
                          _records(data, eng.candidate_impl))


# ---------------------------------------------------------------------------
# In-graph CRC-32 (the device-verify satellite)
# ---------------------------------------------------------------------------

def _crc(buf, n):
    import jax.numpy as jnp

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
    import jax
    import jax.numpy as jnp

    rng = _rng()
    rows = rng.integers(0, 256, (6, K), np.uint8)
    ns = np.array([0, 1, 1023, 1025, K - 1, K], np.int32)
    got = jax.jit(jax.vmap(ops.crc32_bytes))(jnp.asarray(rows),
                                             jnp.asarray(ns))
    want = [binascii.crc32(r[:n].tobytes()) for r, n in zip(rows, ns)]
    assert [int(g) for g in got] == want
