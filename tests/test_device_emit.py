"""Device-side emission: bit-identity against the host emitter oracle.

Covers the PR-3 acceptance surface:
  * `compress_block_bytes` (records -> bytes entirely in-graph) is
    byte-identical to `emit_block` (the host oracle) on random and
    adversarial corpora — incompressible, all-zero, RLE runs that end at
    token-nibble and extension-byte boundaries;
  * the in-graph size equals `BlockRecords.size` and never exceeds OUT_CAP;
  * `emit_bytes` vmapped over blocks, as the write program runs it, on
    hand-built records that take every per-sequence field to its edges;
  * `LZ4Engine(device_emit=True)` frames are bit-identical to
    `device_emit=False` frames, which in turn are guarded against drift
    from the seed construction (emit_block + encode_frame by hand);
  * the device-emit path transfers fewer device->host bytes than the
    records path (EngineStats.host_bytes).
"""
import numpy as np
import pytest

from repro.core import LZ4Engine, decode_block, decode_frame, encode_frame
from repro.core.emitter import emit_block, emit_block_from_records
from repro.core.frame import block_crc
from repro.core.jax_compressor import (
    OUT_CAP,
    compress_block_bytes,
    compress_block_records,
    pad_block,
)
from repro.core.lz4_types import MAX_BLOCK, MF_LIMIT


def _rng():
    return np.random.default_rng(20260730)


def _adversarial_corpus() -> dict[str, bytes]:
    """Random + adversarial blocks aimed at emit-layout edge cases."""
    from tests.test_lz4_jax import _word_lane_data

    rng = _rng()
    return {
        "empty": b"",
        "one_byte": b"\x07",
        "all_zero_block": b"\x00" * MAX_BLOCK,
        "all_zero_short": b"\x00" * 1000,
        "incompressible": rng.integers(0, 256, MAX_BLOCK, np.uint8).tobytes(),
        "incompressible_short": rng.integers(0, 256, 4096, np.uint8).tobytes(),
        # Literal counts straddling the token-nibble (15) and first
        # extension-byte (270) boundaries, then a match so the literals are
        # mid-block rather than the final sequence.
        "lit_nibble_edge": bytes(rng.integers(0, 256, 14, np.uint8)) + b"Z" * 64,
        "lit_nibble_edge2": bytes(rng.integers(0, 256, 15, np.uint8)) + b"Z" * 64,
        "lit_ext_edge": bytes(rng.integers(0, 256, 269, np.uint8)) + b"Z" * 64,
        "lit_ext_edge2": bytes(rng.integers(0, 256, 270, np.uint8)) + b"Z" * 64,
        # RLE run ending exactly at the block boundary (final-literals rule
        # interacts with the run) and just short of it.
        "rle_to_boundary": b"\xaa" * MAX_BLOCK,
        "rle_near_boundary": bytes(rng.integers(0, 256, 100, np.uint8)) + b"\xbb" * (MAX_BLOCK - 100),
        "rle_then_tail": b"\xcc" * (MAX_BLOCK - 7) + b"tail567"[:7],
        "text": b"the quick brown fox jumps over the lazy dog. " * 1000,
        "low_entropy": rng.integers(0, 4, MAX_BLOCK, np.uint8).tobytes(),
        "structured": bytes(rng.integers(0, 16, 64, np.uint8)) * 1024,
        # matches ending on every word lane of the extension and at its cap
        "word_lanes": _word_lane_data(),
    }


def _oracle_and_device(data: bytes):
    import jax.numpy as jnp

    buf, n = pad_block(data)
    rec = compress_block_records(jnp.asarray(buf), jnp.int32(n))
    oracle = emit_block_from_records(data, rec, n)
    out, size = compress_block_bytes(jnp.asarray(buf), jnp.int32(n))
    return rec, oracle, np.asarray(out), int(size)


# ---------------------------------------------------------------------------
# Bit-identity vs the host oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", list(_adversarial_corpus().keys()))
def test_device_emit_bit_identical_to_oracle(name):
    data = _adversarial_corpus()[name]
    rec, oracle, out, size = _oracle_and_device(data)
    assert size == int(rec.size)          # layout total == in-graph plan size
    assert size <= OUT_CAP
    assert out[:size].tobytes() == oracle
    assert np.all(out[size:] == 0)        # padding region is zeroed
    if data:
        assert decode_block(out[:size].tobytes(), max_out=len(data)) == data


def test_device_emit_random_lengths():
    rng = _rng()
    for size in (1, 14, 15, 16, 255, 270, 271, 4096, MAX_BLOCK - 1):
        data = bytes(rng.integers(0, 8, size, np.uint8))
        _, oracle, out, s = _oracle_and_device(data)
        assert out[:s].tobytes() == oracle, size


# ---------------------------------------------------------------------------
# emit_bytes under vmap on hand-built records: every field at its edges
# ---------------------------------------------------------------------------

# A block is a list of pieces, ("lit", count) or ("match", length, offset);
# its n is the bytes they cover.  Each match starts in a window of its own.
_EDGE_CASES = {
    "lit_14": [("lit", 14), ("match", 18, 14), ("lit", 8)],
    "lit_15": [("lit", 15), ("match", 19, 15), ("lit", 8)],
    "lit_270": [("lit", 270), ("match", 36, 100), ("lit", 8)],
    "lit_271": [("lit", 271), ("match", 36, 271), ("lit", 8)],
    "whole_block_literals": [("lit", MAX_BLOCK)],
    "mlen_18_19_36": [("lit", 20), ("match", 18, 20), ("lit", 3),
                      ("match", 19, 5), ("match", 36, 30), ("lit", 10)],
    "offset_1": [("lit", 1), ("match", 36, 1), ("match", 19, 1), ("lit", 7)],
    "offset_65500": [("lit", 65500), ("match", 19, 65500), ("lit", 17)],
    # 0xFFFF fills both offset bytes; no 64 KiB block can reach that far
    # back and still end a match 5 bytes before its end, so this one is
    # compared to the oracle but not decoded.
    "offset_65535": [("lit", 40), ("match", 36, 65535), ("lit", 12)],
    "final_literals_only": [("lit", 100)],
    "n_0": [],
    "n_mf_limit": [("lit", MF_LIMIT)],
}


def _edge_block(pieces, seed):
    """(data, emit, pos, length, offset) for one hand-built block, with the
    write graph's record shape: one record per 8-position window."""
    rng = np.random.default_rng(seed)
    W = MAX_BLOCK // 8
    emit = np.zeros(W, bool)
    pos = np.full(W, -1, np.int32)
    length = np.zeros(W, np.int32)
    offset = np.zeros(W, np.int32)
    out = bytearray()
    for kind, *args in pieces:
        if kind == "lit":
            out += rng.integers(0, 256, args[0], np.uint8).tobytes()
            continue
        mlen, moff = args
        p = len(out)
        w = p // 8
        assert not emit[w:].any()
        emit[w], pos[w], length[w], offset[w] = True, p, mlen, moff
        for i in range(mlen):
            src = p + i - moff
            out.append(out[src] if src >= 0 else int(rng.integers(0, 256)))
    return bytes(out), emit, pos, length, offset


@pytest.mark.parametrize("name", list(_EDGE_CASES))
def test_emit_bytes_vmapped_field_edges(name):
    """`emit_bytes` vmapped over 3 blocks, the write program's form, against
    `emit_block` row by row; the batch is the named case and the two after
    it, so every case also runs beside other blocks."""
    import jax
    import jax.numpy as jnp

    from repro.core.jax_compressor import _PAD
    from repro.kernels import ops

    names = list(_EDGE_CASES)
    i = names.index(name)
    rows = [names[(i + j) % len(names)] for j in range(3)]
    blocks = [_edge_block(_EDGE_CASES[r], 20260731 + names.index(r)) for r in rows]
    bufs = np.zeros((3, MAX_BLOCK + _PAD), np.int32)
    for b, (data, *_) in zip(bufs, blocks):
        b[: len(data)] = np.frombuffer(data, np.uint8)
    recs = [jnp.asarray(np.stack([blk[f] for blk in blocks])) for f in range(1, 5)]
    ns = jnp.asarray([len(blk[0]) for blk in blocks], jnp.int32)
    emit_rows = jax.vmap(lambda *a: ops.emit_bytes(*a, out_cap=OUT_CAP))
    out, size = emit_rows(jnp.asarray(bufs), *recs, ns)
    out, size = np.asarray(out), np.asarray(size)
    for r, (data, emit, pos, length, offset), o, s in zip(rows, blocks, out, size):
        oracle = emit_block(data, emit, pos, length, offset, len(data))
        assert o[:s].tobytes() == oracle, r
        assert np.all(o[s:] == 0), r
        if r != "offset_65535":
            assert decode_block(o[:s].tobytes(), max_out=len(data)) == data, r


# ---------------------------------------------------------------------------
# Engine-level equality and the seed guard
# ---------------------------------------------------------------------------

def _multiblock_corpus() -> bytes:
    rng = _rng()
    return (b"engine level corpus " * 9000                      # compressible
            + rng.integers(0, 256, MAX_BLOCK + 333, np.uint8).tobytes()  # raw
            + b"\x00" * (MAX_BLOCK + 17))                       # RLE


def test_engine_device_emit_frames_bit_identical():
    data = _multiblock_corpus()
    dev = LZ4Engine(micro_batch=2, device_emit=True)
    host = LZ4Engine(micro_batch=2, device_emit=False)
    f_dev, f_host = dev.compress(data), host.compress(data)
    assert f_dev == f_host
    assert decode_frame(f_dev) == data
    # Device emission must fetch fewer bytes per block than the records path.
    assert dev.stats.host_bytes < host.stats.host_bytes
    assert dev.stats.host_bytes > 0


def test_engine_device_emit_blocks_bit_identical():
    data = _multiblock_corpus()
    assert (LZ4Engine(device_emit=True).compress_to_blocks(data)
            == LZ4Engine(device_emit=False).compress_to_blocks(data))


def test_host_path_guard_unchanged_from_seed():
    """device_emit=False must still produce the seed's frame bytes.

    Reconstructs the frame exactly as the seed write path did — per-block
    `emit_block` of the fetched records, raw passthrough when the in-graph
    size does not beat raw, v2 checksums of the uncompressed chunk — and
    asserts byte equality, so the host path can never silently drift while
    the device path evolves.
    """
    import jax.numpy as jnp

    data = _multiblock_corpus()
    payloads, usizes, raws, crcs = [], [], [], []
    for i in range(0, len(data), MAX_BLOCK):
        chunk = data[i: i + MAX_BLOCK]
        buf, n = pad_block(chunk)
        rec = compress_block_records(jnp.asarray(buf), jnp.int32(n))
        if int(rec.size) >= n:
            payloads.append(chunk)
            raws.append(True)
        else:
            payloads.append(emit_block(chunk, np.asarray(rec.emit),
                                       np.asarray(rec.pos), np.asarray(rec.length),
                                       np.asarray(rec.offset), n))
            raws.append(False)
        usizes.append(n)
        crcs.append(block_crc(chunk))
    seed_frame = encode_frame(payloads, usizes, raws, checksums=crcs)
    assert LZ4Engine(device_emit=False).compress(data) == seed_frame
    assert LZ4Engine(device_emit=True).compress(data) == seed_frame


def test_host_path_uses_emit_block(monkeypatch):
    """The switch is real: emit_block runs on host iff device_emit=False."""
    import repro.core.engine as engine_mod

    calls = []
    orig = engine_mod.emit_block
    monkeypatch.setattr(engine_mod, "emit_block",
                        lambda *a, **k: calls.append(1) or orig(*a, **k))
    data = b"switchable emission " * 2000
    LZ4Engine(device_emit=True).compress(data)
    assert calls == []
    LZ4Engine(device_emit=False).compress(data)
    assert len(calls) == 1


def test_engine_raw_passthrough_identical_across_paths():
    # Incompressible input: size >= n, both paths must store raw payloads.
    data = _rng().integers(0, 256, 2 * MAX_BLOCK, np.uint8).tobytes()
    dev, host = LZ4Engine(device_emit=True), LZ4Engine(device_emit=False)
    assert dev.compress(data) == host.compress(data)
    assert dev.stats.raw_blocks == host.stats.raw_blocks == 2
