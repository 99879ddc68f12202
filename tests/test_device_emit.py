"""Device-side emission: bit-identity against the host emitter oracle.

Covers the PR-3 acceptance surface:
  * `compress_block_bytes` (records -> bytes entirely in-graph) is
    byte-identical to `emit_block` (the host oracle) on random and
    adversarial corpora — incompressible, all-zero, RLE runs that end at
    token-nibble and extension-byte boundaries;
  * the in-graph size equals `BlockRecords.size` and never exceeds OUT_CAP;
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
from repro.core.lz4_types import MAX_BLOCK


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
