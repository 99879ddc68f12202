"""JAX engine vs numpy golden model: exact per-window record equality,
sequential vs associative scan equivalence, and end-to-end round trips."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import LZ4Engine, decode_block, encode_frame, plan_size
from repro.core.emitter import emit_block
from repro.core.frame import block_crc
from repro.core.jax_compressor import (
    compress_block_records,
    compress_blocks_records,
    pad_block,
    records_to_plan,
)
from repro.core.lz4_types import MAX_BLOCK
from repro.core.schemes import compress_windowed, window_candidates
from repro.core.reference import fib_hash, le32_words
from repro.kernels.ref import scatter_candidates_ref


def _word_lane_data() -> bytes:
    """Copies of one random string cut one byte longer each time (3 to 41
    bytes, then a flipped byte), so matches end on every byte lane of every
    compared word, at the 36-byte cap and past it; RLE runs between them."""
    rng = np.random.default_rng(18)
    base = rng.integers(0, 256, 48, np.uint8).tobytes()
    out = [base]
    for L in range(3, 42):
        out.append(base[:L] + bytes([base[L] ^ 0xFF])
                   + rng.integers(0, 256, 9, np.uint8).tobytes()
                   + bytes([L]) * (L + 2))
    return b"".join(out)


def _datasets():
    rng = np.random.default_rng(42)
    out = {
        "zeros": b"\x00" * 5000,
        "repeat8": b"abcdefgh" * 700,
        "text": (b"the quick brown fox jumps over the lazy dog. " * 250),
        "low_entropy": rng.integers(0, 4, 20000, dtype=np.uint8).tobytes(),
        "med_entropy": rng.integers(0, 64, 30000, dtype=np.uint8).tobytes(),
        "random": rng.integers(0, 256, 8192, dtype=np.uint8).tobytes(),
        "tiny": b"hello",
        "empty": b"",
        "block_64k": rng.integers(0, 16, 65536, dtype=np.uint8).tobytes(),
        "self_overlap": b"a" * 3000 + b"xyz" + b"a" * 3000,
        "word_lanes": _word_lane_data(),
    }
    return out


def _adversarial_corpus() -> dict[str, bytes]:
    """Blocks aimed at the datapath's edge cases: RLE chains, token nibble /
    extension-byte boundaries, incompressible noise, and candidates far
    back in the block (all names prefixed ``adv_``)."""
    rng = np.random.default_rng(20260729)
    seed64 = bytes(rng.integers(0, 16, 64, np.uint8))
    tile = 2048
    out = {
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
        # The 64-byte seed repeats over the whole block: every candidate
        # is one seed length back, in every 2048-byte span.
        "structured": seed64 * (MAX_BLOCK // 64),
        # A long match straddling a 2048-byte boundary whose candidate
        # sits just before the previous one.
        "tile_straddle": (bytes(rng.integers(0, 256, tile - 30, np.uint8))
                          + seed64 + bytes(rng.integers(0, 256, tile - 80,
                                                        np.uint8)) + seed64),
    }
    return {f"adv_{k}": v for k, v in out.items()}


def _all_inputs() -> dict[str, bytes]:
    return {**_datasets(), **_adversarial_corpus()}


def _run_jax(data, hash_bits, max_match, scan_impl="sequential", pws=8):
    buf, n = pad_block(data)
    return compress_block_records(
        jnp.asarray(buf), jnp.int32(n),
        hash_bits=hash_bits, max_match=max_match, pws=pws, scan_impl=scan_impl,
    ), n


def _assert_matches_golden(data, hash_bits, max_match, pws=8):
    golden = compress_windowed(data, hash_bits=hash_bits, max_match=max_match,
                               pws=pws)
    rec, n = _run_jax(data, hash_bits, max_match, pws=pws)
    W = len(golden.emit)
    emit = np.asarray(rec.emit)[:W]
    np.testing.assert_array_equal(emit, golden.emit, err_msg="emit")
    np.testing.assert_array_equal(np.asarray(rec.pos)[:W][emit], golden.pos[golden.emit])
    np.testing.assert_array_equal(np.asarray(rec.length)[:W][emit], golden.length[golden.emit])
    np.testing.assert_array_equal(np.asarray(rec.offset)[:W][emit], golden.offset[golden.emit])
    # windows beyond the golden range never emit
    assert not np.asarray(rec.emit)[W:].any()
    # analytic size == exact plan size
    assert int(rec.size) == plan_size(golden.sequences)


@pytest.mark.parametrize("name", list(_all_inputs().keys()))
@pytest.mark.parametrize("hash_bits,max_match", [(8, 36), (12, 36), (6, 12), (10, 68)])
def test_jax_matches_golden(name, hash_bits, max_match):
    _assert_matches_golden(_all_inputs()[name], hash_bits, max_match)


@pytest.mark.parametrize("hash_bits,max_match,pws",
                         [(6, 12, 8), (10, 68, 4), (8, 36, 16), (12, 36, 8)])
def test_fused_param_sweep(hash_bits, max_match, pws):
    """The write graph equals the golden model at (hash_bits, max_match,
    pws) corners, window width included."""
    corpus = _adversarial_corpus()
    for name in ("adv_text", "adv_low_entropy", "adv_all_zero_short",
                 "adv_tile_straddle"):
        _assert_matches_golden(corpus[name], hash_bits, max_match, pws)


@pytest.mark.parametrize("name", list(_datasets().keys()))
def test_associative_equals_sequential(name):
    data = _datasets()[name]
    rec_s, _ = _run_jax(data, 8, 36, scan_impl="sequential")
    rec_a, _ = _run_jax(data, 8, 36, scan_impl="associative")
    np.testing.assert_array_equal(np.asarray(rec_s.emit), np.asarray(rec_a.emit))
    np.testing.assert_array_equal(np.asarray(rec_s.pos), np.asarray(rec_a.pos))
    np.testing.assert_array_equal(np.asarray(rec_s.length), np.asarray(rec_a.length))
    assert int(rec_s.size) == int(rec_a.size)


@pytest.mark.parametrize("name", list(_all_inputs().keys()))
def test_roundtrip_via_encoder(name):
    data = _all_inputs()[name]
    rec, n = _run_jax(data, 8, 36)
    from repro.core import encode_block

    plan = records_to_plan(rec, n)
    assert decode_block(encode_block(data, plan)) == data
    assert len(encode_block(data, plan)) == int(rec.size)


def test_batched_blocks_vmap():
    rng = np.random.default_rng(9)
    datas = [rng.integers(0, 4, 65536, dtype=np.uint8).tobytes() for _ in range(3)]
    bufs, ns = zip(*(pad_block(d) for d in datas))
    recs = compress_blocks_records(jnp.asarray(np.stack(bufs)), jnp.asarray(ns, jnp.int32))
    for i, d in enumerate(datas):
        single, _ = _run_jax(d, 8, 36)
        assert int(recs.size[i]) == int(single.size)


_scatter_candidates = jax.jit(scatter_candidates_ref, static_argnums=(2, 3))


@pytest.mark.parametrize("name", ["low_entropy", "zeros", "text", "random", "block_64k"])
def test_scatter_candidates_equal_sort(name):
    """The write graph's scatter-max candidate stage equals the golden
    model's sort-based `window_candidates`, on the golden model's hashes."""
    data = _datasets()[name]
    n = len(data)
    words = le32_words(np.frombuffer(data, np.uint8))
    for hash_bits, pws in ((8, 8), (6, 4)):
        hashes = fib_hash(words, hash_bits)
        padded = np.zeros(MAX_BLOCK, np.int32)
        padded[: len(hashes)] = hashes
        got = np.asarray(_scatter_candidates(jnp.asarray(padded), jnp.int32(n),
                                             hash_bits, pws))
        np.testing.assert_array_equal(got[: len(hashes)],
                                      window_candidates(hashes, pws))
        assert (got[len(hashes):] == -1).all()


def _multiblock_corpus() -> bytes:
    rng = np.random.default_rng(20260729)
    return (b"fused datapath corpus " * 9000
            + rng.integers(0, 256, MAX_BLOCK + 333, np.uint8).tobytes()
            + b"\x00" * (MAX_BLOCK + 17))


def test_fused_guard_unchanged_from_seed():
    """Default engine frames must equal the seed-constructed frame.

    Reconstructs the frame exactly as the seed write path did — per-block
    `emit_block` of the graph's records, raw passthrough when the in-graph
    size does not beat raw, checksums of the original chunk — so the
    device-emit engine can never silently drift the frame bytes while the
    datapath evolves.
    """
    data = _multiblock_corpus()
    payloads, usizes, raws, crcs = [], [], [], []
    for i in range(0, len(data), MAX_BLOCK):
        chunk = data[i: i + MAX_BLOCK]
        rec, n = _run_jax(chunk, 8, 36)
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
    assert LZ4Engine().compress(data) == seed_frame
    assert LZ4Engine(micro_batch=2).compress(data) == seed_frame
