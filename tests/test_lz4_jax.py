"""JAX engine vs numpy golden model: exact per-window record equality,
sequential vs associative scan equivalence, and end-to-end round trips."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import decode_block, plan_size
from repro.core.jax_compressor import (
    compress_block_records,
    compress_blocks_records,
    compress_bytes,
    pad_block,
    records_to_plan,
)
from repro.core.schemes import compress_windowed


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


def _run_jax(data, hash_bits, max_match, scan_impl="sequential", use_pallas=False):
    buf, n = pad_block(data)
    return compress_block_records(
        jnp.asarray(buf), jnp.int32(n),
        hash_bits=hash_bits, max_match=max_match,
        use_pallas=use_pallas, scan_impl=scan_impl,
    ), n


@pytest.mark.parametrize("name", list(_datasets().keys()))
@pytest.mark.parametrize("hash_bits,max_match", [(8, 36), (12, 36), (6, 12), (10, 68)])
def test_jax_matches_golden(name, hash_bits, max_match):
    data = _datasets()[name]
    golden = compress_windowed(data, hash_bits=hash_bits, max_match=max_match)
    rec, n = _run_jax(data, hash_bits, max_match)
    W = len(golden.emit)
    emit = np.asarray(rec.emit)[:W]
    np.testing.assert_array_equal(emit, golden.emit, err_msg=f"{name} emit")
    np.testing.assert_array_equal(np.asarray(rec.pos)[:W][emit], golden.pos[golden.emit])
    np.testing.assert_array_equal(np.asarray(rec.length)[:W][emit], golden.length[golden.emit])
    np.testing.assert_array_equal(np.asarray(rec.offset)[:W][emit], golden.offset[golden.emit])
    # windows beyond the golden range never emit
    assert not np.asarray(rec.emit)[W:].any()
    # analytic size == exact plan size
    assert int(rec.size) == plan_size(golden.sequences)


@pytest.mark.parametrize("name", list(_datasets().keys()))
def test_associative_equals_sequential(name):
    data = _datasets()[name]
    rec_s, _ = _run_jax(data, 8, 36, scan_impl="sequential")
    rec_a, _ = _run_jax(data, 8, 36, scan_impl="associative")
    np.testing.assert_array_equal(np.asarray(rec_s.emit), np.asarray(rec_a.emit))
    np.testing.assert_array_equal(np.asarray(rec_s.pos), np.asarray(rec_a.pos))
    np.testing.assert_array_equal(np.asarray(rec_s.length), np.asarray(rec_a.length))
    assert int(rec_s.size) == int(rec_a.size)


@pytest.mark.parametrize("scan_impl", ["sequential", "associative"])
def test_pallas_path_equals_ref_path(scan_impl):
    data = _datasets()["low_entropy"]
    rec_r, _ = _run_jax(data, 8, 36, scan_impl=scan_impl, use_pallas=False)
    rec_p, _ = _run_jax(data, 8, 36, scan_impl=scan_impl, use_pallas=True)
    np.testing.assert_array_equal(np.asarray(rec_r.emit), np.asarray(rec_p.emit))
    np.testing.assert_array_equal(np.asarray(rec_r.length), np.asarray(rec_p.length))
    assert int(rec_r.size) == int(rec_p.size)


@pytest.mark.parametrize("name", list(_datasets().keys()))
def test_roundtrip_via_encoder(name):
    data = _datasets()[name]
    rec, n = _run_jax(data, 8, 36)
    from repro.core import encode_block

    plan = records_to_plan(rec, n)
    assert decode_block(encode_block(data, plan)) == data
    assert len(encode_block(data, plan)) == int(rec.size)


def test_compress_bytes_multiblock():
    rng = np.random.default_rng(7)
    data = rng.integers(0, 8, 200_000, dtype=np.uint8).tobytes()
    blocks = compress_bytes(data)
    restored = b"".join(decode_block(b) for b in blocks)
    assert restored == data
    assert sum(len(b) for b in blocks) < len(data)


def test_batched_blocks_vmap():
    rng = np.random.default_rng(9)
    datas = [rng.integers(0, 4, 65536, dtype=np.uint8).tobytes() for _ in range(3)]
    bufs, ns = zip(*(pad_block(d) for d in datas))
    recs = compress_blocks_records(jnp.asarray(np.stack(bufs)), jnp.asarray(ns, jnp.int32))
    for i, d in enumerate(datas):
        single, _ = _run_jax(d, 8, 36)
        assert int(recs.size[i]) == int(single.size)


@pytest.mark.parametrize("name", ["low_entropy", "zeros", "text", "random", "block_64k"])
def test_scatter_candidates_equal_sort(name):
    """Beyond-paper scatter-max candidate resolution is bit-identical."""
    data = _datasets()[name]
    buf, n = pad_block(data)
    a = compress_block_records(jnp.asarray(buf), jnp.int32(n), candidate_impl="sort")
    for impl in ("scatter", "sortkey"):
        b = compress_block_records(jnp.asarray(buf), jnp.int32(n), candidate_impl=impl)
        np.testing.assert_array_equal(np.asarray(a.emit), np.asarray(b.emit))
        np.testing.assert_array_equal(np.asarray(a.pos), np.asarray(b.pos))
        np.testing.assert_array_equal(np.asarray(a.length), np.asarray(b.length))
        np.testing.assert_array_equal(np.asarray(a.offset), np.asarray(b.offset))
        assert int(a.size) == int(b.size)
