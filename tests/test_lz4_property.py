"""Property-based tests for the system's core invariants.

Two flavours: hypothesis-driven generative tests (skipped individually when
hypothesis is not installed in the image) and seeded differential sweeps
(always run) pinning every datapath variant — shard count x drain mode — to
byte-identical frames.
"""
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - depends on image contents
    HAVE_HYPOTHESIS = False

    class _StrategyStub:
        """Placeholder so module-level strategy expressions still evaluate;
        every @given test is skipped before these stubs are ever drawn."""

        def __getattr__(self, name):
            return lambda *a, **k: self

        def __call__(self, *a, **k):
            return self

    st = _StrategyStub()

    def given(*a, **k):
        return lambda f: pytest.mark.skip(
            reason="hypothesis not installed in this image")(f)

    def settings(*a, **k):
        return lambda f: f

from repro.core import (
    compress_greedy,
    compress_windowed,
    decode_block,
    encode_block,
    plan_coverage,
    plan_size,
)
from repro.core.jax_compressor import compress_block_records, pad_block, records_to_plan

# Byte-stream strategies with different redundancy structure.
_raw = st.binary(min_size=0, max_size=4096)
_structured = st.builds(
    lambda unit, reps, tail: unit * reps + tail,
    st.binary(min_size=1, max_size=64),
    st.integers(min_value=1, max_value=200),
    st.binary(min_size=0, max_size=32),
)
_low_entropy = st.builds(
    lambda seed, n: np.random.default_rng(seed).integers(0, 3, n, dtype=np.uint8).tobytes(),
    st.integers(0, 2**31),
    st.integers(0, 4096),
)
_any_data = st.one_of(_raw, _structured, _low_entropy)


@given(_any_data)
@settings(max_examples=60, deadline=None)
def test_greedy_roundtrip(data):
    plan = compress_greedy(data, hash_bits=10)
    assert plan_coverage(plan) == len(data)
    assert decode_block(encode_block(data, plan)) == data


@given(_any_data, st.sampled_from([6, 8, 12]), st.sampled_from([12, 36, None]))
@settings(max_examples=60, deadline=None)
def test_windowed_roundtrip(data, bits, max_match):
    res = compress_windowed(data, hash_bits=bits, max_match=max_match)
    assert plan_coverage(res.sequences) == len(data)
    assert decode_block(encode_block(data, res.sequences)) == data
    # no match may start in the last 12 bytes or end past len-5
    for s in res.sequences[:-1]:
        start = s.lit_start + s.lit_len
        assert start <= len(data) - 12
        assert start + s.match_len <= len(data) - 5
        assert 1 <= s.offset <= 65535


@given(_any_data)
@settings(max_examples=25, deadline=None)
def test_jax_engine_equals_golden_and_roundtrips(data):
    golden = compress_windowed(data, hash_bits=8, max_match=36)
    buf, n = pad_block(data)
    rec = compress_block_records(jnp.asarray(buf), jnp.int32(n))
    plan = records_to_plan(rec, n)
    assert plan_size(plan) == int(rec.size) == plan_size(golden.sequences)
    assert decode_block(encode_block(data, plan)) == data


@given(_any_data)
@settings(max_examples=30, deadline=None)
def test_scheme_ratio_ordering(data):
    """Restricting the compressor can never shrink the output below the less
    restricted scheme's output: greedy <= single-match <= single+capped."""
    greedy = plan_size(compress_greedy(data, hash_bits=8))
    single = plan_size(compress_windowed(data, hash_bits=8, max_match=None).sequences)
    combined = plan_size(compress_windowed(data, hash_bits=8, max_match=36).sequences)
    assert greedy <= single <= combined
    # worst case bound: one token per 15-ish literals overhead
    assert combined <= len(data) + len(data) // 255 + 16


# ---------------------------------------------------------------------------
# Differential fabric tests: frame bytes must be IDENTICAL across shard
# counts x drain modes (the sharded fabric's merge stage and every datapath
# variant are pinned to one another, not just to "decodes back").  Seeded adversarial corpora, not hypothesis: each engine config
# costs a jit compile, so the sweep is deterministic and shared.
# ---------------------------------------------------------------------------

from repro.core import LZ4Engine  # noqa: E402
from repro.core.frame import decode_frame_serial, frame_info  # noqa: E402
from repro.core.lz4_types import MAX_BLOCK  # noqa: E402

_SHARD_COUNTS = (1, 2, 4)
_DRAINS = ("sliced", "full")


def _adversarial_corpus(seed: int) -> bytes:
    """RLE runs, matches straddling 2048-byte tile boundaries, structured
    text, and an incompressible tail — 3 blocks and change."""
    rng = np.random.default_rng(seed)
    parts = []
    # RLE runs (extension-byte boundaries at lengths near 15/270)
    for n in (14, 15, 19, 270, 271, 5000):
        parts.append(bytes([int(rng.integers(0, 256))]) * n)
    # tile-straddle: an 8-byte unit repeating ACROSS the 2048 boundary
    unit = bytes(rng.integers(0, 256, 8, dtype=np.uint8))
    parts.append(unit * 600)  # 4800 B, crosses two tile boundaries
    # structured text
    parts.append(b"shard fabric differential %d " % seed * 400)
    # incompressible tail
    parts.append(rng.integers(0, 256, 70000, dtype=np.uint8).tobytes())
    data = b"".join(parts)
    # pad to 3 blocks + a partial fourth so shard counts 2 and 4 are uneven
    reps = (3 * MAX_BLOCK + MAX_BLOCK // 2) // len(data) + 1
    return (data * reps)[: 3 * MAX_BLOCK + MAX_BLOCK // 2]


def _payload_bytes(frame: bytes) -> list[bytes]:
    """Per-block payload bytes (shard/version metadata stripped)."""
    return [frame[b["offset"]: b["offset"] + b["csize"]]
            for b in frame_info(frame)["blocks"]]


@pytest.mark.parametrize("seed", [0, 1])
def test_frame_identity_impl_x_shards_x_drain(seed):
    data = _adversarial_corpus(seed)
    reference = {}  # shards -> frame from the first drain mode
    ref_payloads = None
    for shards in _SHARD_COUNTS:
        for drain in _DRAINS:
            frame = LZ4Engine(drain=drain, shards=shards).compress(data)
            # identity across drains (fixed shard count)
            if shards not in reference:
                reference[shards] = frame
                assert decode_frame_serial(frame) == data
            else:
                assert frame == reference[shards], \
                    f"drain={drain} shards={shards}"
        # across shard counts the container header differs (v4 shard
        # column) but every block's payload bytes must be identical
        payloads = _payload_bytes(reference[shards])
        if ref_payloads is None:
            ref_payloads = payloads
        else:
            assert payloads == ref_payloads, f"shards={shards}"


@given(st.integers(0, 2**31), st.sampled_from([1, 2, 4]))
@settings(max_examples=10, deadline=None)
def test_sharded_roundtrip_random(seed, shards):
    """Any byte stream round-trips through the sharded writer and both
    readers (serial oracle and engine)."""
    rng = np.random.default_rng(seed)
    n = int(rng.integers(0, 3 * MAX_BLOCK))
    data = rng.integers(0, 256, n, dtype=np.uint8).tobytes()
    frame = LZ4Engine(shards=shards).compress(data)
    info = frame_info(frame)
    assert info["shard_count"] == shards
    assert decode_frame_serial(frame) == data
