"""Device-resident decode (PR-4 acceptance surface).

  * `execute_device_plan` (the NumPy twin of the device algorithm —
    per-byte source maps + pointer doubling + one gather) is bit-identical
    to `execute_plan` on compressor output, overlap-heavy chains, and
    adversarial random plans;
  * `kernels.ops.decode_gather` equals both host oracles for every
    `rounds` in {exact, worst-case};
  * `LZ4DecodeEngine(executor="device")` decode is bit-identical to
    `decode_frame_serial` on the frame corpora, with `host_bytes` counting
    exactly the decoded payload;
  * a trimmed byte-flip/truncation sweep over the fuzz corpora: corrupt
    frames must raise through the device executor exactly like the serial
    oracle — never decode silently to different bytes;
  * fixed-shape caps: plans that overflow `DevicePlanCaps` fall back to
    host execution per block (counted, still bit-identical);
  * the accelerator-to-accelerator restore path: `decode_to_device`,
    `FrameReader.read_range_device`, and `OffloadedCacheReader(
    to_device=True)` return device arrays with zero device->host content
    traffic — since PR 5 even with ``verify=True``, whose CRC32 runs
    in-graph (`kernels.ops.crc32_bytes`) and syncs only a 4-byte checksum;
    corrupt content must still be rejected exactly like the serial oracle.
"""
import numpy as np
import pytest

from repro.core import (
    DevicePlanCaps,
    DevicePlanOverflow,
    FrameFormatError,
    LZ4DecodeEngine,
    LZ4Engine,
    Sequence,
    decode_frame_serial,
    encode_block,
    execute_device_plan,
    execute_plan,
    plan_block_fast,
    to_device_plan,
)
from repro.core.decode_plan import MAX_RESOLVE_ROUNDS
from repro.core.lz4_types import MAX_BLOCK


def _rng():
    return np.random.default_rng(20260801)


def _encode_oracle(data: bytes) -> bytes:
    from repro.core import compress_windowed

    res = compress_windowed(data, hash_bits=8, max_match=36)
    return encode_block(data, res.sequences)


def _block_corpus() -> dict[str, bytes]:
    rng = _rng()
    return {
        "text": b"the quick brown fox jumps over the lazy dog. " * 400,
        "zeros": b"\x00" * MAX_BLOCK,        # RLE chain: depth-65535 resolve
        "low_entropy": rng.integers(0, 4, 30000, np.uint8).tobytes(),
        "structured": bytes(rng.integers(0, 16, 64, np.uint8)) * 40,
        "literal_tail": rng.integers(0, 256, 700, np.uint8).tobytes()
                        + b"Q" * 900,
        "one": b"\x51",
    }


def _frame_corpus() -> dict[str, bytes]:
    rng = _rng()
    return {
        "empty": b"",
        "tiny": b"xyz",
        "multi_text": b"spam and eggs and ham, " * 12000,
        "zeros_multi": b"\x00" * (2 * MAX_BLOCK + 17),
        "raw_multi": rng.integers(0, 256, MAX_BLOCK + 5000, np.uint8).tobytes(),
        "mixed": ((b"ab" * MAX_BLOCK)[:MAX_BLOCK - 7]
                  + rng.integers(0, 256, MAX_BLOCK, np.uint8).tobytes()
                  + b"pattern-" * 4000),
    }


@pytest.fixture(scope="module")
def engine():
    return LZ4Engine(micro_batch=4)


@pytest.fixture(scope="module")
def device_engine():
    return LZ4DecodeEngine(executor="device", micro_batch=4)


# ---------------------------------------------------------------------------
# Host oracle of the device algorithm vs execute_plan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", list(_block_corpus().keys()))
def test_device_oracle_equals_execute_plan(name):
    blk = _encode_oracle(_block_corpus()[name])
    plan = plan_block_fast(blk)
    assert execute_device_plan(blk, plan).tobytes() == \
        execute_plan(blk, plan).tobytes()


def test_device_oracle_overlap_chains():
    # Self-overlapping matches (offset < length) and chains of matches
    # reading each other's output: the wave scheduler's hard cases, which
    # pointer doubling must resolve without any fallback.
    for offset, mlen, lead in [(1, 95, b"a"), (2, 40, b"ab"), (3, 100, b"xyz"),
                               (1, 5000, b"z"), (5, 6, b"olapp")]:
        data = lead + (lead * (mlen // len(lead) + 2))[:mlen]
        seq = [Sequence(0, len(lead), mlen, offset), Sequence(len(lead) + mlen, 0)]
        blk = encode_block(data, seq)
        plan = plan_block_fast(blk)
        assert execute_device_plan(blk, plan).tobytes() == data


def test_device_oracle_random_plans():
    rng = _rng()
    for trial in range(25):
        src = bytes(rng.integers(0, 256, 4096, np.uint8))
        data = bytearray()
        seqs = []
        cursor = 0
        for _ in range(int(rng.integers(1, 40))):
            lit = int(rng.integers(0, 30))
            lit_start = len(data)
            data += src[cursor:cursor + lit]
            cursor += lit
            if len(data) == 0:
                continue
            offset = int(rng.integers(1, min(len(data), 65535) + 1))
            mlen = int(rng.integers(4, 60))
            seqs.append(Sequence(lit_start, lit, mlen, offset))
            s = len(data) - offset
            for j in range(mlen):
                data.append(data[s + j])
        seqs.append(Sequence(len(data), 0))
        data = bytes(data)
        blk = encode_block(data, seqs)
        plan = plan_block_fast(blk)
        assert execute_device_plan(blk, plan).tobytes() == data, trial


# ---------------------------------------------------------------------------
# DevicePlan shape/wave semantics
# ---------------------------------------------------------------------------

def test_device_plan_wave_semantics():
    # Pure-literal block: zero resolve rounds.
    plan = plan_block_fast(_encode_oracle(_rng().integers(
        0, 256, 2500, np.uint8).tobytes()))
    dp = to_device_plan(plan)
    if dp.n_match == 0:
        assert dp.n_waves == 0
    # The all-zeros RLE chain needs the full worst-case depth.
    plan_z = plan_block_fast(_encode_oracle(b"\x00" * MAX_BLOCK))
    dp_z = to_device_plan(plan_z)
    assert dp_z.n_waves == MAX_RESOLVE_ROUNDS
    assert dp_z.wave[:dp_z.n_match].max() == MAX_RESOLVE_ROUNDS
    # Padding rows are zeros, wave padding is -1.
    assert (dp_z.wave[dp_z.n_match:] == -1).all()
    assert (dp_z.match_len[dp_z.n_match:] == 0).all()
    # compute_waves=False pins the static worst case.
    dp_s = to_device_plan(plan_z, compute_waves=False)
    assert dp_s.n_waves == MAX_RESOLVE_ROUNDS and (dp_s.wave == -1).all()
    assert dp_z.n_sequences == dp_z.n_lit + dp_z.n_match == plan_z.n_sequences


def test_device_plan_overflow():
    plan = plan_block_fast(_encode_oracle(b"overflow check " * 1000))
    tiny = DevicePlanCaps(max_lit=2, max_match=2)
    with pytest.raises(DevicePlanOverflow):
        to_device_plan(plan, tiny)


def test_device_engine_caps_fallback(engine):
    # An engine with absurdly small caps must still decode bit-exactly —
    # every block through the per-block host fallback, and counted.
    data = b"fallback parity " * 20000
    frame = engine.compress(data)
    de = LZ4DecodeEngine(executor="device",
                         caps=DevicePlanCaps(max_lit=2, max_match=2))
    assert de.decode(frame) == data
    assert de.stats.fallback_blocks == de.stats.blocks
    assert de.stats.device_blocks == 0


def test_specplan_caps_fallback(engine):
    # Same caps-overflow semantics under the SPECULATIVE planner: the
    # in-graph status vector flags the overflow per block, the engine
    # replans that block on host (counted in fallback_blocks), and the
    # output stays bit-exact.  device_blocks counts only blocks that
    # actually finished in-graph: zero here.
    data = b"speculative fallback parity " * 20000
    frame = engine.compress(data)
    de = LZ4DecodeEngine(executor="device", plan_on_device=True,
                         caps=DevicePlanCaps(max_lit=2, max_match=2))
    assert de.decode(frame) == data
    assert de.stats.fallback_blocks == de.stats.blocks
    assert de.stats.device_blocks == 0
    # And with default caps the same engine config takes zero fallbacks.
    ok = LZ4DecodeEngine(executor="device", plan_on_device=True)
    assert ok.decode(frame) == data
    assert ok.stats.fallback_blocks == 0
    assert ok.stats.device_blocks == ok.stats.blocks


# ---------------------------------------------------------------------------
# decode_gather vs the oracles
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["text", "zeros", "low_entropy", "one"])
def test_decode_gather_both_kernels_bit_identical(name):
    import jax.numpy as jnp

    from repro.kernels.ops import decode_gather

    data = _block_corpus()[name]
    blk = _encode_oracle(data)
    plan = plan_block_fast(blk)
    dp = to_device_plan(plan)
    buf = np.zeros(dp.caps.blk_cap, np.uint8)
    buf[: len(blk)] = np.frombuffer(blk, np.uint8)
    args = (jnp.asarray(buf),
            jnp.asarray(dp.lit_src), jnp.asarray(dp.lit_dst),
            jnp.asarray(dp.lit_len), jnp.asarray(dp.match_dst),
            jnp.asarray(dp.match_off), jnp.int32(dp.n_lit),
            jnp.int32(dp.n_match), jnp.int32(dp.out_size))
    for rounds in {dp.n_waves, MAX_RESOLVE_ROUNDS}:
        out = np.asarray(decode_gather(*args, out_cap=dp.caps.out_cap,
                                       rounds=rounds))
        assert out[: dp.out_size].tobytes() == data, (name, rounds)
        assert not out[dp.out_size:].any()


# ---------------------------------------------------------------------------
# Engine bit-identity + transfer accounting
# ---------------------------------------------------------------------------

def test_device_engine_bit_identical(engine, device_engine):
    for name, data in _frame_corpus().items():
        frame = engine.compress(data)
        got = device_engine.decode(frame)
        assert got == data, name
        assert got == decode_frame_serial(frame), name


def test_device_engine_host_bytes_exact(engine, device_engine):
    # The device executor slice-fetches rows to their true usize: fetched
    # bytes == decoded payload of the non-raw blocks, nothing padded.
    data = b"exact transfer accounting " * 11000  # multi-block, compressible
    frame = engine.compress(data)
    assert device_engine.decode(frame) == data
    st = device_engine.stats
    assert st.fallback_blocks == 0 and st.raw_blocks == 0
    assert st.host_bytes == len(data)
    assert st.dispatches == -(-st.blocks // device_engine.micro_batch)


def test_device_engine_adaptive_vs_static_rounds(engine):
    data = b"rounds bucketing " * 15000
    frame = engine.compress(data)
    adaptive = LZ4DecodeEngine(executor="device", adaptive_rounds=True)
    static = LZ4DecodeEngine(executor="device", adaptive_rounds=False)
    assert adaptive.decode(frame) == static.decode(frame) == data


def test_device_decode_blocks_plain(engine, device_engine):
    data = b"plain blocks " * 12000
    payloads = engine.compress_to_blocks(data)
    usizes = [min(MAX_BLOCK, len(data) - i * MAX_BLOCK)
              for i in range(len(payloads))]
    out = device_engine.decode_blocks(payloads, [False] * len(payloads),
                                      usizes=usizes)
    assert b"".join(out) == data
    with pytest.raises(Exception):
        device_engine.decode_blocks([payloads[0]], [False],
                                    usizes=[usizes[0] - 1])


# ---------------------------------------------------------------------------
# Corruption through the device executor (trimmed fuzz sweep)
# ---------------------------------------------------------------------------

def _assert_device_rejects(de, mutant: bytes, where: str,
                           original: bytes | None = None):
    try:
        out = de.decode(mutant)
    except FrameFormatError:
        return
    except Exception as e:
        pytest.fail(f"{where}: raised {type(e).__name__}: {e}")
    if original is None or out != original:
        pytest.fail(f"{where}: decoded corrupt frame silently")


def test_device_corruption_never_silent(engine, device_engine):
    rng = _rng()
    corpora = {
        "text": b"fuzz me gently, " * 900,
        "multi": b"the quick brown fox " * 9000,
        "zeros": b"\x00" * (MAX_BLOCK + 5),
        "raw": rng.integers(0, 256, 3000, np.uint8).tobytes(),
    }
    for name, data in corpora.items():
        frame = engine.compress(data)
        assert device_engine.decode(frame) == data
        n = len(frame)
        # Header/table region densely, payload strided — every mutant must
        # behave identically to the serial oracle: reject, or (rarely)
        # decode to the SAME bytes.
        positions = list(range(min(48, n))) + \
            list(range(48, n, max(1, n // 40))) + [n - 1]
        for pos in positions:
            mutant = bytearray(frame)
            mutant[pos] ^= 0x40
            mutant = bytes(mutant)
            try:
                oracle = decode_frame_serial(mutant)
            except FrameFormatError:
                oracle = None
            _assert_device_rejects(device_engine, mutant,
                                   f"{name}: flip {pos}", original=data)
            if oracle is not None:
                # Oracle accepted (provably-harmless flip): device executor
                # must produce the identical bytes.
                assert device_engine.decode(mutant) == oracle, (name, pos)
        for cut in range(0, n, max(1, n // 15)):
            _assert_device_rejects(device_engine, frame[:cut],
                                   f"{name}: truncate {cut}")


def test_device_crc_detects_parse_valid_corruption(engine, device_engine):
    # Flip deep in a literal run: still a valid token stream, only the
    # content CRC can catch it — including on the device path, where the
    # decoded bytes are fetched back for verification.
    data = b"integrity through the device path " * 6000
    frame = bytearray(engine.compress(data))
    frame[-7] ^= 0x40
    with pytest.raises(FrameFormatError):
        device_engine.decode(bytes(frame))


# ---------------------------------------------------------------------------
# Accelerator-to-accelerator restore
# ---------------------------------------------------------------------------

def test_decode_to_device_matches_and_transfers_nothing(engine, device_engine):
    import jax

    data = _frame_corpus()["mixed"]
    frame = engine.compress(data)
    dev = device_engine.decode_to_device(frame)
    assert isinstance(dev, jax.Array)
    assert np.asarray(dev).tobytes() == data
    # verify=True checks CRCs IN-GRAPH (GF(2) matmuls, ops.crc32_bytes): the
    # decoded content itself never crosses to the host even when verified.
    assert device_engine.stats.host_bytes == 0
    # verify=False: additionally skips the per-block checksum sync.
    dev2 = device_engine.decode_to_device(frame, verify=False)
    assert device_engine.stats.host_bytes == 0
    assert np.asarray(dev2).tobytes() == data
    # Corruption still raises when verification is on — caught by the
    # device-computed checksum, without fetching the content.
    mutant = bytearray(frame)
    mutant[-3] ^= 0x08
    with pytest.raises(FrameFormatError):
        device_engine.decode_to_device(bytes(mutant))


def test_decode_to_device_crc_parity_with_serial_oracle(engine, device_engine):
    # Payload byte flips through the VERIFIED device restore must behave
    # exactly like the serial oracle: reject, or (harmless-flip corner)
    # decode to the identical bytes — all without fetching content.
    data = b"device crc parity " * 7000
    frame = engine.compress(data)
    n = len(frame)
    payload_start = n // 2  # well past the header/table, inside payloads
    for pos in range(payload_start, n, max(1, n // 25)):
        mutant = bytearray(frame)
        mutant[pos] ^= 0x40
        mutant = bytes(mutant)
        try:
            oracle = decode_frame_serial(mutant)
        except FrameFormatError:
            oracle = None
        try:
            got = np.asarray(
                device_engine.decode_to_device(mutant)).tobytes()
        except FrameFormatError:
            assert oracle is None, f"device rejected, oracle accepted @ {pos}"
            continue
        assert oracle is not None, f"device accepted, oracle rejected @ {pos}"
        assert got == oracle, pos
        assert device_engine.stats.host_bytes == 0


def test_decode_to_device_rejects_lying_usize_without_verify(device_engine):
    # A table entry claiming more bytes than the block decodes to must be
    # rejected even with verify=False (the plan knows the exact size before
    # dispatch) — otherwise multi-block device reads would slice at wrong
    # offsets.  The host paths catch this in check_block; parity required.
    from repro.core import block_crc, encode_frame

    data = b"short block " * 50  # 600 bytes
    payload = _encode_oracle(data)
    frame = encode_frame([payload], [len(data) + 20], [False],
                         checksums=[block_crc(data)])
    with pytest.raises(FrameFormatError, match="table says"):
        device_engine.decode_to_device(frame, verify=False)
    with pytest.raises(FrameFormatError):
        device_engine.decode(frame)


def test_read_range_device(engine, device_engine):
    from repro.core import FrameReader

    data = _frame_corpus()["multi_text"]
    frame = engine.compress(data)
    reader = FrameReader(frame, engine=device_engine)
    rng = _rng()
    cases = [(0, 0), (0, 1), (len(data), 0), (len(data) - 1, 1),
             (MAX_BLOCK - 3, 7), (MAX_BLOCK, MAX_BLOCK)]
    cases += [(int(rng.integers(0, len(data))), int(rng.integers(0, 9000)))
              for _ in range(8)]
    for start, length in cases:
        length = min(length, len(data) - start)
        got = np.asarray(reader.read_range_device(start, length)).tobytes()
        assert got == data[start: start + length], (start, length)


def test_offloaded_reader_to_device(engine):
    import jax
    import jax.numpy as jnp

    from repro.serving.engine import OffloadedCacheReader, offload_cache

    rng = _rng()
    cache = {
        # "a_pos" sorts before "k", so the COMPRESSED leaf decodes last —
        # the per-leaf stats assertions below see it, not the tiny raw one.
        "a_pos": jnp.asarray(np.arange(7, dtype=np.int32)),
        "k": jnp.asarray((rng.integers(0, 3, (2, 128, 64)) * 0.5)
                         .astype(np.float32)),
    }
    blob, _ = offload_cache(cache)
    de = LZ4DecodeEngine(executor="device")
    rdr = OffloadedCacheReader(blob, decode_engine=de, to_device=True)
    restored = rdr.restore()
    for key in cache:
        got = restored[key]
        assert isinstance(got, jax.Array)
        assert got.dtype == cache[key].dtype and got.shape == cache[key].shape
        assert (np.asarray(got) == np.asarray(cache[key])).all(), key
    # Partial leaf slice stays on device and matches the host reader.
    host = OffloadedCacheReader(blob)
    k_leaf = 1  # flatten order: a_pos, k
    sl = rdr.read_leaf(k_leaf, start=1000, count=500)
    assert isinstance(sl, jax.Array)
    assert (np.asarray(sl) == host.read_leaf(k_leaf, 1000, 500)).all()
    # verify=False makes the whole restore accelerator-to-accelerator:
    # zero plaintext bytes fetched to host for the compressed leaves.
    de2 = LZ4DecodeEngine(executor="device")
    fast = OffloadedCacheReader(blob, decode_engine=de2, to_device=True,
                                verify=False)
    restored2 = fast.restore()
    assert de2.stats.host_bytes == 0
    for key in cache:
        assert (np.asarray(restored2[key]) == np.asarray(cache[key])).all()


def test_checkpoint_restore_device_executor(tmp_path, engine):
    import jax.numpy as jnp

    from repro.checkpoint import checkpoint as ck

    rng = _rng()
    tree = {"w": jnp.asarray((rng.integers(0, 7, (257, 129)) * 0.125)
                             .astype(np.float32)),
            "b": jnp.asarray(np.arange(17, dtype=np.int32))}
    ck.save(str(tmp_path), 5, tree)
    de = LZ4DecodeEngine(executor="device")
    out, step = ck.restore(str(tmp_path), 5, tree, decode_engine=de)
    assert step == 5
    for key in tree:
        assert (np.asarray(out[key]) == np.asarray(tree[key])).all(), key
