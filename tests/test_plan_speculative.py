"""Speculative in-graph decode planning (PR-9 acceptance surface).

The speculative planner (`kernels.ref.plan_fields_ref`, validated and
compacted by `kernels.ops.plan_speculative`) decodes a CANDIDATE sequence
header at every byte offset and chain-selects the one real parse —
replacing the host `plan_block_fast` O(n) walk on the device decode path.
Pinned here:

  * plan bit-identity: the compacted device plan (literal/match columns,
    counts, out_size) equals `to_device_plan(plan_block(...))` — the
    serial parser stays the oracle — on adversarial corpora: 0xFF-run
    extension boundaries, RLE offset-1 chains, literals-only finals,
    hand-built token streams;
  * rejection identity: truncated and lying streams fail with the SAME
    error message the host planner raises, position-priority included;
  * the fused `plan_decode` graph (plan + gather + CRC in one dispatch)
    reproduces payload bytes and `block_crc`;
  * `LZ4DecodeEngine(executor="device", plan_on_device=True)` decodes
    bit-identically to the serial oracle with ZERO host-planner calls and
    `host_bytes == 0` on the to-device paths — planning included;
  * the sharded fabric (`decode_items_sharded` under shard_map) takes the
    same in-graph path on a multi-device mesh (subprocess leg).
"""
import json
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core import (
    DevicePlanCaps,
    FrameFormatError,
    LZ4DecodeEngine,
    LZ4Engine,
    Sequence,
    decode_frame_serial,
    encode_block,
    plan_block,
    plan_block_fast,
    to_device_plan,
)
from repro.core.decode_engine import _spec_err_message
from repro.core.decoder import LZ4FormatError
from repro.core.lz4_types import MAX_BLOCK

_CAPS = DevicePlanCaps()


def _rng():
    return np.random.default_rng(20260808)


def _encode_oracle(data: bytes) -> bytes:
    from repro.core import compress_windowed

    res = compress_windowed(data, hash_bits=8, max_match=36)
    return encode_block(data, res.sequences)


def _adversarial_corpus() -> dict[str, bytes]:
    """Valid blocks hitting every parser edge: returns name -> block."""
    rng = _rng()
    out = {}
    # 0xFF-run boundaries of the LITERAL length extension: 15 needs the
    # first extension byte, 270 the first 0xFF run byte, 525 two runs.
    for ll in (1, 14, 15, 16, 269, 270, 271, 524, 525):
        data = bytes(rng.integers(0, 256, ll, np.uint8))
        out[f"lit_{ll}"] = encode_block(data, [Sequence(0, ll)])
    # Match length extension boundaries (19 = first ext byte, 274 = first
    # 0xFF run) riding an offset-1 RLE chain.
    for ml in (4, 18, 19, 20, 273, 274, 529):
        data = b"z" * (1 + ml)
        seqs = [Sequence(0, 1, ml, 1), Sequence(1 + ml, 0)]
        out[f"rle_{ml}"] = encode_block(data, seqs)
    # Deep RLE chain: the whole block from one seed byte.
    out["zeros"] = _encode_oracle(b"\x00" * MAX_BLOCK)
    # Multi-sequence compressor output (text + structured + random tail).
    out["text"] = _encode_oracle(
        b"the quick brown fox jumps over the lazy dog. " * 400)
    out["structured"] = _encode_oracle(
        bytes(rng.integers(0, 16, 64, np.uint8)) * 40)
    out["lit_tail"] = _encode_oracle(
        bytes(rng.integers(0, 256, 700, np.uint8)) + b"Q" * 900)
    # Final literals-only sequence with a long 0xFF-extended run after
    # matches (the ls_end == n acceptance check, extension on the final).
    data = b"ab" * 40 + bytes(rng.integers(0, 256, 300, np.uint8))
    seqs = [Sequence(0, 2, 78, 2), Sequence(80, 300)]
    out["final_ext"] = encode_block(data, seqs)
    out["one"] = b"\x00"  # empty-literal final token: decodes to b""
    return out


def _lying_corpus() -> dict[str, tuple[bytes, int]]:
    """Malformed streams -> (block, max_out), each targeting one check."""
    fin = b"\x10B"  # final literals-only sequence, 1 byte
    return {
        "zero_offset": (b"\x10A\x00\x00" + fin, MAX_BLOCK),
        "offset_beyond": (b"\x10A\x05\x00" + fin, MAX_BLOCK),
        "missing_final": (b"\x10A\x01\x00", MAX_BLOCK),
        "lit_past_end": (b"\xf0" + b"\xff" * 3, MAX_BLOCK),
        "out_limit_lit": (b"\x40ABCD", 3),
        "out_limit_match": (b"\x1fA\x01\x00\x20" + fin, 10),
        "empty": (b"", MAX_BLOCK),
    }


def _spec_plan(blk: bytes, max_out: int = MAX_BLOCK):
    import jax.numpy as jnp

    from repro.kernels import ops as kops

    buf = np.zeros(_CAPS.blk_cap + kops.SPEC_PAD, np.uint8)
    buf[: len(blk)] = np.frombuffer(blk, np.uint8)
    res = kops.plan_speculative(jnp.asarray(buf), jnp.int32(len(blk)),
                                jnp.int32(max_out),
                                max_lit=_CAPS.max_lit,
                                max_match=_CAPS.max_match,
                                out_cap=_CAPS.out_cap)
    return [np.asarray(a) for a in res]


def _assert_plan_matches_oracle(name, blk):
    from repro.kernels import ops as kops

    *cols, status = _spec_plan(blk)
    lit_src, lit_dst, lit_len, match_dst, match_off, match_len = cols
    assert status[kops.SPEC_ERR] == 0, (name, status)
    assert status[kops.SPEC_OVERFLOW] == 0, name
    dp = to_device_plan(plan_block(bytes(blk)), _CAPS, compute_waves=False)
    assert status[kops.SPEC_N_LIT] == dp.n_lit, name
    assert status[kops.SPEC_N_MATCH] == dp.n_match, name
    assert status[kops.SPEC_OUT_SIZE] == dp.out_size, name
    for got, want, col in (
            (lit_src, dp.lit_src, "lit_src"),
            (lit_dst, dp.lit_dst, "lit_dst"),
            (lit_len, dp.lit_len, "lit_len"),
            (match_dst, dp.match_dst, "match_dst"),
            (match_off, dp.match_off, "match_off"),
            (match_len, dp.match_len, "match_len")):
        assert np.array_equal(got, np.asarray(want, np.int32)), (name, col)


# ---------------------------------------------------------------------------
# Plan bit-identity vs the serial oracle
# ---------------------------------------------------------------------------

def test_speculative_plan_equals_serial_oracle():
    for name, blk in _adversarial_corpus().items():
        _assert_plan_matches_oracle(name, blk)


def test_speculative_rejects_identically():
    from repro.kernels import ops as kops

    for name, (blk, max_out) in _lying_corpus().items():
        with pytest.raises(LZ4FormatError) as ei:
            plan_block_fast(blk, max_out=max_out)
        *_, status = _spec_plan(blk, max_out=max_out)
        err = int(status[kops.SPEC_ERR])
        assert err != 0, name
        assert _spec_err_message(err) == str(ei.value), name


@pytest.mark.parametrize("name", ["text", "rle_274", "lit_270", "final_ext"])
def test_truncation_sweep_rejects_identically(name):
    """Every truncation of a valid stream: accept with the oracle's exact
    plan or reject with the oracle's exact message — never disagree."""
    from repro.kernels import ops as kops

    blk = _adversarial_corpus()[name]
    step = max(1, len(blk) // 60)
    for cut in list(range(0, len(blk), step)) + [len(blk) - 1]:
        t = blk[:cut]
        try:
            plan_block_fast(t)
            oracle_msg = None
        except LZ4FormatError as e:
            oracle_msg = str(e)
        *_, status = _spec_plan(t)
        err = int(status[kops.SPEC_ERR])
        if oracle_msg is None:
            assert err == 0, (name, cut)
            _assert_plan_matches_oracle(f"{name}[: {cut}]", t)
        else:
            assert err != 0, (name, cut, oracle_msg)
            assert _spec_err_message(err) == oracle_msg, (name, cut)


def test_interior_flip_sweep_rejects_identically():
    """Byte rewrites inside the token stream (lying lengths/offsets): the
    speculative parser and the serial parser must agree on accept/reject
    AND on the message; accepted mutants must replan identically."""
    from repro.kernels import ops as kops

    blk = _adversarial_corpus()["text"]
    rng = _rng()
    for _ in range(40):
        m = bytearray(blk)
        pos = int(rng.integers(0, len(blk)))
        m[pos] = int(rng.integers(0, 256))
        m = bytes(m)
        try:
            plan_block_fast(m)
            oracle_msg = None
        except LZ4FormatError as e:
            oracle_msg = str(e)
        *_, status = _spec_plan(m)
        err = int(status[kops.SPEC_ERR])
        if oracle_msg is None:
            if status[kops.SPEC_OVERFLOW]:
                continue  # legal parse that exceeds caps: host fallback
            assert err == 0, pos
            _assert_plan_matches_oracle(f"flip@{pos}", m)
        else:
            assert err != 0 and _spec_err_message(err) == oracle_msg, pos


# ---------------------------------------------------------------------------
# The fused plan_decode graph
# ---------------------------------------------------------------------------

def test_fused_plan_decode_payload_and_crc():
    import jax.numpy as jnp

    from repro.core import block_crc
    from repro.core.decode_plan import MAX_RESOLVE_ROUNDS
    from repro.kernels import ops as kops
    from repro.kernels.ops import plan_decode

    corpus = _adversarial_corpus()
    for name in ("text", "lit_tail", "rle_274", "final_ext"):
        blk = corpus[name]
        data = _decode_oracle(blk)
        buf = np.zeros(_CAPS.blk_cap + kops.SPEC_PAD, np.uint8)
        buf[: len(blk)] = np.frombuffer(blk, np.uint8)
        out, status, crc = plan_decode(
            jnp.asarray(buf), jnp.int32(len(blk)), jnp.int32(MAX_BLOCK),
            out_cap=_CAPS.out_cap, max_lit=_CAPS.max_lit,
            max_match=_CAPS.max_match, rounds=MAX_RESOLVE_ROUNDS)
        status = np.asarray(status)
        assert status[kops.SPEC_ERR] == 0, name
        size = int(status[kops.SPEC_OUT_SIZE])
        got = np.asarray(out)[:size].tobytes()
        assert got == data, name
        assert int(crc) == block_crc(data), name


def _decode_oracle(blk: bytes) -> bytes:
    from repro.core import decode_block_bytewise

    return decode_block_bytewise(blk)


# ---------------------------------------------------------------------------
# Engine path: plan_on_device
# ---------------------------------------------------------------------------

def _frame_corpus() -> dict[str, bytes]:
    rng = _rng()
    return {
        "empty": b"",
        "tiny": b"xyz",
        "multi_text": b"spam and eggs and ham, " * 12000,
        "zeros_multi": b"\x00" * (2 * MAX_BLOCK + 17),
        "raw_multi": rng.integers(0, 256, MAX_BLOCK + 5000,
                                  np.uint8).tobytes(),
        "mixed": ((b"ab" * MAX_BLOCK)[:MAX_BLOCK - 7]
                  + rng.integers(0, 256, MAX_BLOCK, np.uint8).tobytes()
                  + b"pattern-" * 4000),
    }


@pytest.fixture(scope="module")
def engine():
    return LZ4Engine(micro_batch=4)


@pytest.fixture(scope="module")
def spec_engine():
    return LZ4DecodeEngine(executor="device", plan_on_device=True,
                           micro_batch=4)


def test_plan_on_device_requires_device_executor():
    with pytest.raises(ValueError, match="plan_on_device"):
        LZ4DecodeEngine(plan_on_device=True)
    with pytest.raises(ValueError, match="plan_on_device"):
        LZ4DecodeEngine(executor="thread", plan_on_device=True)


def test_specplan_engine_bit_identical(engine, spec_engine):
    for name, data in _frame_corpus().items():
        frame = engine.compress(data)
        got = spec_engine.decode(frame)
        assert got == data, name
        assert got == decode_frame_serial(frame), name


def test_specplan_no_host_planner_calls(engine, monkeypatch):
    """The clean device path must never touch the host parser: planning,
    execution, and CRC verification all live in the jit graph."""
    import repro.core.decode_engine as dem

    data = b"no host planning " * 15000
    frame = engine.compress(data)

    def _boom(*a, **k):
        raise AssertionError("host planner called on the speculative path")

    monkeypatch.setattr(dem, "plan_block_fast", _boom)
    de = LZ4DecodeEngine(executor="device", plan_on_device=True)
    assert de.decode(frame) == data
    assert de.stats.fallback_blocks == 0
    assert de.stats.device_blocks == de.stats.blocks


def test_specplan_to_device_zero_host_bytes(engine, spec_engine):
    import jax

    data = _frame_corpus()["mixed"]
    frame = engine.compress(data)
    dev = spec_engine.decode_to_device(frame)
    assert isinstance(dev, jax.Array)
    assert np.asarray(dev).tobytes() == data
    # host_bytes == 0 now INCLUDES planning: no token stream walk on host.
    assert spec_engine.stats.host_bytes == 0
    dev2 = spec_engine.decode_to_device(frame, verify=False)
    assert spec_engine.stats.host_bytes == 0
    assert np.asarray(dev2).tobytes() == data


def test_specplan_read_range_device_zero_host_bytes(engine, spec_engine):
    from repro.core import FrameReader

    data = _frame_corpus()["multi_text"]
    frame = engine.compress(data)
    reader = FrameReader(frame, engine=spec_engine)
    calls, host = spec_engine.totals.calls, spec_engine.totals.host_bytes
    for start, length in [(0, 1), (MAX_BLOCK - 3, 7), (70000, 9000)]:
        got = np.asarray(reader.read_range_device(start, length)).tobytes()
        assert got == data[start: start + length], (start, length)
    # Each read is a call of its own in `totals`: three, fetching nothing.
    assert spec_engine.totals.calls == calls + 3
    assert spec_engine.totals.host_bytes == host


def test_specplan_offloaded_reader_to_device():
    from repro.serving.engine import OffloadedCacheReader, offload_cache

    import jax.numpy as jnp

    rng = _rng()
    cache = {"k": jnp.asarray((rng.integers(0, 3, (2, 128, 64)) * 0.5)
                              .astype(np.float32))}
    blob, _ = offload_cache(cache)
    de = LZ4DecodeEngine(executor="device", plan_on_device=True)
    rdr = OffloadedCacheReader(blob, decode_engine=de, to_device=True)
    restored = rdr.restore()
    assert (np.asarray(restored["k"]) == np.asarray(cache["k"])).all()
    assert de.stats.host_bytes == 0


def test_specplan_corruption_parity(engine, spec_engine):
    """Flips through the speculative engine behave exactly like the serial
    oracle: reject (any FrameFormatError) or decode the SAME bytes."""
    data = b"the quick brown fox " * 9000
    frame = engine.compress(data)
    n = len(frame)
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
        try:
            got = spec_engine.decode(mutant)
        except FrameFormatError:
            assert oracle is None, f"spec rejected, oracle accepted @ {pos}"
            continue
        assert oracle is not None, f"spec accepted, oracle rejected @ {pos}"
        assert got == oracle, pos


def test_specplan_error_message_parity(engine, spec_engine):
    """A parse-breaking payload flip must surface the oracle's exact
    per-block message (e.g. 'block 0: zero offset') through the engine."""
    from repro.core import block_crc, encode_frame

    blk, _ = _lying_corpus()["zero_offset"]
    frame = encode_frame([blk], [3], [False], checksums=[block_crc(b"AB?")])
    with pytest.raises(FrameFormatError) as serial_err:
        decode_frame_serial(frame)
    with pytest.raises(FrameFormatError) as spec_err:
        spec_engine.decode(frame)
    assert str(spec_err.value) == str(serial_err.value)
    assert "zero offset" in str(spec_err.value)


# ---------------------------------------------------------------------------
# Sharded fabric: the same in-graph path under shard_map (subprocess leg)
# ---------------------------------------------------------------------------

_MESH_SUBPROC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax
    import numpy as np
    from repro.core.engine import LZ4Engine
    from repro.core.decode_engine import LZ4DecodeEngine
    from repro.distributed.sharding import make_mesh

    assert len(jax.devices()) == 8
    rng = np.random.default_rng(7)
    data = (b"sharded speculative planning " * 5000
            + rng.integers(0, 256, 30000, np.uint8).tobytes())
    frame = LZ4Engine(micro_batch=4, shards=3).compress(data)
    mesh = make_mesh((2, 2), ("data", "model"))
    dec = LZ4DecodeEngine(mesh=mesh, executor="device",
                          plan_on_device=True, micro_batch=2)
    assert dec.decode(frame) == data
    st = dec.stats
    assert st.fallback_blocks == 0, st
    assert st.device_blocks == st.blocks - st.raw_blocks, st
    print("RESULT:" + json.dumps({"ok": True, "dispatches": st.dispatches,
                                  "device_blocks": st.device_blocks}))
""")


def test_subprocess_mesh_specplan():
    proc = subprocess.run(
        [sys.executable, "-c", _MESH_SUBPROC],
        capture_output=True, text=True, timeout=500,
        env={"PYTHONPATH": "src:.", "PATH": "/usr/bin:/bin",
             "HOME": "/root", "JAX_PLATFORMS": "cpu"},
        cwd="/root/repo",
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines()
            if l.startswith("RESULT:")][0]
    result = json.loads(line[len("RESULT:"):])
    assert result["ok"]
    assert result["device_blocks"] > 0
