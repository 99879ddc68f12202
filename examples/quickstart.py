"""Quickstart: the LZ4-HT engine in five minutes.

  PYTHONPATH=src python examples/quickstart.py

Covers: the batched `LZ4Engine` pipeline (one device dispatch per
micro-batch, device-resident byte emission, self-describing frame output),
the `device_emit` switch and what it saves in host transfer, the frame
round trip through `decode_frame`, the parallel decompression subsystem
(`LZ4DecodeEngine` + seekable `FrameReader` random access), comparing
schemes (the paper's Tables I-III in miniature), and the hardware cycle
model (Table IV).

Deeper dives: docs/architecture.md (pipeline map), docs/frame-format.md
(container spec), docs/tuning.md (parameter trade-offs).
"""
import numpy as np

from repro.core import (
    FrameReader,
    LZ4DecodeEngine,
    LZ4Engine,
    compress_greedy,
    compress_windowed,
    decode_block,
    decode_frame,
    encode_block,
    frame_info,
    plan_size,
)
from repro import compile_cache
from repro.core.cycle_model import ours_throughput

compile_cache.enable()

# --- some compressible data -------------------------------------------------
rng = np.random.default_rng(0)
data = (b"the quick brown fox jumps over the lazy dog. " * 800)[:32768]

# --- 1. the batched engine: frame in/out, one dispatch per micro-batch ------
engine = LZ4Engine()                     # paper's combined scheme (S1+S2)
frame = engine.compress(data)            # self-describing frame bytes
assert decode_frame(frame) == data       # no out-of-band lengths needed
info = frame_info(frame)
ratio = len(data) / len(frame)
print(f"LZ4Engine: ratio {ratio:.3f}, {info['block_count']} block(s), "
      f"{engine.stats.dispatches} dispatch(es), frame round-trip OK")

# --- 1b. device-side emission: only final bytes cross the host boundary ------
# By default (device_emit=True) the byte emission — prefix-sum offsets and
# the literal/token scatter — runs inside the jit graph, so the host fetches
# one padded byte buffer + size per block.  device_emit=False fetches the
# per-window match records instead and emits on host (the oracle path); the
# frames are bit-identical either way.  stats.host_bytes shows the saving.
host_engine = LZ4Engine(device_emit=False)
assert host_engine.compress(data) == frame
print(f"device_emit: host transfer {engine.stats.host_bytes} B "
      f"vs {host_engine.stats.host_bytes} B for the records path "
      f"({host_engine.stats.host_bytes / engine.stats.host_bytes:.2f}x), "
      f"frames bit-identical")

# --- 2. decompression: parallel decode + random access -----------------------
# decode_frame delegates to the LZ4DecodeEngine (two-phase plan/execute
# decode; blocks are independent, so an executor="process" engine fans them
# across cores).  The frame's block table doubles as a seek index:
# FrameReader.read_range decodes ONLY the 64 KB blocks covering a byte
# range — no full-frame decompress for partial reads.
big = (b"the quick brown fox jumps over the lazy dog. " * 8000)  # ~360 KB, 6 blocks
big_frame = LZ4Engine().compress(big)
reader = FrameReader(big_frame)
start, length = 200_000, 1_000
assert reader.read_range(start, length) == big[start:start + length]
assert reader.read_block(2) == big[reader.block_range(2)[0]:reader.block_range(2)[1]]
par = LZ4DecodeEngine(workers=2)           # executor="process" for multi-core
assert par.decode(big_frame) == big
blocks_touched = len(reader.blocks_for_range(start, length))
print(f"random access: read_range({start}, {length}) decoded "
      f"{blocks_touched}/{reader.block_count} blocks; parallel decode OK")
par.close()

# The device executor runs plan execution INSIDE jit (pointer-doubling
# source resolve, one vmapped dispatch per micro-batch); decode_to_device
# returns the restored bytes as a device array that never touched the host.
dev = LZ4DecodeEngine(executor="device")
assert dev.decode(big_frame) == big
arr = dev.decode_to_device(big_frame, verify=False)
assert bytes(memoryview(np.asarray(arr))) == big and dev.stats.host_bytes == 0
print(f"device decode: {dev.stats.device_blocks} blocks in "
      f"{dev.stats.dispatches} jit dispatches; device-resident restore "
      f"fetched {dev.stats.host_bytes} plaintext bytes to host")

# --- 3. scheme comparison (paper Tables I-III in miniature) ------------------
greedy = plan_size(compress_greedy(data, hash_bits=8))
single = plan_size(compress_windowed(data, hash_bits=8, max_match=None).sequences)
combined = plan_size(compress_windowed(data, hash_bits=8, max_match=36).sequences)
print(f"software LZ4 (multi-match) : {len(data)/greedy:.3f}")
print(f"single-match/window (S1)   : {len(data)/single:.3f}")
print(f"combined (S1+S2, cap 36)   : {len(data)/combined:.3f}")

# --- 4. why: deterministic hardware throughput (Table IV) --------------------
t = ours_throughput(len(data))
print(f"hardware model: {t.bytes_per_cycle:.3f} B/cycle -> "
      f"{list(t.gbps_at.values())[0]:.2f} Gb/s @ 251.57 MHz (paper: 16.10)")

# --- 5. golden-model equivalence ---------------------------------------------
res = compress_windowed(data, hash_bits=8, max_match=36)
blk = encode_block(data[:65536], res.sequences)
assert decode_block(blk) == data[:65536]
print("golden numpy model == exact LZ4 block format, decoder verified")
