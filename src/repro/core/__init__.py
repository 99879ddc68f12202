"""Core LZ4 compression library — the paper's contribution.

The primary APIs are the two engines (`LZ4Engine` in, `LZ4DecodeEngine`
out); everything else is a building block or a bit-identity oracle for one
of their stages.  docs/architecture.md maps each stage to the paper's
hardware pipeline and to these modules.

Public API:
    LZ4Engine            — batched compression pipeline (frame in/out); with
                           ``device_emit=True`` (default) byte emission stays
                           in the jit graph and only final frame bytes cross
                           the host boundary
    LZ4DecodeEngine      — parallel two-phase (plan/execute) frame decoder;
                           ``executor="device"`` runs plan execution inside
                           the jit graph (fixed-shape DevicePlans, pointer-
                           doubling source resolve) and `decode_to_device`
                           restores straight into device memory
    FrameReader          — seekable random access over a frame's block table
                           (`read_range_device` keeps the bytes on device)
    default_engine       — process-wide shared LZ4Engine
    compress_greedy      — software baseline (GitHub-like, multi-match, unbounded)
    compress_windowed    — the paper's single-match / bounded scheme (golden model)
    encode_block / decode_block — exact LZ4 block format round trip
    plan_block / execute_plan   — two-phase block decode building blocks
    DevicePlan / to_device_plan — fixed-shape (jit-stackable) form of a
                           BlockPlan; `execute_device_plan` is the NumPy
                           twin of the on-device decode algorithm
    emit_block           — host-side vectorized (prefix-sum) block emission:
                           the engine's ``device_emit=False`` path and the
                           oracle for the device emitter
    encode_frame / decode_frame — self-describing multi-block container
                           (byte-level spec: docs/frame-format.md)
    decode_frame_serial  — serial block-walk oracle for the decode engine
"""
from .lz4_types import (  # noqa: F401
    DEFAULT_HASH_BITS,
    DEFAULT_MAX_MATCH,
    DEFAULT_PWS,
    MAX_BLOCK,
    Sequence,
    plan_coverage,
    plan_size,
)
from .reference import compress_greedy, compression_ratio  # noqa: F401
from .schemes import compress_windowed, compress_windowed_multi  # noqa: F401
from .encoder import encode_block  # noqa: F401
from .decoder import decode_block, decode_block_bytewise, LZ4FormatError  # noqa: F401
from .emitter import emit_block, emit_block_from_records  # noqa: F401
from .frame import (  # noqa: F401
    VERSION_V1,
    VERSION_V2,
    VERSION_V3,
    VERSION_V4,
    VERSION_V5,
    VERSION_V6,
    FrameFormatError,
    block_crc,
    check_content_crc,
    decode_frame,
    decode_frame_serial,
    encode_frame,
    frame_info,
    parity_group_blocks,
    scan_frame,
    xor_bytes,
)
from .decode_plan import (  # noqa: F401
    BlockPlan,
    DevicePlan,
    DevicePlanCaps,
    DevicePlanOverflow,
    decode_block_planned,
    execute_device_plan,
    execute_plan,
    plan_block,
    plan_block_fast,
    to_device_plan,
)
from .decode_engine import (  # noqa: F401
    DecodeStats,
    FrameReader,
    LZ4DecodeEngine,
    default_decode_engine,
)
from .engine import EngineStats, LZ4Engine, default_engine  # noqa: F401
from .corpus import corpus_blocks, corpus_files  # noqa: F401
