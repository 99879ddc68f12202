"""Batched, device-resident compression pipeline: the `LZ4Engine`.

The engine is the write-path API.  It keeps the paper's feedback-free
token pipeline batch-parallel end to end:

  * arbitrary-length input is split into a ``(B, MAX_BLOCK + _PAD)`` uint8
    stack and compressed with ONE vmapped+jitted dispatch per micro-batch
    (configurable ``micro_batch``, donated input buffers);
  * dispatch is double-buffered: while the device crunches micro-batch i,
    the host pads and dispatches micro-batch i+1, so padding/transfer —
    and, with ``device_emit``, the host-side frame assembly of the previous
    micro-batch — overlaps device compute;
  * byte emission is device-resident by default (``device_emit=True``): the
    jit graph computes token byte-lengths, exclusive prefix-sum offsets,
    and the byte scatter (`jax_compressor.compress_block_bytes` ->
    `kernels.ops.emit_bytes`), so only final frame bytes cross the host
    boundary, once per micro-batch.  ``device_emit=False`` fetches the
    per-window match records instead and emits on host with the vectorized
    prefix-sum emitter (emitter.py) — the bit-identity oracle path;
  * output is a self-describing frame (frame.py, spec in
    docs/frame-format.md) with per-block sizes, CRC32s, and a
    raw-passthrough flag for uncompressible blocks, decodable by
    `decode_frame` with no out-of-band metadata.

`EngineStats.host_bytes` counts every byte fetched from the device, so the
host-transfer saving of ``device_emit`` is directly observable
(benchmarks/engine_batched.py records it; trade-offs in docs/tuning.md).

Partial trailing micro-batches are padded up to the next power of two (capped
at ``micro_batch``) so the number of compiled shapes is bounded by
log2(micro_batch) + 1 rather than one per input length.

See docs/architecture.md for the stage-by-stage map of the write path onto
the paper's hardware pipeline.
"""
from __future__ import annotations

import dataclasses
import functools
import threading

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs

from .emitter import emit_block
from .frame import block_crc, decode_frame, encode_frame
from .jax_compressor import (
    _PAD,
    compress_block_bytes,
    compress_block_records,
)
from .lz4_types import (
    DEFAULT_HASH_BITS,
    DEFAULT_MAX_MATCH,
    DEFAULT_PWS,
    MAX_BLOCK,
    pad_pow2_count,
)

__all__ = ["LZ4Engine", "EngineStats", "default_engine"]


@functools.lru_cache(maxsize=1)
def default_engine() -> "LZ4Engine":
    """Process-wide default engine (shared by serving offload, checkpointing)."""
    return LZ4Engine()


@functools.lru_cache(maxsize=None)
def _batched_compiled(hash_bits, max_match, pws, scan_impl, donate,
                      device_emit):
    """Jitted vmap of the single-block graph, cached per static config.

    Module-level cache so every LZ4Engine instance shares compilations;
    jit's own cache then keys on batch shape.
    ``device_emit`` selects the fused compress+emit graph (bytes out) over
    the records-only graph (match records out, emitted on host).
    """
    base = compress_block_bytes if device_emit else compress_block_records
    fn = functools.partial(
        base,
        hash_bits=hash_bits, max_match=max_match, pws=pws, scan_impl=scan_impl,
    )
    kw = {"donate_argnums": (0,)} if donate else {}
    return jax.jit(jax.vmap(fn), **kw)


@dataclasses.dataclass
class EngineStats:
    """Per-call counters (PLUS a lifetime accumulator on the engine).

    ``engine.stats`` is replaced at the start of every `compress` /
    `compress_to_blocks` call — it describes the MOST RECENT call only.
    ``engine.totals`` is the cumulative sum over the engine's lifetime
    (merged in as each call finishes, even on error); use it — or the
    ``engine.*`` counters in `repro.obs.registry()` when telemetry is on —
    for anything that must survive across calls.
    """

    blocks: int = 0
    dispatches: int = 0
    raw_blocks: int = 0
    bytes_in: int = 0
    bytes_out: int = 0
    host_bytes: int = 0  # bytes fetched device -> host (records or emit buffers)
    shards: int = 0  # sharded-fabric calls: shard count of the v4 container
    calls: int = 0  # 1 per finished call (so totals.calls counts calls)

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)

    def accumulate(self, other: "EngineStats") -> None:
        """Fold ``other`` (one finished call) into this accumulator.

        NOT thread-safe by itself — the engine serializes its `totals`
        accumulation behind a lock (`_finish_call`); external accumulators
        shared across threads need their own.
        """
        for f in ("blocks", "dispatches", "raw_blocks", "bytes_in",
                  "bytes_out", "host_bytes"):
            setattr(self, f, getattr(self, f) + getattr(other, f))
        self.calls += max(other.calls, 1)
        self.shards = max(self.shards, other.shards)


def _slice_payload(out: np.ndarray, j: int, size: int) -> bytes:
    """Row j's first `size` bytes of a drained (M, out_cap) emit buffer."""
    return out[j, :size].tobytes()


# The sliced drain fetches each row's payload through one jitted slice whose
# length is `size` rounded up to this quantum.  An eager ``out[j, :size]``
# compiles a program per distinct (row, size), which costs a fraction of a
# second each on a TPU and dominated the write path there; rounding bounds
# the programs to ~17 lengths per micro-batch shape.
FETCH_QUANTUM = 4096


@functools.partial(jax.jit, static_argnums=2)
def _row_prefix(out, row, length: int):
    return jax.lax.dynamic_slice_in_dim(out, row, 1)[0, :length]


def fetch_row_prefix(out_dev, row: int, size: int) -> tuple[bytes, int]:
    """Row ``row``'s first ``size`` bytes of a device (M, out_cap) emit
    buffer, and the number of bytes moved to the host to get them."""
    length = min(-(-size // FETCH_QUANTUM) * FETCH_QUANTUM, out_dev.shape[1])
    buf = np.asarray(_row_prefix(out_dev, row, length))
    return buf[:size].tobytes(), length


class LZ4Engine:
    """Batched LZ4 compression engine (the paper's combined scheme, S1+S2).

    >>> eng = LZ4Engine()
    >>> frame = eng.compress(data)          # one dispatch per micro-batch
    >>> assert eng.decompress(frame) == data
    """

    def __init__(self, hash_bits: int = DEFAULT_HASH_BITS,
                 max_match: int = DEFAULT_MAX_MATCH,
                 pws: int = DEFAULT_PWS,
                 micro_batch: int = 32,
                 scan_impl: str = "sequential",
                 donate: bool | None = None,
                 device_emit: bool = True,
                 drain: str = "sliced",
                 content_crc: bool = False,
                 parity_group: int | None = None,
                 telemetry: bool | None = None,
                 mesh=None,
                 shard_axes: tuple[str, ...] | None = None,
                 shards: int | None = None):
        if micro_batch < 1:
            raise ValueError("micro_batch must be >= 1")
        if drain not in ("sliced", "full"):
            raise ValueError('drain must be "sliced" or "full"')
        # Sharded-fabric configuration (docs/architecture.md §Sharded
        # compression fabric).  ``mesh`` routes `compress` through
        # shard_map over ``shard_axes`` (default: every mesh axis) and the
        # output becomes a frame-v4 container; ``shards`` without a mesh
        # selects the host-partition path (the per-shard oracle, and the
        # only option on a single-device container) writing the same v4
        # shape.  ``shards=None`` with no mesh keeps the classic v3 writer.
        if mesh is not None:
            axes = tuple(shard_axes) if shard_axes is not None \
                else tuple(mesh.axis_names)
            for a in axes:
                if a not in mesh.axis_names:
                    raise ValueError(f"shard axis {a!r} not in mesh "
                                     f"{tuple(mesh.axis_names)}")
            from repro.distributed.fabric import mesh_shard_count

            n = mesh_shard_count(mesh, axes)
            if shards is not None and shards != n:
                raise ValueError(f"shards={shards} != mesh shard count {n}")
            if not device_emit and n > 1:
                raise ValueError(
                    "the mesh fabric path requires device_emit=True "
                    "(host emission cannot run under shard_map)")
            self.mesh, self.shard_axes, self.shards = mesh, axes, n
        else:
            if shard_axes is not None:
                raise ValueError("shard_axes requires mesh")
            if shards is not None and shards < 1:
                raise ValueError("shards must be >= 1")
            self.mesh, self.shard_axes, self.shards = None, (), shards
        self.hash_bits = hash_bits
        self.max_match = max_match
        self.pws = pws
        self.micro_batch = micro_batch
        self.scan_impl = scan_impl
        # Donation only pays (and only avoids a warning) off-CPU.
        self.donate = (jax.default_backend() != "cpu") if donate is None else donate
        # device_emit=True: byte emission stays in the jit graph; only the
        # final bytes cross the host boundary.  False: fetch match records
        # and emit on host via emit_block (the bit-identity oracle path).
        self.device_emit = device_emit
        # drain="sliced" (device_emit only): two-step fetch — size scalars
        # first, then `size` bytes per block rounded up to FETCH_QUANTUM,
        # and NOTHING for blocks bound for raw passthrough — so host_bytes
        # is the compressed payload plus under 4 KB per block.  "full"
        # fetches the whole padded (M, out_cap) buffer per micro-batch in
        # one transfer (fewer, larger copies; the pre-two-step behaviour,
        # kept measurable in benchmarks).
        self.drain = drain
        # content_crc=True: stamp a whole-object CRC32 trailer on every
        # frame (version 5) on top of the per-block checksums — full-frame
        # decoders verify the JOINED output too (frame.py docstring has the
        # failure modes per-block checks cannot see).  Default off: the v3
        # (or v4, sharded) writer stays byte-identical.
        self.content_crc = content_crc
        # parity_group=N: append one XOR parity block per N data blocks so
        # salvage (repro.resilience) can reconstruct any SINGLE damaged
        # block per group byte-identically — the frame becomes version 6,
        # which always carries the whole-content trailer too (the v6 writer
        # implies content_crc).  Default off: frame bytes are untouched.
        if parity_group is not None and parity_group < 1:
            raise ValueError("parity_group must be >= 1")
        self.parity_group = parity_group
        # Telemetry: None follows the global `repro.obs` gate (REPRO_OBS /
        # obs.configure) at CALL time; True/False pins this instance.  The
        # resolved flag never changes frame bytes — it only decides whether
        # spans/metrics are recorded (tested byte-identical either way).
        self.telemetry = telemetry
        self.stats = EngineStats()      # most recent call (see EngineStats)
        self.totals = EngineStats()     # lifetime accumulator
        # `totals` is shared mutable state: concurrent calls (serving
        # offload threads all using default_engine()) each fold their own
        # per-call stats object in under this lock, so lifetime counters
        # never lose updates.  `stats` stays a last-call-wins pointer.
        self._totals_lock = threading.Lock()
        self._sp = obs.span_factory(False)  # refreshed per call
        self._worker: "LZ4Engine | None" = None  # fabric host-path clone

    def _obs_on(self) -> bool:
        return obs.enabled_for(self.telemetry)

    def _shard_worker(self) -> "LZ4Engine":
        """Single-device clone for the fabric's host-partition path (same
        datapath config, no mesh — the per-shard oracle)."""
        if self._worker is None:
            self._worker = LZ4Engine(
                hash_bits=self.hash_bits, max_match=self.max_match,
                pws=self.pws, micro_batch=self.micro_batch,
                scan_impl=self.scan_impl, donate=self.donate,
                device_emit=self.device_emit, drain=self.drain,
                telemetry=self.telemetry,
            )
        return self._worker

    def _finish_call(self, st: EngineStats) -> None:
        """Fold the finished call's stats into `totals` + the obs registry."""
        s = st
        s.calls = 1
        with self._totals_lock:
            self.totals.accumulate(s)
        if self._obs_on():
            r = obs.registry()
            r.counter("engine.calls", "compress calls").inc()
            r.counter("engine.blocks", "64 KB blocks compressed").inc(s.blocks)
            r.counter("engine.raw_blocks",
                      "blocks stored as raw passthrough").inc(s.raw_blocks)
            r.counter("engine.dispatches", "jit dispatches").inc(s.dispatches)
            r.counter("engine.bytes_in", "input bytes").inc(s.bytes_in)
            r.counter("engine.bytes_out", "frame bytes out").inc(s.bytes_out)
            r.counter("engine.host_bytes",
                      "bytes fetched device -> host").inc(s.host_bytes)

    # -- dispatch -----------------------------------------------------------

    def _dispatch(self, stack: np.ndarray, ns: np.ndarray, st: EngineStats):
        """ONE device dispatch for a (M, MAX_BLOCK+_PAD) micro-batch."""
        fn = _batched_compiled(
            self.hash_bits, self.max_match, self.pws, self.scan_impl,
            self.donate, self.device_emit,
        )
        st.dispatches += 1
        with self._sp("compress.dispatch", rows=len(ns)):
            return fn(jnp.asarray(stack), jnp.asarray(ns))

    def _pad_batch(self, chunks: list[bytes]) -> tuple[np.ndarray, np.ndarray]:
        """Stack chunks into a fixed-shape micro-batch (padded rows get n=0)."""
        with self._sp("compress.pad", blocks=len(chunks)):
            m = pad_pow2_count(len(chunks), self.micro_batch)
            stack = np.zeros((m, MAX_BLOCK + _PAD), np.uint8)
            ns = np.zeros((m,), np.int32)
            for j, c in enumerate(chunks):
                stack[j, : len(c)] = np.frombuffer(c, np.uint8)
                ns[j] = len(c)
            return stack, ns

    def _payload_iter(self, data: bytes, st: EngineStats):
        """Yield (chunk, n, size, payload_fn) per block, counting into `st`.

        `payload_fn()` materializes the compressed block bytes: a buffer
        slice on the device-emit path, a host `emit_block` call otherwise.
        Double-buffered: micro-batch i+1 is padded and dispatched before the
        host blocks on micro-batch i's results, so host-side padding (and
        frame assembly) overlaps device compute (jax dispatch is
        asynchronous).  ``st`` is the CALL-LOCAL stats object (incremented,
        never replaced) — concurrent calls each carry their own, which is
        what keeps `totals` exact under threaded use.
        """
        chunks = [data[i: i + MAX_BLOCK] for i in range(0, len(data), MAX_BLOCK)]
        st.blocks += len(chunks)
        st.bytes_in += len(data)
        ob = self._obs_on()
        self._sp = obs.span_factory(ob)
        occupancy = obs.registry().gauge(
            "engine.inflight_batches",
            "micro-batches dispatched but not yet drained (double buffer)",
        ) if ob else obs.NOOP_METRIC
        inflight = None
        for start in range(0, len(chunks), self.micro_batch):
            batch = chunks[start: start + self.micro_batch]
            stack, ns = self._pad_batch(batch)
            res = self._dispatch(stack, ns, st)
            occupancy.inc()
            if inflight is not None:
                # Double-buffer overlap: batch i drains while i+1 computes.
                if ob:
                    obs.registry().counter(
                        "engine.overlapped_dispatches",
                        "dispatches issued while the previous batch was "
                        "still in flight").inc()
                yield from self._drain(*inflight, st)
                occupancy.dec()
            inflight = (batch, res)
        if inflight is not None:
            yield from self._drain(*inflight, st)
            occupancy.dec()

    def _fetch_sliced(self, out_dev, j: int, size: int, st: EngineStats) -> bytes:
        """Slice-fetch row j's `size` compressed bytes (the device slice
        executes on-device; only the payload, rounded up to
        `FETCH_QUANTUM`, crosses to host)."""
        with self._sp("compress.drain", bytes=size):
            data, moved = fetch_row_prefix(out_dev, j, size)
        st.host_bytes += moved
        return data

    def _drain(self, batch: list[bytes], res, st: EngineStats):
        if self.device_emit:
            if self.drain == "sliced":
                # Two-step drain: sync on the tiny size vector, then fetch
                # exactly size[j] bytes per block — lazily, so blocks the
                # caller stores as raw passthrough (size >= n) never fetch
                # their emit buffer at all.
                out_dev, size_dev = res
                # The device_get is the sync point: its span measures how
                # long the host WAITS on device compute (the rest of the
                # drain is host-side transfer/assembly).
                with self._sp("compress.wait", rows=len(batch)):
                    size = jax.device_get(size_dev)
                st.host_bytes += size.nbytes
                for j, chunk in enumerate(batch):
                    s = int(size[j])
                    yield chunk, len(chunk), s, functools.partial(
                        self._fetch_sliced, out_dev, j, s, st)
                return
            with self._sp("compress.wait", rows=len(batch)):
                out, size = jax.device_get(res)
            st.host_bytes += out.nbytes + size.nbytes
            for j, chunk in enumerate(batch):
                s = int(size[j])
                yield chunk, len(chunk), s, functools.partial(_slice_payload, out, j, s)
        else:
            with self._sp("compress.wait", rows=len(batch)):
                emit, pos, length, offset, size = jax.device_get(
                    (res.emit, res.pos, res.length, res.offset, res.size)
                )
            st.host_bytes += (emit.nbytes + pos.nbytes + length.nbytes
                              + offset.nbytes + size.nbytes)
            for j, chunk in enumerate(batch):
                yield chunk, len(chunk), int(size[j]), functools.partial(
                    emit_block, chunk, emit[j], pos[j], length[j], offset[j],
                    len(chunk),
                )

    # -- public API ---------------------------------------------------------

    def compress(self, data: bytes) -> bytes:
        """bytes -> self-describing frame (see frame.py / docs/frame-format.md).

        Blocks whose exact compressed size (computed in-graph) does not beat
        the raw size are stored as raw passthrough, so worst-case expansion
        is the frame header, not LZ4's literal-run overhead.  With a mesh or
        ``shards=`` configured the call routes through the sharded fabric
        (distributed/fabric.py) and the output is a frame-v4 container.
        """
        st = EngineStats()
        self.stats = st
        ob = self._obs_on()
        sp = obs.span_factory(ob)
        if self.shards is not None:
            from repro.distributed import fabric

            try:
                with sp("compress.total", bytes_in=len(data),
                        shards=self.shards):
                    return fabric.compress_sharded(self, data, st)
            finally:
                self._finish_call(st)
        ratio_hist = obs.registry().histogram(
            "engine.block_ratio", obs.DEFAULT_RATIO_BUCKETS,
            "per-block compression ratio usize/csize (raw blocks -> 1.0)",
        ) if ob else None
        try:
            with sp("compress.total", bytes_in=len(data)):
                payloads, usizes, raws, crcs = [], [], [], []
                for chunk, n, size, payload_fn in self._payload_iter(data, st):
                    if size >= n:
                        payloads.append(chunk)
                        raws.append(True)
                        st.raw_blocks += 1
                        if ratio_hist is not None and n:
                            ratio_hist.observe(1.0)
                    else:
                        payloads.append(payload_fn())
                        raws.append(False)
                        if ratio_hist is not None and size:
                            ratio_hist.observe(n / size)
                    usizes.append(n)
                    # Content checksum over the ORIGINAL chunk (only the
                    # compressor ever sees it): makes the frame a version-2,
                    # integrity-checked container — decode verifies per block.
                    crcs.append(block_crc(chunk))
                with sp("compress.frame", blocks=len(payloads)):
                    frame = encode_frame(
                        payloads, usizes, raws, checksums=crcs,
                        content_crc=block_crc(data)
                        if (self.content_crc or self.parity_group is not None)
                        else None,
                        parity_group=self.parity_group)
                st.bytes_out = len(frame)
                return frame
        finally:
            self._finish_call(st)

    def compress_to_blocks(self, data: bytes) -> list[bytes]:
        """bytes -> list of raw LZ4 blocks (one per 64 KB, no framing).

        Every block is valid LZ4 (no passthrough); lengths must travel
        out-of-band.
        Sharded engines partition the block stack across shards (same
        contiguous split as `compress`) but the output is the same flat,
        globally-ordered block list.
        """
        st = EngineStats()
        self.stats = st
        if not data:
            # Host-emitted empty block: no dispatch.
            st.blocks = 1
            self._finish_call(st)
            return [emit_block(b"", [], [], [], [], 0)]
        if self.shards is not None:
            from repro.distributed import fabric

            try:
                with obs.span_factory(self._obs_on())(
                        "compress.total", bytes_in=len(data), framing=False,
                        shards=self.shards):
                    blocks = fabric.shard_blocks_sharded(self, data, st)
                st.bytes_out = sum(len(b) for b in blocks)
                return blocks
            finally:
                self._finish_call(st)
        try:
            with obs.span_factory(self._obs_on())(
                    "compress.total", bytes_in=len(data), framing=False):
                blocks = [payload_fn() for _, _, _, payload_fn
                          in self._payload_iter(data, st)]
            st.bytes_out = sum(len(b) for b in blocks)
            return blocks
        finally:
            self._finish_call(st)

    def decompress(self, frame: bytes) -> bytes:
        """Inverse of `compress`; validates the frame (sizes + checksums)
        throughout.  Delegates to the parallel `LZ4DecodeEngine`."""
        return decode_frame(frame)
