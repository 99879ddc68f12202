"""Parallel two-phase decompression: the `LZ4DecodeEngine` and `FrameReader`.

The mirror image of engine.py's compress pipeline.  `decode_frame` used to
walk blocks serially in Python, so every restore path (serving KV-offload,
checkpoint load, the data pipeline) was bottlenecked on one byte loop.  The
frame's blocks are independent by construction, which makes the read side
embarrassingly parallel (Sitaridi et al., arXiv 1606.00519):

  * each block is decoded in two phases — `plan_block_fast` parses the token
    stream once into flat NumPy copy arrays (feedback-free field extraction,
    decode_plan.py), `execute_plan` runs the literal/match copies in bulk;
  * independent blocks fan out across a worker pool.  Four executors:

      "serial"   — decode blocks inline.  The default: the planned decoder
                   already beats the old serial `decode_frame`, and on
                   GIL-bound CPython a thread pool cannot add more (see
                   EXPERIMENTS.md for measurements).
      "thread"   — ThreadPoolExecutor.  Pays on free-threaded builds and
                   when block decode offloads to an accelerator; on stock
                   CPython the GIL serializes the Python residue.
      "process"  — fork-based ProcessPoolExecutor, blocks round-trip as
                   bytes.  True multi-core decode on CPython.  Opt-in:
                   forking a process with live JAX threads is officially
                   discouraged (workers never touch JAX, and only the pool
                   fork happens, but create the engine early if you use it).
                   Checked under a parent that holds a TPU v5e chip: the
                   forked workers decode correctly (JAX warns at the fork).
      "device"   — phase two runs INSIDE jit: host planning
                   (`plan_block_fast` -> `to_device_plan`) stacks a
                   micro-batch of fixed-shape `DevicePlan`s and ONE
                   vmapped+jitted `kernels.ops.decode_gather` dispatch
                   resolves and materializes every block's bytes on the
                   accelerator (pointer-doubling source resolve — see
                   decode_plan.py), double-buffered like the compress
                   engine.  The read-side mirror of `device_emit`:
                   `DecodeStats.host_bytes` counts exactly the decoded
                   bytes fetched back (or nothing, via
                   `decode_to_device` — the accelerator-to-accelerator
                   restore path used by serving KV-offload, whose CRC
                   verification also runs in-graph, so even verified
                   restores fetch no content).  Blocks whose
                   plans overflow the fixed caps fall back to the host
                   executor per block (counted in `fallback_blocks`).
                   With ``plan_on_device=True`` phase ONE moves in-graph
                   too: the speculative planner
                   (`kernels.ops.plan_speculative`: the parse of
                   `kernels.ref.plan_fields_ref`, validated and
                   compacted) parses the token
                   stream on device and `kernels.ops.plan_decode` fuses
                   plan + gather + CRC into a single dispatch — the last
                   host O(n) stage is gone, and `host_bytes == 0` on the
                   to-device paths now includes planning.  Malformed or
                   caps-overflowing blocks surface through a 5-lane
                   status vector; overflows replan on host (counted),
                   parse errors raise the host planner's exact message.

  * version-2 frames carry per-block CRC32s of the uncompressed content,
    verified as each block lands, so corruption is caught at the block that
    suffered it — never returned as silent wrong output.

`FrameReader` adds random access on top (Rapidgzip-style seek index,
arXiv 2308.08955): the frame's block table maps any decompressed byte range
to its covering blocks, so `read_range(start, length)` decodes only those
blocks — partial reads of a multi-gigabyte frame cost O(range), not
O(frame).  `read_block(i)` fetches a single block, with a small LRU so
repeated nearby reads (KV-offload restore of one request's slice) decode
each block once.
"""
from __future__ import annotations

import dataclasses
import functools
import os
import threading
from collections import OrderedDict
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from repro import obs

from .decode_plan import (
    _ERR_MESSAGES,
    MAX_RESOLVE_ROUNDS,
    DevicePlanCaps,
    DevicePlanOverflow,
    execute_plan,
    plan_block_fast,
    to_device_plan,
)
from .decoder import LZ4FormatError, decode_block
from .frame import (
    FrameFormatError,
    block_crc,
    check_block,
    check_content_crc,
    frame_info,
)
from .lz4_types import MAX_BLOCK, pad_pow2_count

__all__ = ["LZ4DecodeEngine", "DecodeStats", "FrameReader",
           "default_decode_engine"]

_EXECUTORS = ("serial", "thread", "process", "device")


@functools.lru_cache(maxsize=None)
def _device_decode_compiled(out_cap: int, rounds: int):
    """Jitted vmap of the single-block decode graph, cached per static
    config (shared across engine instances; jit's own cache then keys on
    the stacked batch shape, bounded by the power-of-two padding)."""
    import jax

    from repro.kernels.ops import decode_gather

    fn = functools.partial(decode_gather, out_cap=out_cap, rounds=rounds)
    return jax.jit(jax.vmap(fn))


@functools.lru_cache(maxsize=None)
def _device_plan_decode_compiled(out_cap: int, max_lit: int, max_match: int,
                                 rounds: int, compute_crc: bool):
    """Jitted vmap of the FUSED plan+decode(+CRC) graph (`kernels.ops.
    plan_decode`) — the speculative-planning twin of
    `_device_decode_compiled`.  One dispatch takes a stacked micro-batch of
    raw compressed payloads and returns decoded rows, per-block status
    vectors, and in-graph checksums: no token stream is ever parsed on
    host."""
    import jax

    from repro.kernels.ops import plan_decode

    fn = functools.partial(plan_decode, out_cap=out_cap, max_lit=max_lit,
                           max_match=max_match, rounds=rounds,
                           compute_crc=compute_crc)
    return jax.jit(jax.vmap(fn))


def _spec_err_message(code: int) -> str:
    """Map a speculative-planner status code to the host planner's exact
    error message (codes 1..8 are `_ERR_MESSAGES`; 9 is the serial parser's
    missing-token error — parity asserted in tests/test_plan_speculative.py)."""
    if code == 9:
        return "truncated block: missing token"
    return _ERR_MESSAGES.get(code, f"invalid stream (status {code})")


def _round_bucket(rounds: int) -> int:
    """Round the needed pointer-doubling depth up to a power of two so the
    number of compiled graph variants stays bounded ({0, 1, 2, 4, 8, 16})."""
    if rounds <= 0:
        return 0
    b = 1
    while b < rounds:
        b <<= 1
    return b


@functools.lru_cache(maxsize=1)
def default_decode_engine() -> "LZ4DecodeEngine":
    """Process-wide default engine (shared by decode_frame, serving,
    checkpointing, and the data pipeline).  Serial executor: safe under
    JAX, and the planned decoder is already faster than the byte loop it
    replaced; construct an engine with executor="process" for multi-core
    restores."""
    return LZ4DecodeEngine()


def _decode_planned(payload: bytes, cap: int, sp=None) -> bytes:
    """Two-phase decode of one block (plan once, execute in bulk).

    ``sp`` is an optional span factory (`obs.span_factory`) so the plan and
    execute phases show up as separate trace stages when telemetry is on.
    """
    if sp is None:
        plan = plan_block_fast(payload, max_out=cap)
        return execute_plan(payload, plan).tobytes()
    with sp("decode.plan", bytes_in=len(payload)):
        plan = plan_block_fast(payload, max_out=cap)
    with sp("decode.execute", bytes_out=plan.usize):
        return execute_plan(payload, plan).tobytes()


def _decode_one(payload: bytes, cap, two_phase: bool, ob: bool):
    """One block through the selected per-block decoder, traced when on.

    Spans recorded in thread-pool workers land in the shared tracer
    (per-thread buffers); spans in PROCESS-pool workers die with the child
    — the process executor is traced at the `decode.total` level only.
    """
    if not ob:
        return (_decode_planned(payload, cap) if two_phase
                else decode_block(payload, cap))
    sp = obs.span_factory(True)
    if two_phase:
        return _decode_planned(payload, cap, sp)
    with sp("decode.execute", bytes_in=len(payload), fused=True):
        return decode_block(payload, cap)


def _frame_block_task(args) -> bytes:
    """Decode + verify one frame block (runs in a worker for thread/process
    executors; module-level so it pickles for the process pool)."""
    payload, usize, crc, index, two_phase, ob = args
    try:
        data = _decode_one(payload, usize, two_phase, ob)
    except FrameFormatError:
        raise
    except LZ4FormatError as e:
        raise FrameFormatError(f"block {index}: {e}") from e
    if ob:
        with obs.span_factory(True)("decode.verify", block=index):
            check_block(index, usize, crc, data)
    else:
        check_block(index, usize, crc, data)
    return data


def _plain_block_task(args) -> bytes:
    """Decode one raw LZ4 block (no framing, no checksum)."""
    payload, usize, index, two_phase, ob = args
    cap = usize if usize is not None else MAX_BLOCK
    data = _decode_one(payload, cap, two_phase, ob)
    if usize is not None and len(data) != usize:
        raise LZ4FormatError(
            f"block {index}: decoded {len(data)} bytes, expected {usize}"
        )
    return data


@dataclasses.dataclass
class DecodeStats:
    """Per-call counters (PLUS a lifetime accumulator on the engine).

    Lifecycle — ``engine.stats`` is REPLACED at the start of every
    `decode` / `decode_blocks` / `decode_to_device` call: it describes the
    most recent such call only.  `FrameReader` reads that reach the engine
    (`read_block` on an LRU miss, `read_range` with missing blocks,
    `read_range_device`) carry a per-read stats object of their own: each
    counts as one call in ``engine.totals`` and the ``decode.*`` counters,
    and none touches ``engine.stats``.  For anything that must survive
    across calls use ``engine.totals``, the cumulative sum merged in as
    each call finishes (even on error) — or the ``decode.*`` counters in
    `repro.obs.registry()` when telemetry is on.

    ``host_bytes`` is the read-side twin of `EngineStats.host_bytes`: every
    CONTENT byte fetched device -> host by the "device" executor (exactly
    the decoded payload — rows are slice-fetched to their true usize — or
    zero for a `decode_to_device` restore, which never leaves the
    accelerator: its CRC verification runs in-graph and syncs only a
    4-byte checksum scalar, not counted here).  With ``plan_on_device``
    the zero covers PLANNING too — the speculative planner parses the
    token stream in-graph, and only the per-row status vector (a few
    int32 scalars per block, metadata like the CRC sync) crosses back.

    ``upload_bytes`` counts the other direction: block bytes handed host
    -> device on the to-device paths by raw blocks (uploaded as stored)
    and host-fallback blocks (uploaded after a host decode).  Compressed
    payloads stacked into a decode dispatch are not counted.  A KV page
    resume of one raw 64 KiB block per leaf reads 64 KiB here per leaf.
    """

    blocks: int = 0
    raw_blocks: int = 0
    bytes_in: int = 0
    bytes_out: int = 0
    parallel: bool = False
    dispatches: int = 0        # device executor: jit dispatches issued
    device_blocks: int = 0     # blocks decoded inside the jit graph
    fallback_blocks: int = 0   # device executor blocks decoded on host
    host_bytes: int = 0        # bytes fetched device -> host
    upload_bytes: int = 0      # raw/fallback block bytes put host -> device
    shards: int = 0            # sharded-fabric calls: mesh shard count
    calls: int = 0             # 1 per finished call (totals.calls sums them)

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)

    def accumulate(self, other: "DecodeStats") -> None:
        """Fold ``other`` (one finished call) into this accumulator.

        NOT thread-safe by itself — the engine serializes its `totals`
        accumulation behind a lock (`_finish_call`); external accumulators
        shared across threads need their own.
        """
        o = other  # plain adds: this runs once per page read
        self.blocks += o.blocks
        self.raw_blocks += o.raw_blocks
        self.bytes_in += o.bytes_in
        self.bytes_out += o.bytes_out
        self.dispatches += o.dispatches
        self.device_blocks += o.device_blocks
        self.fallback_blocks += o.fallback_blocks
        self.host_bytes += o.host_bytes
        self.upload_bytes += o.upload_bytes
        self.parallel = self.parallel or other.parallel
        self.shards = max(self.shards, other.shards)
        self.calls += max(other.calls, 1)


class LZ4DecodeEngine:
    """Two-phase (plan/execute) frame decoder with pluggable block fan-out.

    >>> eng = LZ4DecodeEngine(workers=4, executor="process")
    >>> data = eng.decode(frame)             # blocks fan across the pool
    >>> data[a:b] == FrameReader(frame, engine=eng).read_range(a, b - a)
    True

    With ``executor="device"`` phase two runs in jit — one vmapped dispatch
    per micro-batch of stacked `DevicePlan`s — and `decode_to_device`
    returns the restored bytes as a device array without any host copy.
    """

    def __init__(self, workers: int | None = None, executor: str | None = None,
                 min_parallel_blocks: int = 2, two_phase: bool | None = None,
                 micro_batch: int = 8,
                 caps: DevicePlanCaps | None = None,
                 adaptive_rounds: bool = True,
                 plan_on_device: bool = False,
                 on_error: str = "raise",
                 telemetry: bool | None = None,
                 mesh=None,
                 shard_axes: tuple[str, ...] | None = None):
        if executor is not None and executor not in _EXECUTORS:
            raise ValueError(f"executor must be one of {_EXECUTORS}")
        if on_error not in ("raise", "salvage"):
            raise ValueError('on_error must be "raise" or "salvage"')
        if plan_on_device and executor != "device":
            raise ValueError("plan_on_device requires executor='device'")
        if workers is not None and workers < 1:
            raise ValueError("workers must be >= 1")
        if micro_batch < 1:
            raise ValueError("micro_batch must be >= 1")
        # Sharded-fabric configuration — the read-side mirror of
        # `LZ4Engine(mesh=...)`: with a mesh spanning >1 shard, frame-block
        # decode routes through `distributed.fabric.decode_items_sharded`
        # (host planning, then shard_map(vmap(decode_gather)) dispatches).
        if mesh is not None:
            axes = tuple(shard_axes) if shard_axes is not None \
                else tuple(mesh.axis_names)
            for a in axes:
                if a not in mesh.axis_names:
                    raise ValueError(f"shard axis {a!r} not in mesh "
                                     f"{tuple(mesh.axis_names)}")
            from repro.distributed.fabric import mesh_shard_count

            self.mesh, self.shard_axes = mesh, axes
            self.shards = mesh_shard_count(mesh, axes)
        else:
            if shard_axes is not None:
                raise ValueError("shard_axes requires mesh")
            self.mesh, self.shard_axes, self.shards = None, (), 1
        if executor is None:
            executor = "serial" if (workers or 1) == 1 else "thread"
        if workers is None:
            workers = 1 if executor in ("serial", "device") \
                else min(4, os.cpu_count() or 1)
        self.workers = workers
        self.executor = executor if (workers > 1 or executor == "device") \
            else "serial"
        self.min_parallel_blocks = min_parallel_blocks
        # Device-executor knobs (harmless elsewhere): blocks per vmapped
        # dispatch, fixed plan-array caps, and whether
        # host planning computes exact wave depths so shallow micro-batches
        # compile fewer pointer-doubling rounds (vs the static worst case).
        self.micro_batch = micro_batch
        self.caps = caps or DevicePlanCaps()
        self.adaptive_rounds = adaptive_rounds
        # Speculative in-graph planning: parse the token stream ON DEVICE
        # (kernels.ops.plan_speculative) and fuse plan+execute(+CRC) into
        # one dispatch per micro-batch — `plan_block_fast` runs only as the
        # per-block fallback for payloads/plans that overflow the caps.
        # `adaptive_rounds` has no effect on this path: with no host plan
        # there is no `n_waves`, so the resolve always compiles
        # MAX_RESOLVE_ROUNDS.
        self.plan_on_device = plan_on_device
        # Per-block strategy: the fused chunked decoder wins single-threaded
        # on CPython (one loop, no plan materialization), the two-phase
        # plan/execute decoder releases the GIL through its NumPy phases and
        # is the shape parallel/accelerator backends consume.  Auto: fused
        # inline, two-phase in workers.  Both are bit-identical (tested).
        self.two_phase = (self.executor != "serial") if two_phase is None \
            else two_phase
        # on_error="salvage": `decode` of a damaged frame falls back to the
        # salvage pass (repro.resilience.salvage) and returns everything
        # recoverable with lost blocks zero-filled — NEVER silently: the
        # fallback is counted (``resilience.*`` obs counters) and
        # `last_salvage` holds the full SalvageReport (hole map, per-block
        # errors).  Intact frames are byte-identical either way; "raise"
        # (the default) keeps strict all-or-nothing decode semantics.
        self.on_error = on_error
        self.last_salvage = None
        # Telemetry: None follows the global `repro.obs` gate at call time;
        # True/False pins this instance (never changes decoded bytes).
        self.telemetry = telemetry
        self.stats = DecodeStats()      # most recent call (see DecodeStats)
        self.totals = DecodeStats()     # lifetime accumulator
        # `totals` is shared mutable state: concurrent calls (FrameReader
        # users across threads, serving restore fan-out) each fold their
        # own per-call stats object in under this lock, so lifetime
        # counters never lose updates.  `stats` stays last-call-wins.
        self._totals_lock = threading.Lock()
        self._pool = None
        self._pool_lock = threading.Lock()

    def _obs_on(self) -> bool:
        return obs.enabled_for(self.telemetry)

    def _finish_call(self, st: DecodeStats) -> None:
        """Fold the finished call's stats into `totals` + the obs registry."""
        s = st
        s.calls = 1
        with self._totals_lock:
            self.totals.accumulate(s)
        if self._obs_on():
            r = obs.registry()
            r.counter("decode.calls", "decode calls").inc()
            r.counter("decode.blocks", "frame blocks decoded").inc(s.blocks)
            r.counter("decode.raw_blocks",
                      "raw-passthrough blocks").inc(s.raw_blocks)
            r.counter("decode.bytes_in", "compressed bytes in").inc(s.bytes_in)
            r.counter("decode.bytes_out", "decoded bytes out").inc(s.bytes_out)
            r.counter("decode.dispatches",
                      "device-executor jit dispatches").inc(s.dispatches)
            r.counter("decode.device_blocks",
                      "blocks decoded inside jit").inc(s.device_blocks)
            r.counter("decode.fallback_blocks",
                      "device-executor blocks decoded on host "
                      "(plan overflowed DevicePlanCaps)").inc(s.fallback_blocks)
            r.counter("decode.host_bytes",
                      "content bytes fetched device -> host").inc(s.host_bytes)
            r.counter("decode.upload_bytes",
                      "raw/fallback block bytes put host -> device"
                      ).inc(s.upload_bytes)

    # -- worker pool --------------------------------------------------------

    def _get_pool(self):
        with self._pool_lock:
            if self._pool is None:
                if self.executor == "process":
                    import multiprocessing as mp
                    from concurrent.futures import ProcessPoolExecutor

                    self._pool = ProcessPoolExecutor(
                        self.workers, mp_context=mp.get_context("fork"),
                    )
                else:
                    self._pool = ThreadPoolExecutor(
                        max_workers=self.workers,
                        thread_name_prefix="lz4-decode",
                    )
            return self._pool

    def close(self) -> None:
        with self._pool_lock:
            if self._pool is not None:
                self._pool.shutdown(wait=True)
                self._pool = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def _map(self, fn, items: list, st: DecodeStats) -> list:
        """Run fn over items on the configured executor (inline when the
        batch is too small for fan-out to pay)."""
        if (self.executor in ("thread", "process") and self.workers > 1
                and len(items) >= self.min_parallel_blocks):
            st.parallel = True
            # ~4 chunks per worker: amortizes the process pool's per-task
            # IPC (3x measured) while keeping the tail balanced.
            chunk = max(1, len(items) // (self.workers * 4))
            # list() so the first worker exception propagates to the caller.
            return list(self._get_pool().map(fn, items, chunksize=chunk))
        return [fn(it) for it in items]

    # -- single blocks ------------------------------------------------------

    def decode_block(self, payload: bytes, max_out: int | None = None) -> bytes:
        """Planned decode of one raw LZ4 block (no framing)."""
        return execute_plan(
            payload, plan_block_fast(payload, max_out=max_out)).tobytes()

    def decode_blocks(self, payloads: list[bytes], raws: list[bool],
                      usizes: list[int] | None = None) -> list[bytes]:
        """Decode a bag of independent blocks in parallel.

        ``raws[i]`` marks payloads stored uncompressed (returned as-is).
        ``usizes`` (optional) caps and checks each block's decoded size;
        without it blocks are capped at MAX_BLOCK.  This is the entry point
        for non-frame block stores (the checkpoint format keeps its own
        block index in manifest.json).
        """
        if len(payloads) != len(raws):
            raise ValueError("payloads/raws length mismatch")
        if usizes is not None and len(usizes) != len(payloads):
            raise ValueError("usizes length mismatch")
        st = DecodeStats(
            blocks=len(payloads), raw_blocks=sum(map(bool, raws)),
            bytes_in=sum(len(p) for p in payloads),
        )
        self.stats = st
        try:
            with obs.span_factory(self._obs_on())(
                    "decode.total", blocks=len(payloads),
                    executor=self.executor):
                return self._decode_blocks_inner(payloads, raws, usizes, st)
        finally:
            self._finish_call(st)

    def _decode_blocks_inner(self, payloads, raws, usizes,
                             st: DecodeStats) -> list[bytes]:
        ob = self._obs_on()
        out: list[bytes | None] = [None] * len(payloads)
        if self.mesh is not None and self.shards > 1:
            from repro.distributed import fabric

            st.shards = self.shards
            items = [(i, bytes(p),
                      usizes[i] if usizes is not None else None, None,
                      bool(raw))
                     for i, (p, raw) in enumerate(zip(payloads, raws))]
            out = fabric.decode_items_sharded(self, items, st)
            st.bytes_out = sum(len(d) for d in out)
            return out
        if self.executor == "device" and self.plan_on_device:
            self._decode_blocks_specplan(payloads, raws, usizes, out, st)
        elif self.executor == "device":
            jobs = []
            for i, (payload, raw) in enumerate(zip(payloads, raws)):
                payload = bytes(payload)
                if raw:
                    out[i] = payload
                    continue
                usize = usizes[i] if usizes is not None else None
                plan, dplan = self._plan_for_device(
                    payload, usize if usize is not None else MAX_BLOCK)
                if usize is not None and plan.usize != usize:
                    raise LZ4FormatError(
                        f"block {i}: decoded {plan.usize} bytes, "
                        f"expected {usize}"
                    )
                if dplan is None:
                    st.fallback_blocks += 1
                    out[i] = execute_plan(payload, plan).tobytes()
                else:
                    jobs.append((i, payload, dplan))

            def finish(slot, payload, dp, row):
                out[slot] = self._fetch_row(row, dp.out_size, st)

            self._execute_device(jobs, finish, st)
        else:
            jobs = []
            for i, (payload, raw) in enumerate(zip(payloads, raws)):
                if raw:
                    out[i] = bytes(payload)
                else:
                    jobs.append((i, (bytes(payload),
                                     usizes[i] if usizes is not None else None,
                                     i, self.two_phase, ob)))
            for (i, _), data in zip(jobs, self._map(_plain_block_task,
                                                    [j for _, j in jobs], st)):
                out[i] = data
        st.bytes_out = sum(len(d) for d in out)
        return out

    # -- device executor ----------------------------------------------------

    def _plan_for_device(self, payload: bytes, cap: int | None):
        """Host phase one for the device executor: plan, then convert to a
        fixed-shape DevicePlan.  Returns (plan, dplan-or-None); a None
        dplan means the plan overflowed the caps and this block must
        execute on host (the per-block fallback, counted by the caller)."""
        with obs.span_factory(self._obs_on())(
                "decode.plan", bytes_in=len(payload), executor="device"):
            plan = plan_block_fast(payload, max_out=cap)
            if len(payload) > self.caps.blk_cap:
                return plan, None
            try:
                return plan, to_device_plan(
                    plan, self.caps, compute_waves=self.adaptive_rounds)
            except DevicePlanOverflow:
                return plan, None

    def _dispatch_device(self, batch: list, st: DecodeStats):
        """ONE vmapped jit dispatch for a micro-batch of (payload, dplan).

        Pads the batch count to the next power of two (bounded compile
        shapes, like the compress engine) and buckets the pointer-doubling
        depth to a power of two; padding rows decode to out_size=0.
        """
        import jax.numpy as jnp

        sp = obs.span_factory(self._obs_on())
        caps = self.caps
        m = pad_pow2_count(len(batch), self.micro_batch)
        blk = np.zeros((m, caps.blk_cap), np.uint8)
        lit = [np.zeros((m, caps.max_lit), np.int32) for _ in range(3)]
        mat = [np.zeros((m, caps.max_match), np.int32) for _ in range(2)]
        scal = [np.zeros((m,), np.int32) for _ in range(3)]
        rounds = 0
        for j, (payload, dp) in enumerate(batch):
            blk[j, : len(payload)] = np.frombuffer(payload, np.uint8)
            lit[0][j], lit[1][j], lit[2][j] = dp.lit_src, dp.lit_dst, dp.lit_len
            mat[0][j], mat[1][j] = dp.match_dst, dp.match_off
            scal[0][j], scal[1][j], scal[2][j] = dp.n_lit, dp.n_match, dp.out_size
            rounds = max(rounds, dp.n_waves)
        fn = _device_decode_compiled(caps.out_cap, _round_bucket(rounds))
        st.dispatches += 1
        st.device_blocks += len(batch)
        with sp("decode.execute", rows=len(batch), executor="device",
                rounds=rounds):
            return fn(jnp.asarray(blk), *(jnp.asarray(a) for a in lit),
                      *(jnp.asarray(a) for a in mat),
                      *(jnp.asarray(a) for a in scal))

    def _execute_device(self, jobs: list, finish, st: DecodeStats) -> None:
        """Micro-batched, double-buffered device execution.

        ``jobs``: list of (slot, payload, dplan); ``finish(slot, payload,
        dplan, row)`` consumes one block's device output row (a jnp view of
        the padded output buffer) as each micro-batch drains.  Micro-batch
        i+1 is dispatched before batch i's rows are consumed, so host-side
        stacking overlaps device compute (jax dispatch is asynchronous).
        """
        inflight = None
        for start in range(0, len(jobs), self.micro_batch):
            chunk = jobs[start: start + self.micro_batch]
            res = self._dispatch_device([(p, dp) for _, p, dp in chunk], st)
            if inflight is not None:
                prev, out = inflight
                for row, (slot, payload, dp) in enumerate(prev):
                    finish(slot, payload, dp, out[row])
            inflight = (chunk, res)
        if inflight is not None:
            prev, out = inflight
            for row, (slot, payload, dp) in enumerate(prev):
                finish(slot, payload, dp, out[row])

    def _fetch_row(self, row, usize: int, st: DecodeStats) -> bytes:
        """Slice-fetch exactly `usize` decoded bytes of one output row
        (the transfer the host_bytes counter measures).  The span doubles
        as the device-wait measurement: the fetch synchronizes on the
        dispatched decode graph."""
        with obs.span_factory(self._obs_on())("decode.drain", bytes=usize):
            data = np.asarray(row[:usize]).tobytes()
        st.host_bytes += usize
        return data

    # -- device executor: speculative in-graph planning ---------------------

    def _dispatch_specplan(self, batch: list, st: DecodeStats,
                           compute_crc: bool):
        """ONE fused plan+decode jit dispatch for a micro-batch of raw
        (payload, max_out) pairs — the speculative twin of
        `_dispatch_device`, minus the host parse: payloads are stacked
        as-is and the device does header decode, chain select, validation,
        layout, resolve, and (optionally) CRC in a single graph.
        """
        import jax.numpy as jnp

        from repro.kernels import ops as kops

        sp = obs.span_factory(self._obs_on())
        caps = self.caps
        m = pad_pow2_count(len(batch), self.micro_batch)
        blk = np.zeros((m, caps.blk_cap + kops.SPEC_PAD), np.uint8)
        ns = np.zeros((m,), np.int32)
        mo = np.zeros((m,), np.int32)
        for j, (payload, max_out) in enumerate(batch):
            blk[j, : len(payload)] = np.frombuffer(payload, np.uint8)
            ns[j] = len(payload)
            mo[j] = max_out
        fn = _device_plan_decode_compiled(caps.out_cap, caps.max_lit,
                                          caps.max_match, MAX_RESOLVE_ROUNDS,
                                          compute_crc)
        st.dispatches += 1
        with sp("decode.plan_device", rows=len(batch), executor="device",
                crc=compute_crc):
            return fn(jnp.asarray(blk), jnp.asarray(ns), jnp.asarray(mo))

    def _execute_specplan(self, jobs: list, finish, st: DecodeStats,
                          compute_crc: bool) -> None:
        """Micro-batched, double-buffered speculative execution.

        ``jobs``: list of (slot, payload, max_out); ``finish(slot, payload,
        stat, row, crc)`` consumes one block's host status vector (a
        (SPEC_STATUS,) np.int32 — fetching it synchronizes the dispatch,
        like `_fetch_row`; 20 bytes of metadata, uncounted by the content
        ledger `host_bytes`), decoded device row, and device CRC scalar.
        Batch i+1 is dispatched before batch i's statuses are fetched, so
        stacking overlaps device compute exactly like `_execute_device`.
        """
        def drain(chunk, res):
            out, status, crc = res
            stat = np.asarray(status)
            for row, (slot, payload, _max_out) in enumerate(chunk):
                finish(slot, payload, stat[row], out[row], crc[row])

        inflight = None
        for start in range(0, len(jobs), self.micro_batch):
            chunk = jobs[start: start + self.micro_batch]
            res = self._dispatch_specplan(
                [(p, mo) for _, p, mo in chunk], st, compute_crc)
            if inflight is not None:
                drain(*inflight)
            inflight = (chunk, res)
        if inflight is not None:
            drain(*inflight)

    def _decode_blocks_specplan(self, payloads, raws, usizes, out,
                                st: DecodeStats) -> None:
        """`decode_blocks` body for the speculative planner (fills `out`).

        Error parity with the host-planner branch: parse errors raise the
        planner's exact message unwrapped; a decoded-size mismatch against
        a caller-provided usize raises ``block {i}: decoded ... expected``.
        Payloads over `blk_cap` and plans over the fixed caps take the same
        counted host fallback.
        """
        jobs = []
        for i, (payload, raw) in enumerate(zip(payloads, raws)):
            payload = bytes(payload)
            if raw:
                out[i] = payload
                continue
            usize = usizes[i] if usizes is not None else None
            cap = usize if usize is not None else MAX_BLOCK
            if len(payload) > self.caps.blk_cap:
                st.fallback_blocks += 1
                plan = plan_block_fast(payload, max_out=cap)
                if usize is not None and plan.usize != usize:
                    raise LZ4FormatError(
                        f"block {i}: decoded {plan.usize} bytes, "
                        f"expected {usize}"
                    )
                out[i] = execute_plan(payload, plan).tobytes()
                continue
            jobs.append((i, payload, cap))

        from repro.kernels import ops as kops

        def finish(slot, payload, stat, row, _crc):
            err = int(stat[kops.SPEC_ERR])
            if err:
                raise LZ4FormatError(_spec_err_message(err))
            usize = usizes[slot] if usizes is not None else None
            if int(stat[kops.SPEC_OVERFLOW]):
                st.fallback_blocks += 1
                cap = usize if usize is not None else MAX_BLOCK
                plan = plan_block_fast(payload, max_out=cap)
                if usize is not None and plan.usize != usize:
                    raise LZ4FormatError(
                        f"block {slot}: decoded {plan.usize} bytes, "
                        f"expected {usize}"
                    )
                out[slot] = execute_plan(payload, plan).tobytes()
                return
            out_size = int(stat[kops.SPEC_OUT_SIZE])
            if usize is not None and out_size != usize:
                raise LZ4FormatError(
                    f"block {slot}: decoded {out_size} bytes, "
                    f"expected {usize}"
                )
            st.device_blocks += 1
            out[slot] = self._fetch_row(row, out_size, st)

        self._execute_specplan(jobs, finish, st, compute_crc=False)

    def _specplan_host_fallback(self, i: int, b: dict, payload: bytes,
                                to_device: bool, st: DecodeStats, sp):
        """Host plan+execute for one frame block the speculative path cannot
        keep on device — payload over `blk_cap`, or a valid plan that
        overflowed the fixed caps.  Same counted per-block fallback
        semantics as the host planner's `DevicePlanOverflow` path,
        including the plan-time size-vs-table parity check and the
        unconditional post-decode `check_block`."""
        st.fallback_blocks += 1
        try:
            with sp("decode.plan", bytes_in=len(payload), executor="device",
                    fallback=True):
                plan = plan_block_fast(payload, max_out=b["usize"])
        except FrameFormatError:
            raise
        except LZ4FormatError as e:
            raise FrameFormatError(f"block {i}: {e}") from e
        if plan.usize != b["usize"]:
            raise FrameFormatError(
                f"block {i}: decoded {plan.usize} bytes, "
                f"table says {b['usize']}"
            )
        with sp("decode.execute", block=i, fallback=True):
            data = execute_plan(payload, plan).tobytes()
        with sp("decode.verify", block=i):
            check_block(i, b["usize"], b["crc"], data)
        return self._host_result(data, to_device, i, st, sp)

    def _decode_entries_specplan(self, frame: bytes,
                                 entries: list[tuple[int, dict]],
                                 to_device: bool, verify: bool,
                                 st: DecodeStats):
        """`_decode_entries_device` with speculative in-graph planning.

        The whole per-block pipeline — header parse, chain select,
        validation, layout, resolve, CRC — runs as one fused dispatch per
        micro-batch; the host touches only each block's (SPEC_STATUS,)
        status vector.  With ``to_device=True`` the decoded content never
        crosses device->host (the CRC comes from the same fused graph), so
        `DecodeStats.host_bytes` stays 0 INCLUDING planning.  Error parity
        with the host-planner path: parse errors raise
        ``block {i}: <planner message>``, size mismatches raise the
        ``table says`` message, caps overflows take the counted host
        fallback.
        """
        from repro.kernels import ops as kops

        sp = obs.span_factory(self._obs_on())
        meta = {}
        out: list = [None] * len(entries)
        jobs = []
        pending_crc: list[tuple[int, object, int]] = []
        for j, (i, b) in enumerate(entries):
            payload = frame[b["offset"]: b["offset"] + b["csize"]]
            if b["raw"]:
                with sp("decode.verify", block=i, raw=True):
                    check_block(i, b["usize"], b["crc"], payload)
                out[j] = self._host_result(payload, to_device, i, st, sp)
                continue
            if len(payload) > self.caps.blk_cap:
                out[j] = self._specplan_host_fallback(
                    i, b, payload, to_device, st, sp)
                continue
            meta[j] = (i, b)
            jobs.append((j, payload, b["usize"]))

        def finish(slot, payload, stat, row, crc):
            i, b = meta[slot]
            err = int(stat[kops.SPEC_ERR])
            if err:
                raise FrameFormatError(f"block {i}: {_spec_err_message(err)}")
            if int(stat[kops.SPEC_OVERFLOW]):
                out[slot] = self._specplan_host_fallback(
                    i, b, payload, to_device, st, sp)
                return
            out_size = int(stat[kops.SPEC_OUT_SIZE])
            if out_size != b["usize"]:
                raise FrameFormatError(
                    f"block {i}: decoded {out_size} bytes, "
                    f"table says {b['usize']}"
                )
            st.device_blocks += 1
            if to_device:
                # The in-graph CRC scalar rides the fused dispatch; the
                # host compare is DEFERRED so it never stalls the drain.
                if verify and b["crc"] is not None:
                    pending_crc.append((i, crc, b["crc"]))
                out[slot] = row[:out_size]
                return
            data = self._fetch_row(row, out_size, st)
            with sp("decode.verify", block=i):
                check_block(i, b["usize"], b["crc"], data)
            out[slot] = data

        self._execute_specplan(jobs, finish, st,
                               compute_crc=bool(to_device and verify))
        self._check_pending_crc(pending_crc, sp)
        return out

    # -- frames -------------------------------------------------------------

    def _decode_entries(self, frame: bytes, entries: list[tuple[int, dict]],
                        st: DecodeStats) -> list[bytes]:
        """Decode the given (index, table-entry) frame blocks, in order,
        counting into ``st``, the owning call's stats object."""
        if self.mesh is not None and self.shards > 1:
            from repro.distributed import fabric

            st.shards = self.shards
            items = [(i, frame[b["offset"]: b["offset"] + b["csize"]],
                      b["usize"], b["crc"], b["raw"]) for i, b in entries]
            return fabric.decode_items_sharded(self, items, st)
        if self.executor == "device":
            return self._decode_entries_device(frame, entries,
                                               to_device=False, verify=True,
                                               st=st)
        ob = self._obs_on()
        sp = obs.span_factory(ob)
        out: list[bytes | None] = [None] * len(entries)
        jobs = []
        for j, (i, b) in enumerate(entries):
            payload = frame[b["offset"]: b["offset"] + b["csize"]]
            if b["raw"]:
                with sp("decode.verify", block=i, raw=True):
                    check_block(i, b["usize"], b["crc"], payload)
                out[j] = payload
            else:
                jobs.append((j, (payload, b["usize"], b["crc"], i,
                                 self.two_phase, ob)))
        for (j, _), data in zip(jobs, self._map(_frame_block_task,
                                                [a for _, a in jobs], st)):
            out[j] = data
        return out

    def _decode_entries_device(self, frame: bytes,
                               entries: list[tuple[int, dict]],
                               to_device: bool, verify: bool,
                               st: DecodeStats):
        """Device-executor decode of (index, table-entry) frame blocks.

        ``to_device=True`` returns per-block DEVICE arrays (uint8) instead
        of host bytes — and the content NEVER crosses the device->host
        boundary: with ``verify=True`` each block's CRC32 is computed
        in-graph (GF(2) matmuls, `kernels.ops.crc32_bytes`) and only the
        4-byte checksum is fetched for comparison against the table
        (raw/fallback blocks are uploaded host->device;
        `DecodeStats.host_bytes` stays the download-only *content* counter,
        mirroring `EngineStats`, so verified device restores keep it at 0;
        the uploads count in `DecodeStats.upload_bytes`).
        """
        if self.plan_on_device:
            return self._decode_entries_specplan(
                frame, entries, to_device=to_device, verify=verify, st=st)
        if to_device and verify:
            from repro.kernels.ops import crc32_bytes  # already jitted

        sp = obs.span_factory(self._obs_on())
        meta = {}
        out: list = [None] * len(entries)
        jobs = []
        pending_crc: list[tuple[int, object, int]] = []
        for j, (i, b) in enumerate(entries):
            payload = frame[b["offset"]: b["offset"] + b["csize"]]
            if b["raw"]:
                with sp("decode.verify", block=i, raw=True):
                    check_block(i, b["usize"], b["crc"], payload)
                out[j] = self._host_result(payload, to_device, i, st, sp)
                continue
            try:
                plan, dplan = self._plan_for_device(payload, b["usize"])
            except FrameFormatError:
                raise
            except LZ4FormatError as e:
                raise FrameFormatError(f"block {i}: {e}") from e
            # Size-vs-table parity with the host paths, for free at plan
            # time: the plan knows the exact decoded size before dispatch,
            # so a lying table entry is rejected even when ``verify=False``
            # skips the post-decode check_block (which would need a fetch).
            if plan.usize != b["usize"]:
                raise FrameFormatError(
                    f"block {i}: decoded {plan.usize} bytes, "
                    f"table says {b['usize']}"
                )
            if dplan is None:
                st.fallback_blocks += 1
                with sp("decode.execute", block=i, fallback=True):
                    data = execute_plan(payload, plan).tobytes()
                with sp("decode.verify", block=i):
                    check_block(i, b["usize"], b["crc"], data)
                out[j] = self._host_result(data, to_device, i, st, sp)
                continue
            meta[j] = (i, b)
            jobs.append((j, payload, dplan))

        def finish(slot, payload, dp, row):
            i, b = meta[slot]
            dev = row[: dp.out_size]
            if to_device:
                # Size-vs-table parity was enforced at plan time; the CRC
                # check runs in-graph so the content stays device-resident
                # (only the 4-byte checksum comes home, uncounted by the
                # content ledger `host_bytes`).  The checksum dispatch is
                # asynchronous and the host compare is DEFERRED below, so
                # verification never stalls the double-buffered drain.
                if verify and b["crc"] is not None:
                    pending_crc.append((i, crc32_bytes(row, dp.out_size),
                                        b["crc"]))
                out[slot] = dev
                return
            data = self._fetch_row(row, dp.out_size, st)
            with sp("decode.verify", block=i):
                check_block(i, b["usize"], b["crc"], data)
            out[slot] = data

        self._execute_device(jobs, finish, st)
        self._check_pending_crc(pending_crc, sp)
        return out

    @staticmethod
    def _check_pending_crc(pending: list, sp) -> None:
        """Compare the deferred in-graph CRCs with the table (the sync).

        The span opens only when a CRC is pending: a read of raw blocks
        alone has nothing to wait for and records no empty span."""
        if not pending:
            return
        with sp("decode.verify", blocks=len(pending), in_graph=True):
            for i, got, want in pending:
                if int(got) != want:
                    raise FrameFormatError(f"block {i}: checksum mismatch")

    @staticmethod
    def _host_result(data: bytes, to_device: bool, block: int,
                     st: DecodeStats, sp):
        """``data`` as the caller wants it: host bytes, or a device array
        put host -> device (counted in ``st.upload_bytes``)."""
        if not to_device:
            return data
        import jax.numpy as jnp

        with sp("decode.upload", block=block, bytes=len(data)):
            arr = jnp.asarray(np.frombuffer(data, np.uint8))
        st.upload_bytes += len(data)
        return arr

    def salvage(self, frame: bytes):
        """Salvage pass over a (possibly damaged) frame: decode every
        undamaged block on this engine's executor, reconstruct what v6
        parity can prove byte-identical, and return the `SalvageReport`
        (recovered data with holes zero-filled + exact loss accounting).
        See repro/resilience/salvage.py."""
        from repro.resilience.salvage import salvage_frame

        report = salvage_frame(frame, engine=self)
        self.last_salvage = report
        return report

    def decode(self, frame: bytes) -> bytes:
        """Frame -> original bytes; bit-identical to `decode_frame_serial`.

        Raises FrameFormatError on any malformation, including per-block
        checksum mismatches on version-2 frames — unless constructed with
        ``on_error="salvage"``, which turns a failed strict decode into a
        salvage pass returning everything recoverable (lost blocks
        zero-filled; the full accounting lands in ``last_salvage``).
        """
        if self.on_error == "salvage":
            try:
                return self._decode_strict(frame)
            except FrameFormatError:
                return self.salvage(frame).data
        return self._decode_strict(frame)

    def _decode_strict(self, frame: bytes) -> bytes:
        info = frame_info(frame)
        blocks = info["blocks"]
        st = DecodeStats(
            blocks=len(blocks),
            raw_blocks=sum(b["raw"] for b in blocks),
            bytes_in=len(frame),
        )
        self.stats = st
        try:
            with obs.span_factory(self._obs_on())(
                    "decode.total", blocks=len(blocks),
                    executor=self.executor):
                parts = self._decode_entries(frame, list(enumerate(blocks)),
                                             st)
                out = b"".join(parts)
                # v5 whole-object trailer: per-block CRCs already passed,
                # this catches join-order/table-swap corruption they can't.
                check_content_crc(info["content_crc"], block_crc(out))
            st.bytes_out = len(out)
            return out
        finally:
            self._finish_call(st)

    def decode_to_device(self, frame: bytes, verify: bool = True):
        """Frame -> decoded bytes as ONE device uint8 array (no host copy).

        The accelerator-to-accelerator restore path: compressed blocks are
        uploaded, decoded in-graph, and concatenated on device, so a
        KV-offload restore never materializes the plaintext on the host.
        ``verify=True`` (default) checks each block's CRC32 *on device*
        (GF(2) matmuls in-graph, `kernels.ops.crc32_bytes`) and
        fetches only the 4-byte checksum for comparison — verified
        restores keep `host_bytes` at 0 too; ``verify=False`` skips even
        that scalar sync (the frame table's structural validation and the
        host planner's format checks always run).

        Works on any engine instance (it always uses the device execution
        path, regardless of `executor`).
        """
        import jax.numpy as jnp

        info = frame_info(frame)
        blocks = info["blocks"]
        st = DecodeStats(
            blocks=len(blocks),
            raw_blocks=sum(b["raw"] for b in blocks),
            bytes_in=len(frame),
        )
        self.stats = st
        try:
            sp = obs.span_factory(self._obs_on())
            with sp("decode.total", blocks=len(blocks), executor="device",
                    to_device=True, verify=verify):
                parts = self._decode_entries_device(
                    frame, list(enumerate(blocks)), to_device=True,
                    verify=verify, st=st)
                if not parts:
                    out = jnp.zeros((0,), jnp.uint8)
                else:
                    out = parts[0] if len(parts) == 1 \
                        else jnp.concatenate(parts)
                if verify and info["content_crc"] is not None:
                    # v5 whole-object trailer, checked IN-GRAPH over the
                    # concatenated device array (pow2-padded so compiled
                    # shapes stay bounded); like per-block verification,
                    # only the 4-byte checksum crosses to host.
                    from repro.kernels.ops import crc32_bytes

                    total = int(out.shape[0])
                    cap = 1 if total == 0 else 1 << (total - 1).bit_length()
                    padded = out if cap == total else jnp.concatenate(
                        [out, jnp.zeros((cap - total,), jnp.uint8)])
                    with sp("decode.verify", content=True, in_graph=True):
                        crc = int(crc32_bytes(padded, total))
                    check_content_crc(info["content_crc"], crc)
            st.bytes_out = sum(b["usize"] for b in blocks)
            return out
        finally:
            self._finish_call(st)


class FrameReader:
    """Seekable random-access reader over one frame.

    The frame's block table is the seek index: cumulative block usizes map
    decompressed offsets to blocks, so `read_range` touches only the blocks
    covering the requested range and `read_block` exactly one.  Decoded
    blocks pass through a small LRU (``cache_blocks``) so clustered reads —
    a KV-offload restore walking one request's slice, a data-pipeline batch
    re-reading the same shard region — decode each block once.

    >>> r = FrameReader(frame)
    >>> r.read_range(10, 20) == original[10:30]
    True
    """

    def __init__(self, frame: bytes, engine: LZ4DecodeEngine | None = None,
                 cache_blocks: int = 8, on_error: str = "raise"):
        if on_error not in ("raise", "salvage"):
            raise ValueError('on_error must be "raise" or "salvage"')
        self._frame = bytes(frame)
        self._engine = engine or default_decode_engine()
        if on_error == "salvage":
            # Tolerant table parse: a reader over a damaged frame still
            # exposes every readable entry (reads of blocks whose payloads
            # are damaged fail per-block; `salvage()` has the recovery).
            from .frame import scan_frame

            self._info = scan_frame(self._frame)
        else:
            self._info = frame_info(self._frame)
        self.on_error = on_error
        self._blocks = self._info["blocks"]
        # starts[i] = decompressed offset of block i; starts[-1] = total size.
        self._starts = np.concatenate(
            ([0], np.cumsum([b["usize"] for b in self._blocks]))
        ).astype(np.int64)
        self._cache_blocks = cache_blocks
        self._cache: OrderedDict[int, bytes] = OrderedDict()
        self._cache_lock = threading.Lock()

    # -- index --------------------------------------------------------------

    @property
    def block_count(self) -> int:
        # len(blocks), not the header count: a salvage-mode reader over a
        # truncated table exposes only the entries it could read.
        return len(self._blocks)

    @property
    def usize(self) -> int:
        """Total decompressed size (from the table; no payload touched)."""
        return int(self._starts[-1])

    def __len__(self) -> int:
        return self.usize

    def block_range(self, i: int) -> tuple[int, int]:
        """Decompressed [start, end) interval of block i."""
        if not 0 <= i < self.block_count:
            raise IndexError(f"block {i} out of range [0, {self.block_count})")
        return int(self._starts[i]), int(self._starts[i + 1])

    def blocks_for_range(self, start: int, length: int) -> range:
        """Indices of the blocks covering decompressed [start, start+length)."""
        if start < 0 or length < 0 or start + length > self.usize:
            raise ValueError(
                f"range [{start}, {start + length}) outside [0, {self.usize})"
            )
        if length == 0:
            return range(0, 0)
        lo = int(np.searchsorted(self._starts, start, side="right")) - 1
        hi = int(np.searchsorted(self._starts, start + length, side="left"))
        return range(lo, hi)

    # -- reads --------------------------------------------------------------

    def _cache_put(self, i: int, data: bytes) -> None:
        if self._cache_blocks <= 0:
            return
        with self._cache_lock:
            self._cache[i] = data
            self._cache.move_to_end(i)
            while len(self._cache) > self._cache_blocks:
                self._cache.popitem(last=False)

    def _decode(self, idx, to_device: bool = False, verify: bool = True):
        """Blocks ``idx`` through the engine as one read call: a stats
        object of its own, finished into the engine's ``totals`` and the
        ``decode.*`` counters; the engine's ``stats`` is left alone."""
        blocks = self._blocks
        entries = []
        raw = cin = uout = 0
        for i in idx:
            b = blocks[i]
            entries.append((i, b))
            raw += b["raw"]
            cin += b["csize"]
            uout += b["usize"]
        eng = self._engine
        st = DecodeStats(blocks=len(entries), raw_blocks=raw, bytes_in=cin)
        try:
            if to_device:
                parts = eng._decode_entries_device(
                    self._frame, entries, to_device=True, verify=verify,
                    st=st)
            else:
                parts = eng._decode_entries(self._frame, entries, st)
            st.bytes_out = uout
            return parts
        finally:
            eng._finish_call(st)

    def read_block(self, i: int) -> bytes:
        """Decode (or raw-slice) exactly block i, LRU-cached."""
        self.block_range(i)  # bounds check
        with self._cache_lock:
            if i in self._cache:
                self._cache.move_to_end(i)
                return self._cache[i]
        data = self._decode([i])[0]
        self._cache_put(i, data)
        return data

    def read_range(self, start: int, length: int) -> bytes:
        """original[start : start+length], decoding only the covering blocks.

        Blocks already in the LRU are reused; only the missing ones are
        decoded (in one engine call, so parallel executors still fan out),
        and those land in the LRU for the next clustered read.
        """
        cover = self.blocks_for_range(start, length)
        if len(cover) == 0:
            return b""
        have: dict[int, bytes] = {}
        with self._cache_lock:
            for i in cover:
                if i in self._cache:
                    self._cache.move_to_end(i)
                    have[i] = self._cache[i]
        missing = [i for i in cover if i not in have]
        if missing:
            for i, data in zip(missing, self._decode(missing)):
                have[i] = data
                self._cache_put(i, data)
        joined = have[cover[0]] if len(cover) == 1 else \
            b"".join(have[i] for i in cover)
        base = int(self._starts[cover[0]])
        return joined[start - base: start - base + length]

    def read_range_device(self, start: int, length: int, verify: bool = True):
        """`read_range`, but the result is a DEVICE uint8 array.

        Covering blocks are decoded in-graph (`_decode_entries_device`) and
        concatenated + sliced on device, so a KV-offload restore of one
        request's slice never lands on the host — including its CRC check,
        which runs in-graph (``verify=False`` skips even the checksum
        sync; see `LZ4DecodeEngine.decode_to_device`).  Bypasses the
        host-bytes LRU — device buffers are the accelerator's to cache.
        """
        import jax.numpy as jnp

        cover = self.blocks_for_range(start, length)
        if len(cover) == 0:
            return jnp.zeros((0,), jnp.uint8)
        parts = self._decode(cover, to_device=True, verify=verify)
        base = int(self._starts[cover[0]])
        with obs.span_factory(self._engine._obs_on())("decode.slice",
                                                      bytes=length):
            joined = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
            return joined[start - base: start - base + length]

    def read(self) -> bytes:
        """Full decode (parallel over all blocks)."""
        return self._engine.decode(self._frame)

    def salvage(self):
        """Salvage pass over this reader's frame — decode every undamaged
        block, reconstruct from v6 parity where provable, and return the
        `SalvageReport` (repro/resilience/salvage.py).  Works regardless
        of ``on_error`` (a strict reader can still salvage after a read
        raised)."""
        return self._engine.salvage(self._frame)
