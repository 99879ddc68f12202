"""Sharded multi-chip compression fabric: `shard_map` compress/decode.

The paper's throughput story — many parallelization windows compressing
independently — scales past one chip only if the BLOCK STACK itself is
sharded.  This module is that refactor: the 64 KB block stack is partitioned
into contiguous per-shard slices over the mesh axes defined in
`repro/distributed/sharding.py`, each mesh shard runs the single-device
datapath (`compress_block_bytes` / `kernels.ops.decode_gather`) on its slice
inside ONE `shard_map`-wrapped vmapped jit dispatch, and the per-shard
outputs merge into a **frame v4** container — a shard-aware block table
(`src/repro/core/frame.py`) that stays seekable across shard boundaries.

Partition-compress-merge is the container shape parallel producers want
(Rapidgzip, arXiv 2308.08955; Noel et al. 2023 survey exactly this
decomposition): blocks remain independent and in global content order, so
`FrameReader.read_range` / `read_range_device` work on v4 frames unchanged,
and any single shard's run is byte-identical to a single-device engine run
on the same slice (the fabric's core invariant, asserted by
`tests/test_distributed.py` and `benchmarks/sharded_fabric.py`).

Two execution paths, bit-identical by construction:

  * **mesh path** (`mesh` with >1 shard): one global
    ``(S*r, MAX_BLOCK+_PAD)`` stack per step, put straight onto its shards
    (each chip receives only its ``r`` rows), every shard compresses its
    rows concurrently under `shard_map`, and the two-step sliced drain
    fetches each compressed payload (rounded up to `engine.FETCH_QUANTUM`)
    from the chip that holds its row, through the same single-device
    slice program the one-chip engine uses.  Decode mirrors it: host
    planning (`plan_block_fast` -> `to_device_plan`) stacks fixed-shape
    `DevicePlan`s per shard and one `shard_map`(vmap(`decode_gather`))
    dispatch resolves every block.
  * **host path** (no mesh, or a 1-shard mesh): each shard's slice runs
    through a plain single-device `LZ4Engine` worker sequentially — the
    ORACLE the mesh path is pinned against, and what keeps the v4 writer
    (and its differential tests) runnable on a single-device container.

Spans (`repro.obs`): the fabric reuses the engine's ``compress.pad`` /
``compress.dispatch`` / ``compress.wait`` / ``compress.drain`` stage names
(with ``shards=`` attributes; a mesh-path ``compress.drain`` carries the
``shard=`` it fetched from) and adds ``fabric.put`` (the put of a step's
stack onto its shards), ``compress.shard`` (one per shard on the host path)
and ``compress.merge`` — the per-stage table from `tools/trace_report.py`
shows the merge cost directly.  Counters: ``fabric.dispatches``,
``fabric.merged_blocks``, ``fabric.drain_bytes`` (payload bytes the mesh
path fetched to the host), ``fabric.fallback_blocks``.  In a profiler trace
the sharded programs are ``jit_fabric_compress``, ``jit_fabric_decode`` and
``jit_fabric_plan_decode``.

See docs/architecture.md (fabric section) and docs/tuning.md (mesh-shape
guidance) for when sharding pays.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro import obs
from repro.core.decode_plan import execute_plan
from repro.core.decoder import LZ4FormatError
from repro.core.engine import fetch_row_prefix
from repro.core.frame import FrameFormatError, block_crc, check_block, encode_frame, frame_info
from repro.core.jax_compressor import _PAD, compress_block_bytes
from repro.core.lz4_types import MAX_BLOCK, pad_pow2_count

__all__ = [
    "ShardSlice",
    "partition_blocks",
    "mesh_shard_count",
    "compress_sharded",
    "decode_items_sharded",
    "shard_subframe",
]


@dataclasses.dataclass(frozen=True)
class ShardSlice:
    """Contiguous run of global block indices owned by one shard."""

    shard: int
    start: int
    stop: int

    @property
    def count(self) -> int:
        return self.stop - self.start


def partition_blocks(n_blocks: int, shards: int) -> list[ShardSlice]:
    """Balanced contiguous partition of ``n_blocks`` across ``shards``.

    The first ``n_blocks % shards`` shards take one extra block, so uneven
    stacks (blocks % shards != 0) differ by at most one block per shard and
    trailing shards may own zero blocks when blocks < shards.  Contiguity
    is what keeps the merged v4 frame in global content order.
    """
    if shards < 1:
        raise ValueError("shards must be >= 1")
    base, rem = divmod(n_blocks, shards)
    out, pos = [], 0
    for s in range(shards):
        c = base + (1 if s < rem else 0)
        out.append(ShardSlice(s, pos, pos + c))
        pos += c
    return out


def mesh_shard_count(mesh, shard_axes) -> int:
    """Total shard count = product of the mesh sizes of ``shard_axes``."""
    return int(np.prod([mesh.shape[a] for a in shard_axes], dtype=np.int64)) or 1


# ---------------------------------------------------------------------------
# Compress: shard_map over the block stack.
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _sharded_compress_compiled(mesh, shard_axes, hash_bits, max_match, pws,
                               scan_impl):
    """jit(shard_map(vmap(compress_block_bytes))) cached per static config.

    The leading (block) dim of the stack is split over ``shard_axes``; each
    shard runs the plain vmapped single-block graph on its rows — no
    collectives anywhere, so the per-row bytes are identical to the
    single-device dispatch (the invariant the tests pin).
    """
    fn = functools.partial(
        compress_block_bytes,
        hash_bits=hash_bits, max_match=max_match, pws=pws, scan_impl=scan_impl,
    )
    spec = P(shard_axes)
    sm = jax.shard_map(
        jax.vmap(fn), mesh=mesh,
        in_specs=(spec, spec), out_specs=(spec, spec),
        check_vma=False,
    )

    def fabric_compress(stack, ns):
        return sm(stack, ns)

    return jax.jit(fabric_compress)


def _shard_pieces(out_dev, r: int) -> dict:
    """Shard index -> the single-device ``(r, ...)`` piece of a row-sharded
    global array that holds rows ``[i*r, (i+1)*r)``."""
    return {(s.index[0].start or 0) // r: s.data for s in out_dev.addressable_shards}


def _mesh_collect(engine, chunks, slices, st, sp):
    """Mesh path: per-shard lists of (chunk, n, size, payload_fn).

    One step processes up to ``micro_batch`` blocks PER SHARD: the global
    stack is ``(S*r, MAX_BLOCK+_PAD)`` with shard i owning rows
    ``[i*r, (i+1)*r)`` (``r`` power-of-two-padded so compiled shapes stay
    bounded; rows past a shard's slice carry n=0 and are never drained).
    The stack is put straight onto its shards, and each payload is fetched
    from the shard's own single-device piece of the output, so neither the
    put nor the drain moves a row between chips.  Dispatch is
    double-buffered like the single-device engine: step k+1 is stacked and
    dispatched before the host syncs on step k's size vector.
    """
    per = [chunks[sl.start: sl.stop] for sl in slices]
    S = len(per)
    steps = max((len(p) for p in per), default=0)
    mb = engine.micro_batch
    fn = _sharded_compress_compiled(
        engine.mesh, tuple(engine.shard_axes), engine.hash_bits,
        engine.max_match, engine.pws, engine.scan_impl,
    )
    to_mesh = NamedSharding(engine.mesh, P(tuple(engine.shard_axes)))
    drained = obs.registry().counter(
        "fabric.drain_bytes", "payload bytes the mesh path fetched to the host",
    ) if engine._obs_on() else obs.NOOP_METRIC
    out_lists: list[list] = [[] for _ in range(S)]

    def fetch(piece, shard: int, j: int, size: int) -> bytes:
        with sp("compress.drain", bytes=size, shard=shard):
            data, moved = fetch_row_prefix(piece, j, size)
        st.host_bytes += moved
        drained.inc(moved)
        return data

    def drain(meta, res):
        start, counts, r = meta
        out_dev, size_dev = res
        with sp("compress.wait", rows=sum(counts), shards=S):
            sizes = jax.device_get(size_dev)
        st.host_bytes += sizes.nbytes
        pieces = _shard_pieces(out_dev, r)
        for i, cnt in enumerate(counts):
            for j in range(cnt):
                chunk = per[i][start + j]
                size = int(sizes[i * r + j])
                out_lists[i].append((chunk, len(chunk), size,
                                     functools.partial(fetch, pieces[i], i, j,
                                                       size)))

    inflight = None
    for start in range(0, steps, mb):
        counts = [max(0, min(mb, len(p) - start)) for p in per]
        r = pad_pow2_count(max(counts), mb)
        with sp("compress.pad", blocks=sum(counts), shards=S):
            stack = np.zeros((S * r, MAX_BLOCK + _PAD), np.uint8)
            ns = np.zeros((S * r,), np.int32)
            for i, p in enumerate(per):
                for j in range(counts[i]):
                    c = p[start + j]
                    row = i * r + j
                    stack[row, : len(c)] = np.frombuffer(c, np.uint8)
                    ns[row] = len(c)
        with sp("fabric.put", bytes=stack.nbytes, shards=S):
            stack_dev, ns_dev = jax.device_put((stack, ns), to_mesh)
        st.dispatches += 1
        with sp("compress.dispatch", rows=sum(counts), shards=S):
            res = fn(stack_dev, ns_dev)
        if inflight is not None:
            drain(*inflight)
        inflight = ((start, counts, r), res)
    if inflight is not None:
        drain(*inflight)
    return out_lists


def _host_collect(engine, chunks, slices, st, sp):
    """Host path: each shard's slice through a single-device worker engine.

    This IS the per-shard oracle — shard i's payload bytes are produced by
    exactly the dispatch a standalone `LZ4Engine` would run on the slice,
    so mesh-path equality checks reduce to comparing against this path.
    """
    worker = engine._shard_worker()
    out_lists: list[list] = [[] for _ in slices]
    for sl in slices:
        if sl.count == 0:
            continue
        piece = b"".join(chunks[sl.start: sl.stop])
        with sp("compress.shard", shard=sl.shard, blocks=sl.count):
            out_lists[sl.shard] = list(worker._payload_iter(piece, st))
    return out_lists


def compress_sharded(engine, data: bytes, st) -> bytes:
    """bytes -> frame v4, sharded across ``engine.shards`` producers.

    ``st`` is the engine call's `EngineStats` (the caller owns lifecycle).
    Blocks are partitioned contiguously (`partition_blocks`), compressed on
    the mesh path when ``engine.mesh`` spans >1 shard (host-worker path
    otherwise), and merged — raw-passthrough decisions, CRCs, and the v4
    shard column — under one ``compress.merge`` span.
    """
    ob = engine._obs_on()
    sp = obs.span_factory(ob)
    chunks = [data[i: i + MAX_BLOCK] for i in range(0, len(data), MAX_BLOCK)]
    S = engine.shards
    st.shards = S
    slices = partition_blocks(len(chunks), S)
    if engine.mesh is not None and S > 1:
        # Host path counts blocks/bytes_in inside the worker's
        # `_payload_iter`; the mesh path counts them here.
        st.blocks += len(chunks)
        st.bytes_in += len(data)
        per_shard = _mesh_collect(engine, chunks, slices, st, sp)
    else:
        per_shard = _host_collect(engine, chunks, slices, st, sp)
    ratio_hist = obs.registry().histogram(
        "engine.block_ratio", obs.DEFAULT_RATIO_BUCKETS,
        "per-block compression ratio usize/csize (raw blocks -> 1.0)",
    ) if ob else None
    payloads, usizes, raws, crcs, shard_ids = [], [], [], [], []
    with sp("compress.merge", blocks=len(chunks), shards=S):
        for sl, items in zip(slices, per_shard):
            for chunk, n, size, payload_fn in items:
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
                crcs.append(block_crc(chunk))
                shard_ids.append(sl.shard)
        pg = getattr(engine, "parity_group", None)
        frame = encode_frame(payloads, usizes, raws, checksums=crcs,
                             shards=shard_ids, shard_count=S,
                             content_crc=block_crc(data)
                             if (getattr(engine, "content_crc", False)
                                 or pg is not None)
                             else None,
                             parity_group=pg)
    if ob:
        r = obs.registry()
        r.counter("fabric.dispatches",
                  "sharded compress/decode jit dispatches").inc(st.dispatches)
        r.counter("fabric.merged_blocks",
                  "blocks merged into v4 frames").inc(len(chunks))
    st.bytes_out = len(frame)
    return frame


def shard_blocks_sharded(engine, data: bytes, st) -> list[bytes]:
    """Sharded twin of `LZ4Engine.compress_to_blocks` (raw LZ4 blocks, no
    framing, no raw-passthrough): every block's bytes via its shard's
    datapath, returned in global order."""
    sp = obs.span_factory(engine._obs_on())
    chunks = [data[i: i + MAX_BLOCK] for i in range(0, len(data), MAX_BLOCK)]
    st.shards = engine.shards
    slices = partition_blocks(len(chunks), engine.shards)
    if engine.mesh is not None and engine.shards > 1:
        st.blocks += len(chunks)
        st.bytes_in += len(data)
        per_shard = _mesh_collect(engine, chunks, slices, st, sp)
    else:
        per_shard = _host_collect(engine, chunks, slices, st, sp)
    out = []
    with sp("compress.merge", blocks=len(chunks), shards=engine.shards,
            framing=False):
        for items in per_shard:
            out.extend(payload_fn() for _, _, _, payload_fn in items)
    return out


# ---------------------------------------------------------------------------
# Decode: shard_map over stacked DevicePlans.
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _sharded_decode_compiled(mesh, shard_axes, out_cap, rounds):
    """jit(shard_map(vmap(decode_gather))) cached per static config."""
    from repro.kernels.ops import decode_gather

    fn = functools.partial(decode_gather, out_cap=out_cap, rounds=rounds)
    spec = P(shard_axes)
    sm = jax.shard_map(
        jax.vmap(fn), mesh=mesh,
        in_specs=(spec,) * 9, out_specs=spec,
        check_vma=False,
    )

    def fabric_decode(*args):
        return sm(*args)

    return jax.jit(fabric_decode)


@functools.lru_cache(maxsize=None)
def _sharded_plan_decode_compiled(mesh, shard_axes, out_cap, max_lit,
                                  max_match, rounds):
    """jit(shard_map(vmap(plan_decode))) cached per static config — the
    speculative-planning twin of `_sharded_decode_compiled`: every shard
    parses, validates, and decodes its raw payload rows in one fused graph
    (no host token parse anywhere).  CRC verification stays on host here
    (the sharded frame path returns host bytes and runs `check_block`)."""
    from repro.kernels.ops import plan_decode

    fn = functools.partial(plan_decode, out_cap=out_cap, max_lit=max_lit,
                           max_match=max_match, rounds=rounds,
                           compute_crc=False)
    spec = P(shard_axes)
    sm = jax.shard_map(
        jax.vmap(fn), mesh=mesh,
        in_specs=(spec,) * 3, out_specs=(spec, spec, spec),
        check_vma=False,
    )

    def fabric_plan_decode(blk, ns, max_out):
        return sm(blk, ns, max_out)

    return jax.jit(fabric_plan_decode)


def _round_bucket(rounds: int) -> int:
    if rounds <= 0:
        return 0
    b = 1
    while b < rounds:
        b <<= 1
    return b


def decode_items_sharded(engine, items, st) -> list:
    """Sharded device decode of independent blocks.

    ``items``: list of ``(index, payload, usize, crc, raw)`` in output
    order (``crc`` None skips the checksum, ``usize`` None caps at
    MAX_BLOCK).  Raw blocks short-circuit; blocks whose plans overflow
    `DevicePlanCaps` fall back to host execution (counted in
    ``st.fallback_blocks``); the rest are planned on host, partitioned
    contiguously across the mesh shards, and executed by
    `shard_map`(vmap(`decode_gather`)) dispatches — the read-side mirror of
    the compress fabric.  Returns the decoded bytes per item.

    Engines with ``plan_on_device=True`` route to the speculative path
    instead: raw payloads are stacked as-is and
    `shard_map`(vmap(`plan_decode`)) parses + validates + decodes them in
    one fused dispatch per step, with per-row status vectors checked at
    drain (`_decode_items_sharded_spec`).
    """
    if getattr(engine, "plan_on_device", False):
        return _decode_items_sharded_spec(engine, items, st)
    ob = engine._obs_on()
    sp = obs.span_factory(ob)
    out: list = [None] * len(items)
    jobs = []  # (slot, index, usize, crc, payload, dplan)
    for slot, (i, payload, usize, crc, raw) in enumerate(items):
        if raw:
            with sp("decode.verify", block=i, raw=True):
                check_block(i, usize if usize is not None else len(payload),
                            crc, payload)
            out[slot] = payload
            continue
        try:
            plan, dplan = engine._plan_for_device(
                payload, usize if usize is not None else MAX_BLOCK)
        except FrameFormatError:
            raise
        except LZ4FormatError as e:
            raise FrameFormatError(f"block {i}: {e}") from e
        if usize is not None and plan.usize != usize:
            raise FrameFormatError(
                f"block {i}: decoded {plan.usize} bytes, table says {usize}"
            )
        if dplan is None:
            st.fallback_blocks += 1
            with sp("decode.execute", block=i, fallback=True):
                data = execute_plan(payload, plan).tobytes()
            with sp("decode.verify", block=i):
                check_block(i, plan.usize, crc, data)
            out[slot] = data
            continue
        jobs.append((slot, i, plan.usize, crc, payload, dplan))

    if not jobs:
        return out

    caps = engine.caps
    S = engine.shards
    slices = partition_blocks(len(jobs), S)
    per = [jobs[sl.start: sl.stop] for sl in slices]
    steps = max(len(p) for p in per)
    mb = engine.micro_batch

    def drain(meta, res):
        start, counts, r = meta
        for i, cnt in enumerate(counts):
            for j in range(cnt):
                slot, idx, usize, crc, _payload, _dp = per[i][start + j]
                row = res[i * r + j]
                with sp("decode.drain", bytes=usize):
                    data = np.asarray(row[:usize]).tobytes()
                st.host_bytes += usize
                with sp("decode.verify", block=idx):
                    check_block(idx, usize, crc, data)
                out[slot] = data

    inflight = None
    for start in range(0, steps, mb):
        counts = [max(0, min(mb, len(p) - start)) for p in per]
        r = pad_pow2_count(max(counts), mb)
        blk = np.zeros((S * r, caps.blk_cap), np.uint8)
        lit = [np.zeros((S * r, caps.max_lit), np.int32) for _ in range(3)]
        mat = [np.zeros((S * r, caps.max_match), np.int32) for _ in range(2)]
        scal = [np.zeros((S * r,), np.int32) for _ in range(3)]
        rounds = 0
        for i in range(S):
            for j in range(counts[i]):
                _slot, _idx, _usize, _crc, payload, dp = per[i][start + j]
                row = i * r + j
                blk[row, : len(payload)] = np.frombuffer(payload, np.uint8)
                lit[0][row], lit[1][row], lit[2][row] = (dp.lit_src, dp.lit_dst,
                                                         dp.lit_len)
                mat[0][row], mat[1][row] = dp.match_dst, dp.match_off
                scal[0][row], scal[1][row], scal[2][row] = (dp.n_lit,
                                                            dp.n_match,
                                                            dp.out_size)
                rounds = max(rounds, dp.n_waves)
        fn = _sharded_decode_compiled(engine.mesh, tuple(engine.shard_axes),
                                      caps.out_cap, _round_bucket(rounds))
        st.dispatches += 1
        st.device_blocks += sum(counts)
        with sp("decode.execute", rows=sum(counts), shards=S,
                executor="sharded", rounds=rounds):
            res = fn(jnp.asarray(blk), *(jnp.asarray(a) for a in lit),
                     *(jnp.asarray(a) for a in mat),
                     *(jnp.asarray(a) for a in scal))
        if inflight is not None:
            drain(*inflight)
        inflight = ((start, counts, r), res)
    if inflight is not None:
        drain(*inflight)
    if ob:
        obs.registry().counter(
            "fabric.dispatches",
            "sharded compress/decode jit dispatches").inc(st.dispatches)
        obs.registry().counter(
            "fabric.fallback_blocks",
            "sharded-decode blocks executed on host "
            "(plan overflowed DevicePlanCaps)").inc(st.fallback_blocks)
    return out


def _spec_host_fallback_item(engine, i, payload, usize, crc, st, sp):
    """Host plan+execute for one sharded item the speculative path cannot
    keep on device (payload over `blk_cap` or caps overflow) — counted,
    size-checked against the table, and CRC-verified like the host-planner
    fallback."""
    from repro.core.decode_plan import plan_block_fast

    st.fallback_blocks += 1
    try:
        with sp("decode.plan", bytes_in=len(payload), executor="device",
                fallback=True):
            plan = plan_block_fast(
                payload, max_out=usize if usize is not None else MAX_BLOCK)
    except FrameFormatError:
        raise
    except LZ4FormatError as e:
        raise FrameFormatError(f"block {i}: {e}") from e
    if usize is not None and plan.usize != usize:
        raise FrameFormatError(
            f"block {i}: decoded {plan.usize} bytes, table says {usize}")
    with sp("decode.execute", block=i, fallback=True):
        data = execute_plan(payload, plan).tobytes()
    with sp("decode.verify", block=i):
        check_block(i, plan.usize, crc, data)
    return data


def _decode_items_sharded_spec(engine, items, st) -> list:
    """`decode_items_sharded` with speculative in-graph planning.

    No host token parse: raw compressed payloads are stacked into the
    ``(S*r, blk_cap + SPEC_PAD)`` global buffer with their lengths and
    size caps, and ONE `shard_map`(vmap(`plan_decode`)) dispatch per step
    parses candidate headers, selects chains, validates, lays out, and
    decodes every shard's rows.  The host consumes only each row's
    (SPEC_STATUS,) status vector at drain — parse errors raise the host
    planner's exact per-block message, size mismatches the ``table says``
    message, caps overflows take the counted host fallback (error parity
    with `LZ4DecodeEngine._decode_entries_specplan`).
    """
    from repro.core.decode_engine import _spec_err_message
    from repro.core.decode_plan import MAX_RESOLVE_ROUNDS
    from repro.kernels import ops as kops

    ob = engine._obs_on()
    sp = obs.span_factory(ob)
    out: list = [None] * len(items)
    jobs = []  # (slot, index, usize, crc, payload, max_out)
    for slot, (i, payload, usize, crc, raw) in enumerate(items):
        if raw:
            with sp("decode.verify", block=i, raw=True):
                check_block(i, usize if usize is not None else len(payload),
                            crc, payload)
            out[slot] = payload
            continue
        if len(payload) > engine.caps.blk_cap:
            out[slot] = _spec_host_fallback_item(
                engine, i, payload, usize, crc, st, sp)
            continue
        jobs.append((slot, i, usize, crc, payload,
                     usize if usize is not None else MAX_BLOCK))

    if not jobs:
        return out

    caps = engine.caps
    S = engine.shards
    slices = partition_blocks(len(jobs), S)
    per = [jobs[sl.start: sl.stop] for sl in slices]
    steps = max(len(p) for p in per)
    mb = engine.micro_batch
    fn = _sharded_plan_decode_compiled(
        engine.mesh, tuple(engine.shard_axes), caps.out_cap, caps.max_lit,
        caps.max_match, MAX_RESOLVE_ROUNDS)

    def drain(meta, res):
        start, counts, r = meta
        rows, status, _crc = res
        stat = np.asarray(status)
        for si in range(S):
            for j in range(counts[si]):
                slot, idx, usize, crc, payload, _mo = per[si][start + j]
                row = si * r + j
                err = int(stat[row, kops.SPEC_ERR])
                if err:
                    raise FrameFormatError(
                        f"block {idx}: {_spec_err_message(err)}")
                if int(stat[row, kops.SPEC_OVERFLOW]):
                    out[slot] = _spec_host_fallback_item(
                        engine, idx, payload, usize, crc, st, sp)
                    continue
                out_size = int(stat[row, kops.SPEC_OUT_SIZE])
                if usize is not None and out_size != usize:
                    raise FrameFormatError(
                        f"block {idx}: decoded {out_size} bytes, "
                        f"table says {usize}")
                st.device_blocks += 1
                with sp("decode.drain", bytes=out_size):
                    data = np.asarray(rows[row][:out_size]).tobytes()
                st.host_bytes += out_size
                with sp("decode.verify", block=idx):
                    check_block(idx, out_size, crc, data)
                out[slot] = data

    inflight = None
    for start in range(0, steps, mb):
        counts = [max(0, min(mb, len(p) - start)) for p in per]
        r = pad_pow2_count(max(counts), mb)
        blk = np.zeros((S * r, caps.blk_cap + kops.SPEC_PAD), np.uint8)
        ns = np.zeros((S * r,), np.int32)
        mo = np.zeros((S * r,), np.int32)
        for si in range(S):
            for j in range(counts[si]):
                _slot, _idx, _usize, _crc, payload, max_out = per[si][start + j]
                row = si * r + j
                blk[row, : len(payload)] = np.frombuffer(payload, np.uint8)
                ns[row] = len(payload)
                mo[row] = max_out
        st.dispatches += 1
        with sp("decode.plan_device", rows=sum(counts), shards=S,
                executor="sharded"):
            res = fn(jnp.asarray(blk), jnp.asarray(ns), jnp.asarray(mo))
        if inflight is not None:
            drain(*inflight)
        inflight = ((start, counts, r), res)
    if inflight is not None:
        drain(*inflight)
    if ob:
        obs.registry().counter(
            "fabric.dispatches",
            "sharded compress/decode jit dispatches").inc(st.dispatches)
        obs.registry().counter(
            "fabric.fallback_blocks",
            "sharded-decode blocks executed on host "
            "(plan overflowed DevicePlanCaps)").inc(st.fallback_blocks)
    return out


# ---------------------------------------------------------------------------
# Provenance helpers.
# ---------------------------------------------------------------------------

def shard_subframe(frame: bytes, shard: int) -> bytes:
    """Extract one shard's blocks from a v4 frame as a standalone v3 frame.

    The fabric's core invariant made testable: for every shard,
    ``shard_subframe(v4_frame, s)`` must be byte-identical to
    ``LZ4Engine(<same config>).compress(slice_bytes)`` on that shard's
    slice of the input — no payload is re-encoded here, the bytes are
    lifted straight out of the container.
    """
    info = frame_info(frame)
    if info["shard_count"] is None:
        raise FrameFormatError("not a version-4 (sharded) frame")
    payloads, usizes, raws, crcs = [], [], [], []
    for b in info["blocks"]:
        if b["shard"] != shard:
            continue
        payloads.append(frame[b["offset"]: b["offset"] + b["csize"]])
        usizes.append(b["usize"])
        raws.append(b["raw"])
        crcs.append(b["crc"])
    return encode_frame(payloads, usizes, raws, checksums=crcs)
