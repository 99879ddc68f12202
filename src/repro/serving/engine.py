"""Batched serving engine: prefill + decode loop with a request scheduler and
LZ4 KV-cache offload for paused sessions.

Static-batch design (TPU-friendly shapes): requests are grouped into fixed
batches; prompts are right-aligned/padded to the batch max, decode proceeds
greedily until max_new_tokens.  Paused sessions' KV caches can be offloaded
through the LZ4 engine (serialize -> compress -> host RAM/disk) and restored
bit-exactly — the paper's throughput-optimized compressor sits on exactly
this path in a production fleet.
"""
from __future__ import annotations

import dataclasses

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs.base import ModelConfig
from repro.core.decode_engine import FrameReader, default_decode_engine
from repro.core.engine import default_engine
from repro.core.frame import block_crc, encode_frame
from repro.models import lm
from repro.resilience.errors import FrameError


@dataclasses.dataclass
class Request:
    uid: int
    prompt: list[int]
    max_new_tokens: int = 16
    output: list | None = None


class ServingEngine:
    def __init__(self, cfg: ModelConfig, params, max_batch: int = 4, cache_len: int = 256):
        self.cfg = cfg
        self.params = params
        self.max_batch = max_batch
        self.cache_len = cache_len
        self.queue: list[Request] = []
        self._decode = jax.jit(lm.decode_step, static_argnums=4)
        self._prefill = jax.jit(lm.prefill, static_argnums=(2, 3))

    def add_request(self, req: Request):
        self.queue.append(req)

    def _run_batch(self, reqs: list[Request]) -> None:
        B = len(reqs)
        max_p = max(len(r.prompt) for r in reqs)
        toks = np.zeros((B, max_p), np.int32)
        for i, r in enumerate(reqs):  # right-align so last token is real
            toks[i, max_p - len(r.prompt):] = r.prompt
        batch = {"tokens": jnp.asarray(toks)}
        if self.cfg.family == "encdec":
            batch["enc_embeds"] = jnp.zeros(
                (B, self.cfg.enc_seq, self.cfg.d_model), jnp.dtype(self.cfg.compute_dtype)
            )
        if self.cfg.family == "vlm":
            batch["vision_embeds"] = jnp.zeros(
                (B, self.cfg.vision_tokens, self.cfg.d_model),
                jnp.dtype(self.cfg.compute_dtype),
            )
        with obs.span("serving.prefill", batch=B, max_prompt=max_p):
            cache, logits = self._prefill(self.params, batch, self.cfg, self.cache_len)
        outs = [[] for _ in reqs]
        steps = max(r.max_new_tokens for r in reqs)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        with obs.span("serving.decode_loop", batch=B, steps=steps):
            for _ in range(steps):
                for i in range(B):
                    outs[i].append(int(tok[i]))
                logits, cache = self._decode(self.params, cache, tok, cache["pos"], self.cfg)
                tok = jnp.argmax(logits, -1).astype(jnp.int32)
        for r, o in zip(reqs, outs):
            r.output = o[: r.max_new_tokens]

    def run(self) -> list[Request]:
        done = []
        while self.queue:
            batch = self.queue[: self.max_batch]
            self.queue = self.queue[self.max_batch:]
            self._run_batch(batch)
            done.extend(batch)
        return done


# ---------------------------------------------------------------------------
# KV-cache offload through the LZ4 engine
# ---------------------------------------------------------------------------

def offload_cache(cache) -> tuple[list, dict]:
    """Serialize + LZ4-compress a cache pytree. Returns (blobs, stats).

    Each leaf becomes one self-describing frame: the engine batches all of
    the leaf's 64 KB blocks into micro-batched dispatches, and uncompressible
    blocks ride the frame's raw-passthrough flag — no out-of-band `lz4`
    markers or per-block length lists needed.
    """
    t0 = time.perf_counter()
    with obs.span("serving.offload"):
        leaves, treedef = jax.tree.flatten(cache)
        blobs = []
        raw_total = comp_total = 0
        for leaf in leaves:
            arr = np.asarray(leaf)
            raw = arr.tobytes()
            if len(raw) >= 1024:
                frame = default_engine().compress(raw)
            elif raw:
                # Tiny leaf: a raw single-block frame, no kernel dispatch.
                frame = encode_frame([raw], [len(raw)], [True], checksums=[block_crc(raw)])
            else:
                frame = encode_frame([], [], [], checksums=[])
            blobs.append({"shape": arr.shape, "dtype": str(arr.dtype), "frame": frame})
            raw_total += len(raw)
            comp_total += len(frame)
    stats = {"raw": raw_total, "compressed": comp_total,
             "ratio": raw_total / max(comp_total, 1)}
    if obs.is_enabled():
        obs.counter("serving.offloads", "cache offloads").inc()
        obs.counter("serving.offload_bytes_raw",
                    "serialized cache bytes in").inc(raw_total)
        obs.counter("serving.offload_bytes_compressed",
                    "frame bytes out").inc(comp_total)
        obs.histogram("serving.offload_seconds",
                      help="offload_cache latency").observe(
            time.perf_counter() - t0)
        obs.histogram("serving.offload_ratio", obs.DEFAULT_RATIO_BUCKETS,
                      "whole-cache compression ratio").observe(stats["ratio"])
    return [treedef, blobs], stats


def _device_view(u8, dtype: np.dtype, shape):
    """Reinterpret a device uint8 array as `dtype` and reshape — the
    device-side twin of ``np.frombuffer(...).reshape(...)`` (bitcast, no
    transfer; byte order is the host's little-endian layout either way)."""
    dt = np.dtype(dtype)
    with obs.span("serving.view", dtype=dt.name):
        if dt.itemsize > 1:
            u8 = u8.reshape(-1, dt.itemsize)
        return jax.lax.bitcast_convert_type(u8, dt).reshape(shape)


def restore_cache(obj, decode_engine=None, to_device: bool = False,
                  verify: bool = True, on_error: str = "raise",
                  report: dict | None = None):
    """Full restore: every leaf frame through the parallel decode engine.

    ``on_error="salvage"``: a leaf frame that fails strict decode falls
    back to the salvage pass (`repro.resilience.salvage`) — every
    undamaged block is recovered, frame-v6 parity reconstructs what it
    can prove, and lost spans are zero-filled so the restored tree keeps
    its shapes.  Damage is recorded in ``report`` (leaf index ->
    `SalvageReport`) and the ``resilience.*`` obs counters — never
    silently.  The default ``"raise"`` keeps the strict contract.

    ``to_device=True`` routes each frame through the decode engine's
    device executor (`decode_to_device`): blocks are decompressed inside
    the jit graph and the restored leaves are assembled as device arrays.
    The restore is fully accelerator-to-accelerator either way — with the
    default ``verify=True`` each block's CRC32 is computed in-graph
    (`kernels.ops.crc32_bytes`, GF(2) matmuls) and only the 4-byte
    checksum is synced for comparison, so zero plaintext bytes cross to
    the host (`DecodeStats.host_bytes` 0); ``verify=False`` skips even
    that scalar sync and defers integrity to the caller.  An engine configured with
    ``plan_on_device=True`` keeps even token-stream PLANNING on device
    (the speculative planner, kernels/plan_speculative.py) — the restore
    then has no per-byte host stage at all.
    """
    if on_error not in ("raise", "salvage"):
        raise ValueError('on_error must be "raise" or "salvage"')
    t0 = time.perf_counter()
    treedef, blobs = obj
    eng = decode_engine or default_decode_engine()
    leaves = []
    with obs.span("serving.restore", leaves=len(blobs), to_device=to_device):
        for i, b in enumerate(blobs):
            if to_device:
                try:
                    raw = eng.decode_to_device(b["frame"], verify=verify)
                except FrameError:
                    if on_error != "salvage":
                        raise
                    # Host salvage, then upload: correctness first — the
                    # damaged-frame path is the rare one.
                    rep = eng.salvage(b["frame"])
                    if report is not None:
                        report[i] = rep
                    raw = jnp.asarray(np.frombuffer(rep.data, np.uint8))
                leaves.append(_device_view(raw, np.dtype(b["dtype"]), b["shape"]))
            else:
                try:
                    raw = eng.decode(b["frame"]) if on_error != "salvage" \
                        else eng._decode_strict(b["frame"])
                except FrameError:
                    if on_error != "salvage":
                        raise
                    rep = eng.salvage(b["frame"])
                    if report is not None:
                        report[i] = rep
                    raw = rep.data
                leaves.append(jnp.asarray(
                    np.frombuffer(raw, np.dtype(b["dtype"])).reshape(b["shape"])))
        tree = jax.tree.unflatten(treedef, leaves)
    if obs.is_enabled():
        obs.counter("serving.restores", "cache restores").inc()
        obs.histogram("serving.restore_seconds",
                      help="restore_cache latency").observe(
            time.perf_counter() - t0)
    return tree


class OffloadedCacheReader:
    """Random access into an offloaded cache without a full restore.

    A paused session's cache can be multi-GB; resuming one request, or
    inspecting one layer's KV slice, should not pay a full-tree decompress.
    Each leaf frame gets a lazy `FrameReader`, so a read decodes only the
    64 KB blocks covering the requested element range (the frame block
    table is the seek index) — single-block reads stay single-block.

    ``to_device=True`` makes every read return DEVICE arrays: the covering
    blocks are decompressed inside the jit graph (the decode engine's
    device executor) and sliced/reshaped on the accelerator — the
    accelerator-to-accelerator path a production serving fleet wants
    between offload tiers, with zero plaintext bytes crossing to the host
    (including planning, when the engine speculates in-graph via
    ``plan_on_device=True``).  The default ``verify=True`` keeps that
    property: each block's CRC32 runs in-graph and only the 4-byte
    checksum is synced for comparison; ``verify=False`` defers integrity
    to the caller and skips the sync.

    >>> rdr = OffloadedCacheReader(blob)
    >>> rdr.read_leaf(3, start=128, count=64)   # 64 elements, ~1 block decoded
    >>> OffloadedCacheReader(blob, to_device=True).read_leaf(3)  # jax.Array
    """

    def __init__(self, obj, decode_engine=None, to_device: bool = False,
                 verify: bool = True, on_error: str = "raise"):
        if on_error not in ("raise", "salvage"):
            raise ValueError('on_error must be "raise" or "salvage"')
        self._treedef, self._blobs = obj
        self._engine = decode_engine or default_decode_engine()
        self._to_device = to_device
        self._verify = verify
        # on_error="salvage": leaf readers are built with the tolerant table
        # parse (damaged leaves still expose their readable blocks) and
        # `salvage_leaf` recovers a whole leaf with holes accounted for.
        self.on_error = on_error
        self._readers: list[FrameReader | None] = [None] * len(self._blobs)

    def __len__(self) -> int:
        return len(self._blobs)

    def leaf_meta(self, i: int) -> tuple[tuple, np.dtype]:
        b = self._blobs[i]
        return tuple(b["shape"]), np.dtype(b["dtype"])

    def _reader(self, i: int) -> FrameReader:
        if self._readers[i] is None:
            self._readers[i] = FrameReader(self._blobs[i]["frame"],
                                           engine=self._engine,
                                           on_error=self.on_error)
        return self._readers[i]

    def salvage_leaf(self, i: int):
        """Salvage pass over leaf i's frame: decode every undamaged block,
        reconstruct from v6 parity where provable, zero-fill the rest.
        Returns the `SalvageReport` (repro/resilience/salvage.py) — its
        ``data`` is the leaf's full-length serialized buffer."""
        return self._engine.salvage(self._blobs[i]["frame"])

    def read_leaf_bytes(self, i: int, start: int = 0,
                        length: int | None = None) -> bytes:
        """Byte range of leaf i's serialized buffer (seek-indexed decode)."""
        reader = self._reader(i)
        if length is None:
            length = reader.usize - start
        return reader.read_range(start, length)

    def read_leaf(self, i: int, start: int = 0, count: int | None = None):
        """Flat element slice [start, start+count) of leaf i.

        Returns np.ndarray, or a device-resident jax.Array when the reader
        was built with ``to_device=True`` (the covering blocks decode
        in-graph and only device memory holds the plaintext slice).
        """
        shape, dtype = self.leaf_meta(i)
        total = int(np.prod(shape, dtype=np.int64)) if shape else 1
        if count is None:
            count = total - start
        if start < 0 or count < 0 or start + count > total:
            raise ValueError(f"slice [{start}, {start + count}) outside leaf of {total}")
        timed = obs.is_enabled()
        t0 = time.perf_counter() if timed else 0.0
        with obs.span("serving.read_leaf", leaf=i, count=count,
                      to_device=self._to_device):
            if self._to_device:
                raw = self._reader(i).read_range_device(
                    start * dtype.itemsize, count * dtype.itemsize,
                    verify=self._verify)
                out = _device_view(raw, dtype, (count,))
            else:
                raw = self.read_leaf_bytes(i, start * dtype.itemsize,
                                           count * dtype.itemsize)
                out = np.frombuffer(raw, dtype)
        if timed:
            obs.histogram("serving.read_leaf_seconds",
                          help="partial-restore (resume) read latency"
                          ).observe(time.perf_counter() - t0)
        return out

    def restore(self, report: dict | None = None):
        """Full pytree restore (equivalent to `restore_cache`)."""
        return restore_cache([self._treedef, self._blobs], self._engine,
                             to_device=self._to_device, verify=self._verify,
                             on_error=self.on_error, report=report)
