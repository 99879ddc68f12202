"""Batched LZ4Engine throughput vs the serial per-block baseline.

Measures blocks/s of `LZ4Engine.compress` over micro-batch sizes
{1, 8, 32, 128} for BOTH emission paths — ``device_emit=True`` (byte
emission inside the jit graph, one padded uint8 buffer + size scalar
crossing the host boundary per block) and ``device_emit=False`` (per-window
match records fetched to host, vectorized NumPy emission) — against the
pre-refactor serial path (one dispatch per 64 KB block + Python byte-loop
emission) on the same corpus and kernel config.

Also records, per path:
  * host-transfer bytes (`EngineStats.host_bytes`): the device-emit path
    must move fewer bytes across the host boundary than the records path —
    this is the acceptance metric for device-side emission;
  * emit-stage throughput: the host emitter timed alone on pre-fetched
    records, vs the device path's fused emit (reported as the marginal
    pipeline cost, since in-graph emission cannot be timed separately).

JSON lands in experiments/benchmarks/engine_batched.json and is mirrored to
BENCH_engine_batched.json at the repo root so the perf trajectory is easy to
diff across PRs.  Methodology notes + measured tables: EXPERIMENTS.md;
parameter guidance distilled from these numbers: docs/tuning.md.
"""
from __future__ import annotations

import json
import os

from repro.core import LZ4Engine, decode_frame
from repro.core.lz4_types import MAX_BLOCK

if __package__ in (None, ""):        # `python benchmarks/engine_batched.py`
    import sys

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from common import dump_telemetry, save_json, timed_best
else:
    from .common import dump_telemetry, save_json, timed_best

BATCH_SIZES = (1, 8, 32, 128)


def _corpus(n_blocks: int) -> bytes:
    from repro.core import corpus_blocks

    full = [b for b in corpus_blocks() if len(b) == MAX_BLOCK]
    reps = -(-n_blocks // len(full))
    return b"".join((full * reps)[:n_blocks])


_timed = timed_best


def run(fast: bool = True) -> dict:
    n_blocks = 32 if fast else 128
    sizes = [b for b in BATCH_SIZES if b <= n_blocks]
    repeat = 1 if fast else 2
    data = _corpus(n_blocks)

    out = {"corpus_blocks": n_blocks, "block_kb": 64}

    # Serial baseline: the pre-refactor compress_bytes path — one jit
    # dispatch per 64 KB block, then Python byte loops for emission.
    # (compress_bytes itself now delegates to the engine, so the legacy
    # shape is reconstructed here from its original building blocks.)
    import jax.numpy as jnp

    from repro.core.encoder import encode_block
    from repro.core.jax_compressor import (
        compress_block_records,
        pad_block,
        records_to_plan,
    )

    def serial():
        blocks = []
        for i in range(0, len(data), MAX_BLOCK):
            chunk = data[i: i + MAX_BLOCK]
            buf, n = pad_block(chunk)
            rec = compress_block_records(jnp.asarray(buf), jnp.int32(n))
            blocks.append(encode_block(chunk, records_to_plan(rec, n)))
        return blocks

    dt = _timed(serial, repeat)
    out["serial_blocks_per_s"] = round(n_blocks / dt, 2)
    out["serial_mbps"] = round(len(data) / dt / 1e6, 2)

    # Both engine emission paths over the micro-batch sweep: "batch" is
    # records + host emit, "device_emit" the in-graph emit.
    ref_frame = None
    for key, device_emit in (("batch", False), ("device_emit", True)):
        out[key] = {}
        for b in sizes:
            eng = LZ4Engine(micro_batch=b, device_emit=device_emit)
            frame = eng.compress(data)
            assert decode_frame(frame) == data, "engine round-trip failed"
            if ref_frame is None:
                ref_frame = frame
            assert frame == ref_frame, "emission paths disagree on frame bytes"
            dt = _timed(lambda: eng.compress(data), repeat)
            out[key][str(b)] = {
                "blocks_per_s": round(n_blocks / dt, 2),
                "mbps": round(len(data) / dt / 1e6, 2),
                "dispatches": eng.stats.dispatches,
                "host_bytes": eng.stats.host_bytes,
            }

    # Host-transfer accounting (acceptance metric for device-side emission):
    # bytes fetched device -> host for one full-corpus compress at the
    # default micro-batch.  The records path moves four (W,) arrays per
    # block; device emit with the default two-step drain moves the size
    # vector plus exactly `size` bytes per block (and nothing for
    # raw-passthrough blocks); drain="full" is the pre-two-step behaviour
    # (whole padded buffer per block), kept measured for the delta.
    mb = str(min(32, max(sizes)))
    records_bytes = out["batch"][mb]["host_bytes"]
    device_bytes = out["device_emit"][mb]["host_bytes"]
    full_eng = LZ4Engine(micro_batch=int(mb), drain="full")
    assert full_eng.compress(data) == ref_frame
    full_bytes = full_eng.stats.host_bytes
    out["host_transfer"] = {
        "micro_batch": int(mb),
        "records_path_bytes": records_bytes,
        "device_emit_bytes": device_bytes,
        "device_emit_full_drain_bytes": full_bytes,
        "reduction_x": round(records_bytes / device_bytes, 3),
        "sliced_vs_full_drain_x": round(full_bytes / device_bytes, 3),
    }

    # Emit-stage throughput.  The host emitter can be timed in isolation
    # (records pre-fetched); the device emitter is fused into the dispatch,
    # so its cost shows up as the pipeline delta between the two paths.
    import numpy as np

    recs = []
    for i in range(0, len(data), MAX_BLOCK):
        chunk = data[i: i + MAX_BLOCK]
        buf, n = pad_block(chunk)
        rec = compress_block_records(jnp.asarray(buf), jnp.int32(n))
        recs.append((chunk, np.asarray(rec.emit), np.asarray(rec.pos),
                     np.asarray(rec.length), np.asarray(rec.offset), n))

    from repro.core.emitter import emit_block

    def host_emit_all():
        return [emit_block(c, e, p, l, o, n) for c, e, p, l, o, n in recs]

    dt = _timed(host_emit_all, repeat)
    out["emit_throughput"] = {
        "host_emit_blocks_per_s": round(n_blocks / dt, 2),
        "host_emit_mbps": round(len(data) / dt / 1e6, 2),
        "device_pipeline_mbps": out["device_emit"][mb]["mbps"],
        "records_pipeline_mbps": out["batch"][mb]["mbps"],
    }

    best = max(v["blocks_per_s"]
               for key in ("batch", "device_emit") for v in out[key].values())
    out["speedup_best_vs_serial"] = round(best / out["serial_blocks_per_s"], 3)
    save_json("engine_batched", out)
    root = os.path.join(os.path.dirname(__file__), "..", "BENCH_engine_batched.json")
    with open(root, "w") as f:
        json.dump(out, f, indent=1)
    # With REPRO_OBS=1: export the write-path trace/metrics bundle
    # (dispatch/wait/drain spans, engine.* counters, block-ratio histogram)
    # for tools/trace_report.py; no-op otherwise.
    dump_telemetry("engine_batched")
    return out


if __name__ == "__main__":
    from repro import compile_cache

    compile_cache.enable()
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    args = ap.parse_args()
    print(json.dumps(run(fast=not args.full), indent=1))
