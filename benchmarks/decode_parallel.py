"""Decompression throughput: serial `decode_frame` vs `LZ4DecodeEngine`,
and seekable `read_range` vs full-decode-then-slice.

Compares, on a multi-block corpus frame (round-trip verified):

  * serial chunked  — `decode_frame_serial` (the pre-PR-2 `decode_frame`:
    one Python loop over blocks, chunked `decode_block` per block);
  * serial bytewise — `decode_frame_serial(bytewise=True)`, the
    byte-at-a-time oracle (lower bound reference);
  * engine inline   — `LZ4DecodeEngine()` (fused chunked per-block decode,
    one worker: the default `decode_frame` path);
  * engine inline planned — same, forced onto the two-phase plan/execute
    per-block decoder (`two_phase=True`);
  * engine thread   — workers in {2, 4}, ThreadPoolExecutor;
  * engine process  — workers in {2, 4}, fork pool (true multi-core);
  * engine device   — `executor="device"`: host planning feeds vmapped jit
    plan execution (pointer-doubling resolve), adaptive and worst-case
    static round counts.  The `device` JSON section also records
    `host_bytes` for the fetch-to-host drain and for the
    `decode_to_device` restore path (0 with verification deferred) —
    transfer symmetry with `BENCH_engine_batched.json`'s `host_transfer`.
    On this CPU container the "device" is the host, so the numbers are
    bookkeeping, not the accelerator end-state (see docs/tuning.md);
  * engine device specplan — `executor="device", plan_on_device=True`:
    the speculative in-graph planner (PR 9) replaces the host
    `plan_block_fast` walk, so plan+execute+CRC is one fused jit dispatch
    per micro-batch.  The `plan_stage` JSON section times the retired
    host O(n) stage (`plan_block_fast` over every compressed payload) so
    the ledger shows exactly what left the host, and asserts the
    restore-path `host_bytes` stays 0 *including planning*.

Configs are timed INTERLEAVED (one rep of each per round, min over rounds)
so CPU-frequency noise hits every config equally.  The random-access
section times N scattered 4 KB reads through `FrameReader.read_range`
(decodes only covering blocks, LRU off to keep it honest) against decoding
the whole frame per read and slicing.

JSON lands in experiments/benchmarks/decode_parallel.json and is mirrored
to BENCH_decode_parallel.json at the repo root.
"""
from __future__ import annotations

import json
import os
import time

import numpy as np

from repro.core import (
    FrameReader,
    LZ4DecodeEngine,
    LZ4Engine,
    decode_frame_serial,
)
from repro.core.lz4_types import MAX_BLOCK

if __package__ in (None, ""):        # `python benchmarks/decode_parallel.py`
    import sys

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from common import dump_telemetry, save_json
else:
    from .common import dump_telemetry, save_json


def _corpus(n_blocks: int) -> bytes:
    from repro.core import corpus_blocks

    full = [b for b in corpus_blocks() if len(b) == MAX_BLOCK]
    reps = -(-n_blocks // len(full))
    return b"".join((full * reps)[:n_blocks])


def _process_available() -> bool:
    import multiprocessing as mp

    return "fork" in mp.get_all_start_methods()


def run(fast: bool = True, chaos_seed: int | None = None) -> dict:
    n_blocks = 16 if fast else 64
    rounds = 3 if fast else 5
    data = _corpus(n_blocks)
    frame = LZ4Engine(micro_batch=32).compress(data)

    configs: dict[str, object] = {
        "serial_chunked": lambda: decode_frame_serial(frame),
        "engine_inline": None,  # filled below with engine instances
    }
    engines = {
        "engine_inline": LZ4DecodeEngine(),
        "engine_inline_planned": LZ4DecodeEngine(two_phase=True),
    }
    for w in (2, 4):
        engines[f"engine_thread_w{w}"] = LZ4DecodeEngine(workers=w,
                                                         executor="thread")
    if _process_available():
        for w in (2, 4):
            engines[f"engine_process_w{w}"] = LZ4DecodeEngine(
                workers=w, executor="process")
    engines["engine_device"] = LZ4DecodeEngine(executor="device")
    engines["engine_device_static"] = LZ4DecodeEngine(
        executor="device", adaptive_rounds=False)
    engines["engine_device_specplan"] = LZ4DecodeEngine(
        executor="device", plan_on_device=True)
    for name, eng in engines.items():
        configs[name] = (lambda e: lambda: e.decode(frame))(eng)

    # Correctness gate before any timing.
    for name, fn in configs.items():
        assert fn() == data, f"{name} round-trip failed"

    best = {name: float("inf") for name in configs}
    for _ in range(rounds):  # interleaved: every config sees the same noise
        for name, fn in configs.items():
            t0 = time.perf_counter()
            fn()
            best[name] = min(best[name], time.perf_counter() - t0)

    # Bytewise oracle: far slower; one timed rep is plenty.
    t0 = time.perf_counter()
    assert decode_frame_serial(frame, bytewise=True) == data
    bytewise_s = time.perf_counter() - t0

    serial_s = best["serial_chunked"]
    out = {
        "corpus_blocks": n_blocks,
        "block_kb": 64,
        "frame_bytes": len(frame),
        "data_bytes": len(data),
        "serial_bytewise_ms": round(bytewise_s * 1000, 1),
        "configs": {},
    }
    for name, dt in best.items():
        out["configs"][name] = {
            "ms": round(dt * 1000, 1),
            "mbps": round(len(data) / dt / 1e6, 2),
            "speedup_vs_serial": round(serial_s / dt, 3),
        }
    parallel = [v["speedup_vs_serial"] for k, v in out["configs"].items()
                if k.startswith("engine_") and k != "engine_inline"]
    out["best_parallel_speedup"] = max(parallel) if parallel else None
    out["engine_inline_speedup"] = out["configs"]["engine_inline"][
        "speedup_vs_serial"]

    # -- device executor: transfer accounting + restore path ----------------
    dev = engines["engine_device"]
    assert dev.decode(frame) == data
    dev_stats = dev.stats
    t0 = time.perf_counter()
    arr = dev.decode_to_device(frame, verify=False)
    arr.block_until_ready()
    to_device_s = time.perf_counter() - t0
    assert dev.stats.host_bytes == 0, "decode_to_device(verify=False) fetched"
    out["device"] = {
        "ms": out["configs"]["engine_device"]["ms"],
        "mbps": out["configs"]["engine_device"]["mbps"],
        "speedup_vs_serial":
            out["configs"]["engine_device"]["speedup_vs_serial"],
        "static_rounds_ms": out["configs"]["engine_device_static"]["ms"],
        "dispatches": dev_stats.dispatches,
        "device_blocks": dev_stats.device_blocks,
        "fallback_blocks": dev_stats.fallback_blocks,
        "host_bytes": dev_stats.host_bytes,          # == decoded payload
        "to_device_ms": round(to_device_s * 1000, 1),
        "to_device_host_bytes": 0,                   # asserted above
    }

    # -- speculative in-graph planning: the retired host O(n) stage ---------
    # Time plan_block_fast (the serial token-stream walk the speculative
    # planner replaces) over every compressed payload, then put the fused
    # specplan engine's ledger next to it: same decode, zero host planning.
    from repro.core.decode_plan import plan_block_fast
    from repro.core.frame import frame_info

    info = frame_info(frame)
    payloads = [frame[b["offset"]: b["offset"] + b["csize"]]
                for b in info["blocks"] if not b["raw"]]
    host_plan_s = float("inf")
    for _ in range(rounds):
        t0 = time.perf_counter()
        for p in payloads:
            plan_block_fast(p)
        host_plan_s = min(host_plan_s, time.perf_counter() - t0)

    spec = engines["engine_device_specplan"]
    assert spec.decode(frame) == data
    spec_stats = spec.stats
    assert spec_stats.fallback_blocks == 0, "specplan fell back on corpus"
    t0 = time.perf_counter()
    arr = spec.decode_to_device(frame, verify=False)
    arr.block_until_ready()
    spec_to_device_s = time.perf_counter() - t0
    assert spec.stats.host_bytes == 0, \
        "specplan decode_to_device touched host bytes (planning leaked?)"
    out["plan_stage"] = {
        "compressed_blocks": len(payloads),
        "host_plan_ms": round(host_plan_s * 1000, 1),     # the retired stage
        "specplan_ms": out["configs"]["engine_device_specplan"]["ms"],
        "specplan_mbps": out["configs"]["engine_device_specplan"]["mbps"],
        "dispatches": spec_stats.dispatches,
        "device_blocks": spec_stats.device_blocks,
        "fallback_blocks": spec_stats.fallback_blocks,     # asserted 0
        "host_bytes": spec_stats.host_bytes,               # == decoded payload
        "to_device_ms": round(spec_to_device_s * 1000, 1),
        "to_device_host_bytes": 0,                         # asserted above
    }

    # -- random access: read_range vs full-decode-then-slice ----------------
    rng = np.random.default_rng(0)
    n_reads, read_len = 32, 4096
    offsets = [int(rng.integers(0, len(data) - read_len)) for _ in range(n_reads)]
    reader = FrameReader(frame, cache_blocks=0)
    for off in offsets[:4]:
        assert reader.read_range(off, read_len) == data[off: off + read_len]

    t0 = time.perf_counter()
    for off in offsets:
        reader.read_range(off, read_len)
    ranged_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    for off in offsets[: max(2, n_reads // 8)]:  # full decode per read is slow
        decode_frame_serial(frame)[off: off + read_len]
    full_s = (time.perf_counter() - t0) / max(2, n_reads // 8) * n_reads
    out["random_access"] = {
        "reads": n_reads,
        "read_bytes": read_len,
        "read_range_ms_per_read": round(ranged_s / n_reads * 1000, 3),
        "full_decode_ms_per_read": round(full_s / n_reads * 1000, 3),
        "speedup": round(full_s / ranged_s, 1),
    }

    # -- optional chaos leg: salvage the same frame after seeded damage -----
    # One corrupt block, no parity (this corpus frame is v3): salvage must
    # recover every OTHER block and account the loss — never silently.
    if chaos_seed is not None:
        from repro.core.frame import frame_info as _fi
        from repro.resilience.inject import corrupt_frame_block
        from repro.resilience.salvage import salvage_frame

        n = _fi(frame)["block_count"]
        victim = chaos_seed % n
        bad = corrupt_frame_block(frame, victim, seed=chaos_seed, n=3)
        t0 = time.perf_counter()
        rep = salvage_frame(bad, engines["engine_inline"])
        salvage_s = time.perf_counter() - t0
        assert rep.lost == [victim], f"chaos: lost {rep.lost} != [{victim}]"
        assert len(rep.ok) == n - 1, "chaos: an undamaged block was lost"
        assert len(rep.data) == len(data)
        out["chaos"] = {
            "seed": chaos_seed,
            "damaged_block": victim,
            "recovered_blocks": len(rep.ok),
            "lost_blocks": len(rep.lost),
            "salvage_ms": round(salvage_s * 1000, 1),
        }

    for eng in engines.values():
        eng.close()
    save_json("decode_parallel", out)
    root = os.path.join(os.path.dirname(__file__), "..",
                        "BENCH_decode_parallel.json")
    with open(root, "w") as f:
        json.dump(out, f, indent=1)
    # With REPRO_OBS=1: export the read-path trace/metrics bundle
    # (plan/execute/verify spans across every executor) for
    # tools/trace_report.py; no-op otherwise.
    dump_telemetry("decode_parallel")
    return out


if __name__ == "__main__":
    from repro import compile_cache

    compile_cache.enable()
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--chaos", type=int, default=None, metavar="SEED",
                    help="also run a seeded-corruption salvage leg "
                         "(repro.resilience.inject) and record its ledger")
    args = ap.parse_args()
    print(json.dumps(run(fast=not args.full, chaos_seed=args.chaos),
                     indent=1))
