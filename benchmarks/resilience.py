"""Resilience benchmark: frame-v6 parity write overhead + salvage throughput.

Measures, on a multi-block corpus frame (round-trip verified):

  * parity write overhead — `LZ4Engine(parity_group=G)` for G in {2, 4, 8}
    vs the parity-off baseline: frame size overhead (one XOR parity block
    per G-block group) and compress-time overhead.  Asserts the
    parity-off frame is BYTE-IDENTICAL to the plain engine's (the parity
    feature costs nothing when off);
  * salvage throughput — `salvage_frame` over a seeded-corrupted v6 frame
    (one damaged block per parity group: worst case that still
    reconstructs fully) across the serial / thread / process / device
    executors, MB/s of recovered output.  Every pass must come back
    ``complete`` with ``data`` byte-identical to the original — the
    benchmark doubles as an acceptance check;
  * strict-decode comparison — the undamaged strict decode time next to
    the salvage pass, so the overhead of the recovery path is visible.

``--chaos SEED`` re-seeds every injected corruption (block choice + bit
flips) from one integer — the CI chaos legs sweep a fixed seed matrix and
pin the salvage/reconstruction accounting.  ``--full`` grows the corpus.

JSON lands in experiments/benchmarks/resilience.json and is mirrored to
BENCH_resilience.json at the repo root.
"""
from __future__ import annotations

import json
import os
import time

from repro.core import LZ4DecodeEngine, LZ4Engine, frame_info
from repro.core.lz4_types import MAX_BLOCK
from repro.resilience.inject import corrupt_frame_block
from repro.resilience.salvage import salvage_frame

if __package__ in (None, ""):        # `python benchmarks/resilience.py`
    import sys

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from common import dump_telemetry, save_json
else:
    from .common import dump_telemetry, save_json

PARITY_GROUPS = [2, 4, 8]


def _corpus(n_blocks: int) -> bytes:
    from repro.core import corpus_blocks

    full = [b for b in corpus_blocks() if len(b) == MAX_BLOCK]
    reps = -(-n_blocks // len(full))
    return b"".join((full * reps)[:n_blocks])


def _process_available() -> bool:
    import multiprocessing as mp

    return "fork" in mp.get_all_start_methods()


def _timed_best(fn, rounds: int) -> float:
    fn()  # warmup / jit
    best = float("inf")
    for _ in range(rounds):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def run(fast: bool = True, chaos_seed: int = 0) -> dict:
    n_blocks = 16 if fast else 64
    rounds = 3 if fast else 5
    data = _corpus(n_blocks)

    # -- parity write overhead ---------------------------------------------
    base_engine = LZ4Engine(micro_batch=32)
    base_frame = base_engine.compress(data)
    # Parity off is free: byte-identical to the plain engine's frame.
    assert LZ4Engine(micro_batch=32, parity_group=None).compress(data) \
        == base_frame, "parity_group=None changed the frame bytes"
    base_s = _timed_best(lambda: base_engine.compress(data), rounds)

    out = {
        "corpus_blocks": n_blocks,
        "block_kb": 64,
        "data_bytes": len(data),
        "chaos_seed": chaos_seed,
        "parity_off": {
            "frame_bytes": len(base_frame),
            "compress_ms": round(base_s * 1000, 1),
            "byte_identical_to_plain_engine": True,  # asserted above
        },
        "parity": {},
        "salvage": {},
    }
    for g in PARITY_GROUPS:
        eng = LZ4Engine(micro_batch=32, parity_group=g)
        frame = eng.compress(data)
        dt = _timed_best(lambda e=eng: e.compress(data), rounds)
        info = frame_info(frame)
        out["parity"][f"group_{g}"] = {
            "frame_bytes": len(frame),
            "size_overhead_pct": round(
                (len(frame) - len(base_frame)) / len(base_frame) * 100, 2),
            "parity_blocks": len(info["parity"]),
            "compress_ms": round(dt * 1000, 1),
            "time_overhead_pct": round((dt - base_s) / base_s * 100, 1),
        }

    # -- salvage throughput across executors --------------------------------
    # Worst recoverable case: ONE damaged block in EVERY parity group, so
    # the pass decodes all survivors and reconstructs a block per group.
    g = 4
    v6 = LZ4Engine(micro_batch=32, parity_group=g).compress(data)
    info = frame_info(v6)
    n = info["block_count"]
    bad = v6
    victims = []
    for grp in range(-(-n // g)):
        victim = grp * g + (chaos_seed + grp) % min(g, n - grp * g)
        victims.append(victim)
        bad = corrupt_frame_block(bad, victim, seed=chaos_seed + grp, n=3)

    engines = {"serial": LZ4DecodeEngine(executor="serial"),
               "thread_w4": LZ4DecodeEngine(executor="thread", workers=4)}
    if _process_available():
        engines["process_w4"] = LZ4DecodeEngine(executor="process", workers=4)
    engines["device"] = LZ4DecodeEngine(executor="device")

    strict_s = _timed_best(lambda: engines["serial"].decode(v6), rounds)
    out["strict_decode_ms"] = round(strict_s * 1000, 1)
    for name, eng in engines.items():
        rep = salvage_frame(bad, eng)
        # Acceptance, not just timing: full recovery, byte-identical.
        assert rep.complete, f"{name}: salvage lost blocks {rep.lost}"
        assert sorted(rep.reconstructed) == sorted(victims), \
            f"{name}: reconstructed {rep.reconstructed} != {victims}"
        assert rep.data == data, f"{name}: salvage output differs"
        assert rep.content_crc_ok, f"{name}: content CRC did not re-verify"
        dt = _timed_best(lambda e=eng: salvage_frame(bad, e), rounds)
        out["salvage"][name] = {
            "ms": round(dt * 1000, 1),
            "mbps": round(len(data) / dt / 1e6, 2),
            "vs_strict_decode_x": round(dt / strict_s, 2),
            "reconstructed_blocks": len(rep.reconstructed),
        }

    # -- no-parity loss accounting (the chaos ledger CI pins) ---------------
    bad_v3 = corrupt_frame_block(base_frame, chaos_seed % n, n=3,
                                 seed=chaos_seed)
    rep = salvage_frame(bad_v3, engines["serial"])
    assert rep.lost == [chaos_seed % n] and not rep.reconstructed
    assert len(rep.ok) == n - 1, "salvage missed an undamaged block"
    out["no_parity_salvage"] = {
        "lost_blocks": len(rep.lost),
        "recovered_blocks": len(rep.ok),
        "hole_bytes": sum(e - s for s, e in rep.holes),
    }

    for eng in engines.values():
        eng.close()
    save_json("resilience", out)
    root = os.path.join(os.path.dirname(__file__), "..",
                        "BENCH_resilience.json")
    with open(root, "w") as f:
        json.dump(out, f, indent=1)
    dump_telemetry("resilience")
    return out


if __name__ == "__main__":
    from repro import compile_cache

    compile_cache.enable()
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--chaos", type=int, default=0, metavar="SEED",
                    help="seed for every injected corruption (CI sweeps a "
                         "fixed matrix of these)")
    args = ap.parse_args()
    print(json.dumps(run(fast=not args.full, chaos_seed=args.chaos),
                     indent=1))
