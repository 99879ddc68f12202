"""Compress any file with the batched LZ4Engine and verify the round trip.

  PYTHONPATH=src python examples/compress_file.py [path] [--entries 256] [--micro-batch 32]

Without a path, compresses the built-in corpus and prints per-file ratios
(the paper's Table III setting: combined scheme, 64 KB blocks).  Output is a
self-describing frame; the round trip goes through `decode_frame` with no
out-of-band lengths.
"""
import argparse
import time

from repro.core import LZ4Engine, corpus_files, decode_frame


def compress_report(engine: LZ4Engine, name: str, data: bytes):
    t0 = time.perf_counter()
    frame = engine.compress(data)
    dt = time.perf_counter() - t0
    restored = decode_frame(frame)
    assert restored == data, f"round-trip failed for {name}!"
    s = engine.stats
    print(f"{name:>10}: {len(data):>8} -> {len(frame):>8} bytes "
          f"(ratio {len(data)/max(len(frame), 1):5.3f}) {len(data)/dt/1e6:6.2f} MB/s "
          f"[{s.blocks} blocks / {s.dispatches} dispatches"
          f"{f', {s.raw_blocks} raw' if s.raw_blocks else ''}]  round-trip OK")


if __name__ == "__main__":
    from repro import compile_cache

    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("path", nargs="?")
    ap.add_argument("--entries", type=int, default=256)
    ap.add_argument("--micro-batch", type=int, default=32)
    args = ap.parse_args()
    engine = LZ4Engine(hash_bits=args.entries.bit_length() - 1,
                       micro_batch=args.micro_batch)
    if args.path:
        with open(args.path, "rb") as f:
            data = f.read()
        compress_report(engine, args.path, data)
    else:
        for name, data in corpus_files().items():
            compress_report(engine, name, data)
