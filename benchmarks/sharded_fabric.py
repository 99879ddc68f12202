"""Sharded compression fabric: weak scaling across fake device counts.

The fabric (src/repro/distributed/fabric.py) claims the block stack can be
partitioned over a mesh with per-shard output bytes IDENTICAL to a
single-device engine on the same slice.  This benchmark validates both
halves of that claim on CPU:

  * **weak scaling** — each device count N in {1, 2, 4, 8} compresses a
    corpus of N x BLOCKS_PER_SHARD blocks through a mesh of N fake devices
    (``XLA_FLAGS=--xla_force_host_platform_device_count=N``, so each sweep
    point runs in a fresh subprocess: the flag must be set before jax
    imports).  Under weak scaling the per-shard work is constant, so ideal
    behaviour is flat wall time / linearly growing throughput.  On CPU the
    "devices" all share the host's cores, so the curve mostly measures
    dispatch overhead — the numbers are a correctness-shaped baseline for
    real multi-chip runs, same caveat as device_emit (EXPERIMENTS.md).
  * **byte identity** — every sweep point asserts the mesh-path frame equals
    the host-partition oracle's frame, each shard's subframe equals a
    single-device engine run on that shard's slice, the v4 container
    round-trips through the mesh decoder and the serial oracle, and
    `read_range` spans crossing shard boundaries return the right bytes.

The checks live in `fabric_check`, which runs in the calling process on
whatever devices the mesh holds; ``chip_smoke.py --chips 4`` calls it on
four TPU chips.

Writes experiments/benchmarks/sharded_fabric.json, mirrored to
BENCH_sharded_fabric.json at the repo root.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np

if __package__ in (None, ""):        # `python benchmarks/sharded_fabric.py`
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from common import dump_telemetry, save_json
else:
    from .common import dump_telemetry, save_json

DEVICE_COUNTS = (1, 2, 4, 8)
BLOCKS_PER_SHARD = 2
REPEAT = 2

def weak_scaling_data(n_blocks: int) -> bytes:
    """``n_blocks`` x 64 KB, each block 2/3 compressible structure and 1/3
    incompressible bytes (seeded)."""
    from repro.core.lz4_types import MAX_BLOCK

    rng = np.random.default_rng(7)
    parts = []
    for i in range(n_blocks):
        parts.append((b"weak scaling shard %d " % i) * (2 * MAX_BLOCK // 63))
        parts.append(rng.integers(0, 256, MAX_BLOCK // 3, np.uint8).tobytes())
    return b"".join(parts)[: n_blocks * MAX_BLOCK]


def fabric_check(mesh, data: bytes, repeat: int = 0) -> dict:
    """Compress ``data`` on ``mesh`` and check the fabric against its oracles.

    Runs in the calling process, on whatever devices ``mesh`` holds (fake CPU
    devices in the weak-scaling sweep, chips in ``chip_smoke.py --chips 4``).
    Checks, each a boolean in the result:

      * the mesh frame equals the host-partition oracle
        (``LZ4Engine(shards=S)``) byte for byte;
      * every `fabric.shard_subframe` equals a single-device engine's frame
        of that shard's slice;
      * ``LZ4DecodeEngine(mesh=mesh)`` and the serial oracle both decode the
        frame back to ``data``; a `read_range` across the first shard
        boundary returns the right bytes;
      * the sharded compress dispatch takes its operands and returns its
        results on ``S`` distinct devices (from the compiled program's
        input shardings and the result arrays' shards).

    ``first_compress_s`` is the first call (compilation included) and
    ``compress_s`` the best of ``repeat`` more (None when 0).
    """
    import jax
    import jax.numpy as jnp

    from repro.core import (FrameReader, LZ4DecodeEngine, LZ4Engine,
                            decode_frame_serial, frame_info)
    from repro.core.jax_compressor import _PAD
    from repro.core.lz4_types import MAX_BLOCK
    from repro.distributed import fabric

    eng = LZ4Engine(mesh=mesh)
    S = eng.shards
    t0 = time.perf_counter()
    frame = eng.compress(data)
    first = time.perf_counter() - t0
    warm = None
    for _ in range(repeat):
        t0 = time.perf_counter()
        frame = eng.compress(data)
        dt = time.perf_counter() - t0
        warm = dt if warm is None else min(warm, dt)
    dispatches = eng.stats.dispatches

    info = frame_info(frame)
    chunks = [data[i: i + MAX_BLOCK] for i in range(0, len(data), MAX_BLOCK)]
    single = LZ4Engine()
    per_shard_identical = all(
        fabric.shard_subframe(frame, sl.shard) == single.compress(
            b"".join(chunks[sl.start: sl.stop]))
        for sl in fabric.partition_blocks(len(chunks), S))
    dec = LZ4DecodeEngine(mesh=mesh)
    t0 = time.perf_counter()
    mesh_decode_ok = dec.decode(frame) == data
    decode_s = time.perf_counter() - t0
    b = fabric.partition_blocks(len(chunks), S)[0].stop * MAX_BLOCK
    cross_read_ok = (S == 1 or b >= len(data) or
                     FrameReader(frame).read_range(b - 64, 128)
                     == data[b - 64: b + 64])

    # The engine's own compiled dispatch (cached per config), run once more
    # on an empty stack to read where its operands and results live.
    fn = fabric._sharded_compress_compiled(
        eng.mesh, tuple(eng.shard_axes), eng.hash_bits, eng.max_match,
        eng.pws, eng.scan_impl)
    stack = np.zeros((S, MAX_BLOCK + _PAD), np.uint8)
    ns = np.zeros((S,), np.int32)
    in_shardings = fn.lower(stack, ns).compile().input_shardings[0]
    operand_devices = {d.id for sh in in_shardings for d in sh.device_set}
    res = jax.block_until_ready(fn(jnp.asarray(stack), jnp.asarray(ns)))
    result_devices = {s.device.id for r in res for s in r.addressable_shards}

    return {
        "devices": S,
        "platform": jax.devices()[0].platform,
        "blocks": len(chunks),
        "bytes_in": len(data),
        "frame_bytes": len(frame),
        "first_compress_s": first,
        "compress_s": warm,
        "decode_s": decode_s,
        "dispatches": dispatches,
        "operand_devices": sorted(operand_devices),
        "result_devices": sorted(result_devices),
        "frame_version_4": info["version"] == 4 and info["shard_count"] == S,
        "identical_to_host_oracle": frame == LZ4Engine(shards=S).compress(data),
        "per_shard_identical_to_single_device": per_shard_identical,
        "mesh_decode_ok": mesh_decode_ok,
        "serial_roundtrip_ok": decode_frame_serial(frame) == data,
        "cross_shard_read_range_ok": cross_read_ok,
        "spans_all_devices": (len(operand_devices) == S
                              and len(result_devices) == S),
    }


CHECKS = ("frame_version_4", "identical_to_host_oracle",
          "per_shard_identical_to_single_device", "mesh_decode_ok",
          "serial_roundtrip_ok", "cross_shard_read_range_ok",
          "spans_all_devices")


def _child() -> None:
    """One sweep point, in a fresh interpreter with N fake CPU devices."""
    import jax

    from repro.distributed.sharding import make_mesh

    devices = int(os.environ["FABRIC_BENCH_DEVICES"])
    assert len(jax.devices()) == devices
    data = weak_scaling_data(devices * int(os.environ["FABRIC_BENCH_BPS"]))
    pt = fabric_check(make_mesh((devices,), ("data",)), data,
                      repeat=int(os.environ["FABRIC_BENCH_REPEAT"]))
    pt["compress_mb_s"] = round(len(data) / pt["compress_s"] / 1e6, 3)
    print("RESULT:" + json.dumps(pt))


def _run_point(devices: int) -> dict:
    root = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
    env = dict(os.environ)
    env.update({
        "XLA_FLAGS": f"--xla_force_host_platform_device_count={devices}",
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": os.pathsep.join(
            [os.path.join(root, "src"), root,
             env.get("PYTHONPATH", "")]).rstrip(os.pathsep),
        "FABRIC_BENCH_DEVICES": str(devices),
        "FABRIC_BENCH_BPS": str(BLOCKS_PER_SHARD),
        "FABRIC_BENCH_REPEAT": str(REPEAT),
    })
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.sharded_fabric", "--child"],
        env=env, cwd=root, capture_output=True, text=True, timeout=900)
    if proc.returncode != 0:
        raise RuntimeError(
            f"fabric bench child (devices={devices}) failed:\n"
            + proc.stderr[-3000:])
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT:")][0]
    return json.loads(line[len("RESULT:"):])


def run() -> dict:
    points = []
    for devices in DEVICE_COUNTS:
        pt = _run_point(devices)
        for check in CHECKS:
            assert pt[check], f"devices={devices}: {check} failed"
        points.append(pt)
        print(f"[sharded_fabric] devices={devices} "
              f"blocks={pt['blocks']} {pt['compress_mb_s']} MB/s "
              f"({pt['dispatches']} dispatches)", flush=True)

    base = points[0]
    out = {
        "config": {
            "device_counts": list(DEVICE_COUNTS),
            "blocks_per_shard": BLOCKS_PER_SHARD,
            "repeat": REPEAT,
            "note": "fake CPU devices share the host's cores: the scaling "
                    "column measures dispatch overhead, the identity "
                    "columns are the real acceptance surface",
        },
        "weak_scaling": points,
        "summary": {
            "throughput_x_1_to_8": round(
                points[-1]["compress_mb_s"] / base["compress_mb_s"], 2),
            "all_frames_byte_identical_to_oracle": True,
            "all_per_shard_identical_to_single_device": True,
        },
    }
    save_json("sharded_fabric", out)
    root = os.path.join(os.path.dirname(__file__), "..",
                        "BENCH_sharded_fabric.json")
    with open(root, "w") as f:
        json.dump(out, f, indent=1)
    # With REPRO_OBS=1 the parent process has no spans of its own (the work
    # runs in the sweep children) but the bundle still records the registry
    # state for trace_report's schema check.
    dump_telemetry("sharded_fabric")
    return out


if __name__ == "__main__":
    from repro import compile_cache

    compile_cache.enable()
    if sys.argv[1:] == ["--child"]:
        _child()
    else:
        run()
