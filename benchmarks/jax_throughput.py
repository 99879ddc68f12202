"""Measured throughput of the JAX engine (beyond-paper): CPU wall-clock here,
plus the TPU v5e roofline projection derived from the engine's per-byte
data movement (the engine is memory-bound; see EXPERIMENTS.md §Roofline).

Variants measured: scan_impl sequential vs associative (the beyond-paper
parallel selection) at the kernel level, plus the end-to-end batched
LZ4Engine pipeline (micro-batched dispatch + vectorized emission + framing).
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core import LZ4Engine
from repro.core.jax_compressor import compress_block_records, pad_block
from repro.core.lz4_types import MAX_BLOCK

from .common import save_json, timed

# Per input byte, the engine moves (roofline accounting, bf16/int32 in VMEM/HBM):
#   hash+word build ~ 8 B, sort (log passes over 4B keys) ~ 16 B amortized,
#   candidate/valid masks ~ 12 B, bounded extend gather 2*32 B, scan tables ~ 5 B
_BYTES_PER_BYTE = 8 + 16 + 12 + 64 + 5
_V5E_HBM = 819e9


def run(fast: bool = True) -> dict:
    rng = np.random.default_rng(0)
    data = rng.integers(0, 48, MAX_BLOCK, dtype=np.uint8).tobytes()
    buf, n = pad_block(data)
    buf_j = jnp.asarray(buf)
    n_j = jnp.int32(n)

    out = {"block_kb": 64}
    for impl in ("sequential", "associative"):
        _, dt = timed(
            lambda: compress_block_records(buf_j, n_j, scan_impl=impl).size.block_until_ready(),
            repeat=3,
        )
        out[f"cpu_mbps_{impl}"] = round(MAX_BLOCK / dt / 1e6, 2)

    # End-to-end batched pipeline: micro-batched dispatch, vectorized
    # emission, frame output (and the round trip is free to check here).
    nb = 4 if fast else 16
    batch_data = data * nb
    eng = LZ4Engine(micro_batch=nb, scan_impl="associative")
    _, dt = timed(lambda: eng.compress(batch_data), repeat=3)
    out["cpu_mbps_batch"] = round(len(batch_data) / dt / 1e6, 2)
    out["engine_dispatches"] = eng.stats.dispatches
    out["tpu_v5e_roofline_gbps_per_chip"] = round(8 * _V5E_HBM / _BYTES_PER_BYTE / 1e9, 1)
    out["paper_fpga_gbps"] = 16.10
    save_json("jax_throughput", out)
    return out


if __name__ == "__main__":
    from repro import compile_cache

    compile_cache.enable()
    import json

    print(json.dumps(run(), indent=1))
