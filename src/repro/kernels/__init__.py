"""Pallas TPU kernels for the paper's compute hot-spots.

Kernels: fused_compress.py (the single-pass hash -> LVT candidate ->
bounded-match datapath of paper Fig. 5, VMEM-resident table, grid-
sequential window ordering — `candidate_impl="fused"`), fibhash.py (word
build + Fibonacci hash), match_extend.py (bounded S2 match extension) —
the two stages the fused kernel subsumes, kept as the staged path —
emit_scatter.py (device-side byte emission — the write path's last stage,
so compressed bytes never round-trip through host NumPy), decode_wave.py
(device-side plan execution — pointer-doubling source resolve + byte
gather, the read path's mirror of emit_scatter).  ops.py additionally
carries `crc32_bytes`, the in-graph CRC-32 (GF(2) matmuls over bit
chunks) that keeps verified device restores free of content fetches.

Layout per kernel: <name>.py (pl.pallas_call + BlockSpec), ops.py (jit'd
dispatch wrappers), ref.py (pure-jnp oracles).  backend.py resolves each
kernel's execution mode from the JAX backend: interpreted off the TPU (how
the CPU tests validate them), compiled on a TPU.  For TPU v5e only fibhash
compiles; the other five read at data-dependent indices with 1-D gathers,
which Mosaic refuses, so with ``use_pallas=True`` they raise
`backend.PallasUnsupportedError` there (tests/test_tpu_compile.py).
"""
