"""Pallas TPU kernel: device-side block decode (pointer-doubling resolve).

The read-path twin of emit_scatter.py.  The decode engine's host planner
turns a block's token stream into per-output-byte immediate-source maps
(`kernels/ops.py` `decode_gather` builds them in XLA from the fixed-shape
`DevicePlan` arrays); this kernel resolves the transitive sources and
materializes the bytes:

    for each of `rounds` rounds:  ptr = ptr[ptr]      (pointer doubling)
    out[k] = block[lit_blk[ptr[k]]]                   (one final gather)

Doubling is a GLOBAL fixpoint iteration — round r reads positions written
conceptually by round r-1 at arbitrary indices — so the pointer table stays
fully VMEM-resident (256 KB at the 64 KB block size, the paper's on-chip
buffer scale) and the grid is a single step; parallelism comes from the
vmapped block axis of the micro-batch, not from tiling within a block.
`rounds` is static: the decode engine compiles one variant per power-of-two
depth bucket, worst case ceil(log2(MAX_BLOCK)) = 16, so even pathological
RLE chains (depth 65535) resolve with no data-dependent control flow and no
host fallback.

The gathers are 1-D `jnp.take`, which the TPU compiler refuses
(backend.TPU_REFUSED): the kernel runs only in the interpreter, off the
TPU.  The byte math is
intentionally duplicated from kernels/ref.py `decode_gather_ref` (the jnp
oracle): the two paths stay independent and are asserted bit-identical in
tests/test_device_decode.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .backend import interpret_mode


def _decode_wave_kernel(total_ref, blk_ref, lit_blk_ref, ptr_ref, out_ref, *,
                        rounds):
    k = jax.lax.iota(jnp.int32, out_ref.shape[0])
    p = ptr_ref[...]
    for _ in range(rounds):
        p = jnp.take(p, p)
    b = jnp.take(blk_ref[...], jnp.take(lit_blk_ref[...], p))
    out_ref[...] = jnp.where(k < total_ref[0], b, 0)


@functools.partial(jax.jit, static_argnames=("rounds", "interpret"))
def decode_wave_pallas(block, lit_blk, ptr, total, rounds: int,
                       interpret: bool | None = None):
    """Resolve + materialize one block's decoded bytes on device.

    block   : (B,) int32 compressed-payload byte values (zero-padded)
    lit_blk : (K,) int32 literal source index per output byte
    ptr     : (K,) int32 immediate source position per output byte
    total   : (1,) int32 decoded size; positions >= total emit 0
    rounds  : static pointer-doubling round count (resolves depth 2^rounds)

    Returns (K,) int32 byte values (cast to uint8 at the ops.py boundary —
    int32 lanes keep the kernel on the VPU's native element type).
    ``interpret=None`` resolves from the backend (`backend.interpret_mode`).
    """
    if interpret is None:
        interpret = interpret_mode("decode_wave")
    K = ptr.shape[0]
    B = block.shape[0]
    assert lit_blk.shape[0] == K, (lit_blk.shape, K)
    return pl.pallas_call(
        functools.partial(_decode_wave_kernel, rounds=rounds),
        grid=(1,),
        in_specs=[
            pl.BlockSpec((1,), lambda i: (0,)),   # total: scalar-as-(1,)
            pl.BlockSpec((B,), lambda i: (0,)),   # full compressed block
            pl.BlockSpec((K,), lambda i: (0,)),   # literal source map
            pl.BlockSpec((K,), lambda i: (0,)),   # immediate pointer map
        ],
        out_specs=pl.BlockSpec((K,), lambda i: (0,)),
        out_shape=jax.ShapeDtypeStruct((K,), jnp.int32),
        interpret=interpret,
    )(total, block, lit_blk, ptr)
