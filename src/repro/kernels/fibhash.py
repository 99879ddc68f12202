"""Pallas TPU kernel: PWS-way Fibonacci hash (paper Fig. 5 "Hash Calculation").

The FPGA uses 4 DSP48 slices per multiplier; the TPU-native mapping is the
VPU's elementwise int32 multiply over (8,128) vregs — every position's hash is
computed in the same "cycle" (fully data-parallel), which is exactly the
feedforward property the paper engineers for.

Tiling: positions are tiled into VMEM blocks of TILE elements (lane-aligned,
multiple of 1024).  The four shifted byte streams are separate inputs so the
kernel body is pure elementwise ops — no gathers, no cross-lane traffic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.lz4_types import HASH_PRIME

from .backend import interpret_mode

TILE = 2048  # positions per grid step; 8 vregs of int32


def _fibhash_kernel(b0_ref, b1_ref, b2_ref, b3_ref, w_ref, h_ref, *, hash_bits: int):
    w = (
        b0_ref[...].astype(jnp.uint32)
        | (b1_ref[...].astype(jnp.uint32) << 8)
        | (b2_ref[...].astype(jnp.uint32) << 16)
        | (b3_ref[...].astype(jnp.uint32) << 24)
    )
    h = (w * jnp.uint32(HASH_PRIME)) >> jnp.uint32(32 - hash_bits)
    w_ref[...] = w.astype(jnp.int32)
    h_ref[...] = h.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("hash_bits", "interpret"))
def fibhash_pallas(b0, b1, b2, b3, hash_bits: int = 8,
                   interpret: bool | None = None):
    """(P,) int32 shifted byte streams -> (word_i32, hash_i32), P % TILE == 0.

    ``interpret=None`` resolves from the backend (`backend.interpret_mode`).
    """
    if interpret is None:
        interpret = interpret_mode("fibhash")
    P = b0.shape[0]
    assert P % TILE == 0, f"P={P} must be a multiple of {TILE}"
    grid = (P // TILE,)
    spec = pl.BlockSpec((TILE,), lambda i: (i,))
    return pl.pallas_call(
        functools.partial(_fibhash_kernel, hash_bits=hash_bits),
        grid=grid,
        in_specs=[spec, spec, spec, spec],
        out_specs=[spec, spec],
        out_shape=[
            jax.ShapeDtypeStruct((P,), jnp.int32),
            jax.ShapeDtypeStruct((P,), jnp.int32),
        ],
        interpret=interpret,
    )(b0, b1, b2, b3)
