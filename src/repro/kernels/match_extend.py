"""Pallas TPU kernel: bounded extended-match (paper Section III-B, Fig. 4b).

This is the S2 datapath: because the match length is capped at `max_match`,
the whole extension is a *fixed-depth* compare tree — no feedback loop.  On
the FPGA that means pipeline registers can be inserted freely; on the TPU it
means the loop fully unrolls into `max_match - 4` vectorized compare/accumulate
steps over VMEM-resident data with a static schedule.

Memory layout:
  * The entire 64 KB block lives in VMEM as int32 (256 KB) — the exact
    analogue of the paper's on-chip input buffer ("compatible with the L1
    cache", Section IV-A).  Every grid step sees the whole block (BlockSpec
    maps all tiles to block 0) while candidate indices/outputs are tiled.
  * `block[p + 4 + j]` for a position tile is a *static* slice (p = base +
    iota), a contiguous `lax.dynamic_slice` at the tile base — no gather.
  * `block[cand + 4 + j]` is a genuine data-dependent read: candidates point
    anywhere earlier in the block.  It is expressed as a 1-D `jnp.take`.
    This read is the paper's "data memory" port in Fig. 5 — one read per
    position per j, exactly PWS x (L_max-4) byte-compares per window, same
    as the hardware.

The TPU compiler refuses this kernel (the unaligned slice and the 1-D
gather; backend.TPU_REFUSED), so it runs only in the interpreter, off the
TPU, where the tests check it against its jnp twin.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.lz4_types import LAST_LITERALS, MIN_MATCH

from .backend import interpret_mode

TILE = 2048


def _match_extend_kernel(
    n_ref, block_ref, cand_ref, valid_ref, len_ref, *, max_match: int, tile: int
):
    i = pl.program_id(0)
    base = i * tile
    n = n_ref[0]
    blk = block_ref[...]
    B = blk.shape[0]
    cand = cand_ref[...]
    p = base + jax.lax.iota(jnp.int32, tile)
    max_extra = jnp.clip(
        n - LAST_LITERALS - (p + MIN_MATCH), 0, max_match - MIN_MATCH
    )
    prefix = jnp.ones((tile,), dtype=jnp.bool_)
    length = jnp.zeros((tile,), dtype=jnp.int32)
    for j in range(max_match - MIN_MATCH):
        # Static-offset slice of the block for the current positions...
        cur = jax.lax.dynamic_slice(blk, (base + MIN_MATCH + j,), (tile,))
        # ...and a data-dependent gather for the candidates.
        cnd = jnp.take(blk, jnp.clip(cand + MIN_MATCH + j, 0, B - 1), axis=0)
        prefix = prefix & (cur == cnd) & (j < max_extra)
        length = length + prefix.astype(jnp.int32)
    len_ref[...] = jnp.where(valid_ref[...], MIN_MATCH + length, 0)


@functools.partial(jax.jit, static_argnames=("max_match", "interpret"))
def match_extend_pallas(block, cand, valid, n, max_match: int = 36,
                        interpret: bool | None = None):
    """Bounded match lengths for every position.

    block : (B,) int32, B >= P + max_match (padded); the full on-chip buffer
    cand  : (P,) int32 candidate positions, P % TILE == 0
    valid : (P,) bool
    n     : (1,) int32 true length
    interpret : None resolves from the backend (`backend.interpret_mode`)
    """
    if interpret is None:
        interpret = interpret_mode("match_extend")
    P = cand.shape[0]
    B = block.shape[0]
    assert P % TILE == 0, f"P={P} must be a multiple of {TILE}"
    assert B >= P + max_match, "block must be padded past the last position"
    grid = (P // TILE,)
    return pl.pallas_call(
        functools.partial(_match_extend_kernel, max_match=max_match, tile=TILE),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1,), lambda i: (0,)),          # n: scalar-as-(1,)
            pl.BlockSpec((B,), lambda i: (0,)),          # full block each step
            pl.BlockSpec((TILE,), lambda i: (i,)),       # candidates: tiled
            pl.BlockSpec((TILE,), lambda i: (i,)),       # valid: tiled
        ],
        out_specs=pl.BlockSpec((TILE,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((P,), jnp.int32),
        interpret=interpret,
    )(n, block, cand, valid)
