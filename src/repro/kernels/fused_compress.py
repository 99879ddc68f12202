"""Pallas TPU kernel: the fused single-pass compression datapath.

This is the whole per-window pipeline of paper Fig. 5 — Word Shift + Hash
Calculation, the Last-Value Table (LVT) candidate lookup, Match Searching,
and the bounded Extended Match (S2) — as ONE kernel over on-chip memory.
Before this kernel the stages ran as separate XLA/Pallas dispatches with
HBM round trips between them, and candidate resolution materialized either
a full 64K-element sort (`candidate_impl="sort"`) or a windows x entries
grid (`"scatter"`); here the LVT is what it is in the hardware: a
2^hash_bits-entry table that LIVES in VMEM and is written/read in window
order.

Dataflow per grid step (one tile of TILE positions):

  1. hash      — the four shifted byte streams are static slices of the
                 VMEM-resident block; word build + Fibonacci hash are pure
                 VPU elementwise ops (fibhash.py's math, inlined).
  2. LVT       — intra-tile: scatter-max positions into a (TILE/pws,
                 2^hash_bits) grid and exclusive-cummax along the window
                 axis (log-depth, the paper's read-before-write port
                 ordering); cross-tile: gather the persistent VMEM table.
                 `cand(p) = max{q : hash(q)=hash(p), win(q) < win(p)}`,
                 exactly `_candidates` — and NO SORT ANYWHERE.
  3. update    — the table absorbs the tile's per-bucket maxima (one
                 vector max), so later tiles see every earlier window's
                 entry: the grid is SEQUENTIAL over tiles, which is the
                 hardware's table write/read ordering made explicit.
  4. match     — rebuild the candidate's word with four gathers (the
                 paper's "data memory" port) and compare; then the bounded
                 `max_match` compare tree from match_extend.py runs on the
                 still-resident block.

The LVT persists across grid steps as a revisited output block (constant
index map — the standard Pallas accumulator pattern, initialized at step
0), so one `pallas_call` covers all 32 tiles of a 64 KB block with zero
intermediate HBM materializations; under vmap each block of a micro-batch
gets its own table.  The data-dependent reads are 1-D `jnp.take`, and the
per-tile block reads are unaligned slices; the TPU compiler refuses both
(backend.TPU_REFUSED), so the kernel runs only in the interpreter, off the
TPU.

The jnp twin is `ref.fused_ref` (whole-block scatter formulation, pinned
bit-identical to the `_candidates` sort oracle at the record level);
tests/test_fused_compress.py asserts kernel == twin elementwise and
kernel == sort oracle end to end.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.lz4_types import (
    HASH_PRIME,
    LAST_LITERALS,
    MF_LIMIT,
    MIN_MATCH,
)

from .backend import interpret_mode

TILE = 2048  # positions per grid step (matches fibhash/match_extend tiling)


def _fused_kernel(n_ref, block_ref, cand_ref, len_ref, lvt_ref, *,
                  hash_bits: int, pws: int, max_match: int, tile: int):
    i = pl.program_id(0)
    base = i * tile
    E = 1 << hash_bits
    wins = tile // pws

    # The LVT is a revisited output: every grid step maps to the same
    # (E,) block, so writes from tile i are visible to tile i+1.
    @pl.when(i == 0)
    def _init():
        lvt_ref[...] = jnp.zeros((E,), jnp.int32)

    n = n_ref[0]
    blk = block_ref[...]
    B = blk.shape[0]
    rel = jax.lax.iota(jnp.int32, tile)
    p = base + rel

    # -- 1. word shift + Fibonacci hash (static slices, elementwise) --------
    b0 = jax.lax.dynamic_slice(blk, (base,), (tile,)).astype(jnp.uint32)
    b1 = jax.lax.dynamic_slice(blk, (base + 1,), (tile,)).astype(jnp.uint32)
    b2 = jax.lax.dynamic_slice(blk, (base + 2,), (tile,)).astype(jnp.uint32)
    b3 = jax.lax.dynamic_slice(blk, (base + 3,), (tile,)).astype(jnp.uint32)
    w = b0 | (b1 << 8) | (b2 << 16) | (b3 << 24)
    h = ((w * jnp.uint32(HASH_PRIME)) >> jnp.uint32(32 - hash_bits)).astype(jnp.int32)

    valid_pos = p <= n - MIN_MATCH

    # -- 2. LVT candidate: intra-tile grid + cross-tile table ---------------
    win = rel // pws
    entry = jnp.where(valid_pos, p + 1, 0)  # 0 = empty bucket
    grid_tab = jnp.zeros((wins, E), jnp.int32).at[win, h].max(entry)
    run_max = jax.lax.associative_scan(jnp.maximum, grid_tab, axis=0)
    excl = jnp.concatenate([jnp.zeros((1, E), jnp.int32), run_max[:-1]], axis=0)
    lvt = lvt_ref[...]
    cand = jnp.maximum(excl[win, h], jnp.take(lvt, h)) - 1
    cand = jnp.where(valid_pos, cand, -1)

    # -- 3. table update: later tiles see this tile's windows ---------------
    lvt_ref[...] = jnp.maximum(lvt, run_max[-1])

    # -- 4. match search (word compare) + bounded extension (S2) ------------
    cc = jnp.clip(cand, 0, B - 1)
    w0 = jnp.take(blk, cc).astype(jnp.uint32)
    w1 = jnp.take(blk, jnp.clip(cc + 1, 0, B - 1)).astype(jnp.uint32)
    w2 = jnp.take(blk, jnp.clip(cc + 2, 0, B - 1)).astype(jnp.uint32)
    w3 = jnp.take(blk, jnp.clip(cc + 3, 0, B - 1)).astype(jnp.uint32)
    wc = w0 | (w1 << 8) | (w2 << 16) | (w3 << 24)
    valid4 = (cand >= 0) & (wc == w) & (p <= n - MF_LIMIT)

    max_extra = jnp.clip(
        n - LAST_LITERALS - (p + MIN_MATCH), 0, max_match - MIN_MATCH
    )
    prefix = jnp.ones((tile,), dtype=jnp.bool_)
    length = jnp.zeros((tile,), dtype=jnp.int32)
    for j in range(max_match - MIN_MATCH):
        cur = jax.lax.dynamic_slice(blk, (base + MIN_MATCH + j,), (tile,))
        cnd = jnp.take(blk, jnp.clip(cc + MIN_MATCH + j, 0, B - 1))
        prefix = prefix & (cur == cnd) & (j < max_extra)
        length = length + prefix.astype(jnp.int32)
    len_ref[...] = jnp.where(valid4, MIN_MATCH + length, 0)
    cand_ref[...] = cand


@functools.partial(
    jax.jit,
    static_argnames=("positions", "hash_bits", "pws", "max_match", "interpret"),
)
def fused_compress_pallas(block, n, positions: int, hash_bits: int = 8,
                          pws: int = 8, max_match: int = 36,
                          interpret: bool | None = None):
    """Candidates + bounded match lengths for every position, one kernel.

    block     : (B,) int32 byte values, zeroed past the true length;
                B >= positions + max_match (the padded compressor block)
    n         : (1,) int32 true block length
    positions : static position count P; P % TILE == 0, TILE % pws == 0
    interpret : None resolves from the backend (`backend.interpret_mode`)

    Returns ``(cand, lengths)``: (P,) int32 each — candidate position (-1
    where none/invalid) and full match length (0 where no valid match,
    else in [MIN_MATCH, max_match]), elementwise-equal to `ref.fused_ref`.
    """
    if interpret is None:
        interpret = interpret_mode("fused_compress")
    P = positions
    B = block.shape[0]
    E = 1 << hash_bits
    assert P % TILE == 0, f"P={P} must be a multiple of {TILE}"
    assert TILE % pws == 0, f"pws={pws} must divide the tile size {TILE}"
    assert B >= P + max(max_match, MIN_MATCH), \
        "block must be padded past the last position"
    grid = (P // TILE,)
    cand, lengths, _lvt = pl.pallas_call(
        functools.partial(_fused_kernel, hash_bits=hash_bits, pws=pws,
                          max_match=max_match, tile=TILE),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1,), lambda i: (0,)),      # n: scalar-as-(1,)
            pl.BlockSpec((B,), lambda i: (0,)),      # full block each step
        ],
        out_specs=[
            pl.BlockSpec((TILE,), lambda i: (i,)),   # cand: tiled
            pl.BlockSpec((TILE,), lambda i: (i,)),   # lengths: tiled
            pl.BlockSpec((E,), lambda i: (0,)),      # LVT: persistent
        ],
        out_shape=[
            jax.ShapeDtypeStruct((P,), jnp.int32),
            jax.ShapeDtypeStruct((P,), jnp.int32),
            jax.ShapeDtypeStruct((E,), jnp.int32),
        ],
        interpret=interpret,
    )(n, block)
    return cand, lengths
