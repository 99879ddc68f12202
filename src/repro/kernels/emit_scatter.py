"""Pallas TPU kernel: device-side LZ4 byte emission (scatter-emit).

The paper keeps the whole token pipeline on-chip; byte emission was our last
host-side stage (NumPy prefix sums in core/emitter.py).  This kernel closes
the loop: given the per-sequence layout fields (prefix sums computed in XLA,
see kernels/ops.py `emit_bytes`) and the covering-sequence map `seg`, every
output byte is a pure function of its own position — the inverse-scatter
formulation, so the kernel body is elementwise math plus gathers, with no
variable-length writes and no feedback between positions.

Memory layout (mirrors match_extend.py):
  * the input block and the (N_FIELDS, S) per-sequence field table are fully
    VMEM-resident each grid step (256 KB + ~256 KB at defaults — the paper's
    on-chip buffers);
  * `seg` and the output are tiled by TILE positions;
  * the two data-dependent reads — per-sequence fields at `seg[k]` and input
    literals at `anchor + r` — are 1-D `jnp.take` gathers, which the TPU
    compiler refuses (backend.TPU_REFUSED): the kernel runs only in the
    interpreter, off the TPU.

The byte math is intentionally duplicated from kernels/ref.py
`emit_bytes_ref` (the jnp oracle): the two paths stay independent and are
asserted bit-identical in tests/test_device_emit.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .backend import interpret_mode
from .ref import (
    F_ANCHOR,
    F_HAS_MATCH,
    F_LIT,
    F_LIT_EXT,
    F_MATCH_EXT,
    F_MLX,
    F_OFF,
    F_START,
    N_FIELDS,
)

TILE = 2048


def _emit_scatter_kernel(total_ref, block_ref, fields_ref, seg_ref, out_ref, *, tile):
    i = pl.program_id(0)
    base = i * tile
    total = total_ref[0]
    blk = block_ref[...]
    f = fields_ref[...]
    seg = seg_ref[...]
    k = base + jax.lax.iota(jnp.int32, tile)

    # Gather the covering sequence's layout fields.
    st = jnp.take(f[F_START], seg)
    anc = jnp.take(f[F_ANCHOR], seg)
    lit = jnp.take(f[F_LIT], seg)
    le = jnp.take(f[F_LIT_EXT], seg)
    mlx = jnp.take(f[F_MLX], seg)
    me = jnp.take(f[F_MATCH_EXT], seg)
    off = jnp.take(f[F_OFF], seg)
    hm = jnp.take(f[F_HAS_MATCH], seg)

    r = k - st
    token = (jnp.minimum(lit, 15) << 4) | jnp.where(hm > 0, jnp.minimum(mlx, 15), 0)
    lit_ext_byte = jnp.where(r < le, 255, (lit - 15) % 255)
    src = jnp.clip(anc + r - 1 - le, 0, blk.shape[0] - 1)
    lit_byte = jnp.take(blk, src)
    lit_end = 1 + le + lit
    mext_byte = jnp.where(r - (lit_end + 2) < me - 1, 255, (mlx - 15) % 255)
    b = jnp.where(r == 0, token,
        jnp.where(r <= le, lit_ext_byte,
        jnp.where(r <= le + lit, lit_byte,
        jnp.where(r == lit_end, off & 0xFF,
        jnp.where(r == lit_end + 1, (off >> 8) & 0xFF, mext_byte)))))
    out_ref[...] = jnp.where(k < total, b, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def emit_scatter_pallas(block, seg, fields, total,
                        interpret: bool | None = None):
    """Materialize the compressed block's bytes on device.

    block  : (B,) int32 input byte values (zeroed past the true length)
    seg    : (K,) int32 covering-sequence index per output byte, K % TILE == 0
    fields : (N_FIELDS, S) int32 per-sequence layout rows (ref.F_*)
    total  : (1,) int32 exact compressed size; positions >= total emit 0

    Returns (K,) int32 byte values (cast to uint8 at the ops.py boundary —
    int32 lanes keep the kernel on the VPU's native element type).
    ``interpret=None`` resolves from the backend (`backend.interpret_mode`).
    """
    if interpret is None:
        interpret = interpret_mode("emit_scatter")
    K = seg.shape[0]
    B = block.shape[0]
    S = fields.shape[1]
    assert K % TILE == 0, f"K={K} must be a multiple of {TILE}"
    assert fields.shape[0] == N_FIELDS, fields.shape
    grid = (K // TILE,)
    return pl.pallas_call(
        functools.partial(_emit_scatter_kernel, tile=TILE),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1,), lambda i: (0,)),            # total: scalar-as-(1,)
            pl.BlockSpec((B,), lambda i: (0,)),            # full block each step
            pl.BlockSpec((N_FIELDS, S), lambda i: (0, 0)),  # full field table
            pl.BlockSpec((TILE,), lambda i: (i,)),         # seg map: tiled
        ],
        out_specs=pl.BlockSpec((TILE,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((K,), jnp.int32),
        interpret=interpret,
    )(total, block, fields, seg)
