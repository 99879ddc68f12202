"""Pallas TPU kernel: speculative in-graph LZ4 sequence parsing.

The plan-side twin of decode_wave.py.  The device decode executor used to
parse token streams on host (`plan_block_fast` in core/decode_plan.py) —
the last O(n) host stage in the restore path.  This kernel removes it by
speculating: it decodes a CANDIDATE sequence header at EVERY byte offset
of the compressed block (token nibbles, 0xFF-run literal/match length
extensions, the 16-bit back offset, the next-header position — all pure
functions of the offset once the 0xFF-run table exists), then selects the
single chain actually reachable from offset 0 with log-depth pointer
doubling over the next[] map.  The approach is Sitaridi et al.'s
massively-parallel speculative decompression (PAPERS.md) mapped onto the
covering-sequence machinery this repo already uses for decode.

Two log-depth passes, both VMEM-resident at the 64 KB block scale:

    ffrun[i]  (0xFF-run table)  — suffix-min doubling over "first
              non-0xFF position at or after i", ceil(log2(B)) shifts
    chain     mark = {0}; per round:  mark |= mark scattered through
              jump;  jump = jump[jump]   (reachable set doubles per round)

Headers are at least 3 bytes apart, so a 64 KB block chains < 2^15 deep
and 16 rounds always converge — no data-dependent control flow, no host
fallback for well-formed streams.  The field math reproduces
`plan_block_fast` byte for byte including its clamped reads, so the XLA
validator downstream (`kernels/ops.py` `plan_speculative`) rejects
malformed streams with error codes identical to the host oracle's.

The gathers are 1-D `jnp.take` and the chain union is a scatter-max
(`.at[].max`), per the emit_scatter.py precedent; the TPU compiler refuses
the gathers (backend.TPU_REFUSED), so the kernel runs only in the
interpreter, off the TPU.  The math is intentionally duplicated from
kernels/ref.py `plan_fields_ref` (the jnp oracle): the two paths stay
independent and are asserted bit-identical in tests/test_plan_speculative.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .backend import interpret_mode

# Doubling depth of the chain-select pass: 2^16 hops covers any sequence
# chain a 64 KB block can contain (headers are >= 3 bytes apart).
CHAIN_ROUNDS = 16


def _plan_spec_kernel(n_ref, blk_ref, start_ref, lit_start_ref, lit_len_ref,
                      ls_end_ref, off_ref, mlen_ref, flags_ref, *,
                      chain_rounds):
    B = blk_ref.shape[0]
    n = n_ref[0]
    blk = blk_ref[...]
    idx = jax.lax.iota(jnp.int32, B)
    inb = idx < n
    nm1 = jnp.maximum(n - 1, 0)

    # 0xFF-run table by suffix-min doubling: m[i] converges to the first
    # non-0xFF position at or after i; the run length is m[i] - i.
    m = jnp.where((blk == 255) & inb, B, idx)
    s = 1
    while s < B:
        m = jnp.minimum(m, jnp.take(m, jnp.minimum(idx + s, B - 1)))
        s <<= 1
    ffrun = m - idx

    # Literal half of the candidate header at every offset.
    lit_nib = blk >> 4
    has_lx = lit_nib == 15
    r1 = jnp.take(ffrun, jnp.minimum(idx + 1, B - 1))
    term1 = idx + 1 + r1
    t1b = jnp.take(blk, jnp.minimum(term1, nm1))
    lit_len = jnp.where(has_lx, r1 * 255 + t1b + 15, lit_nib)
    lit_start = idx + 1 + jnp.where(has_lx, 1 + r1, 0)
    ls_end = lit_start + lit_len

    # Match half: offset bytes at ls_end, extension run after them.
    m_nib = blk & 15
    has_mx = m_nib == 15
    o0 = jnp.minimum(ls_end, nm1)
    off = jnp.take(blk, o0) | (jnp.take(blk, jnp.minimum(o0 + 1, nm1)) << 8)
    r2 = jnp.take(ffrun, jnp.minimum(ls_end + 2, n))
    term2 = ls_end + 2 + r2
    t2b = jnp.take(blk, jnp.minimum(term2, nm1))
    mlen = jnp.where(has_mx, r2 * 255 + t2b + 19, m_nib + 4)
    nxt = ls_end + 2 + jnp.where(has_mx, r2 + 1, 0)

    # Chain select: union the set reachable from offset 0 through its
    # 2^k-hop successors, then square the pointer map.  next[] strictly
    # advances (headers >= 3 bytes), so chains exit via the fixed point n.
    jump = jnp.where(inb, jnp.minimum(nxt, n), idx)
    mark = (idx == 0).astype(jnp.int32)
    for _ in range(chain_rounds):
        mark = mark.at[jump].max(mark, mode="drop")
        jump = jnp.take(jump, jump)

    start_ref[...] = jnp.where(inb, mark, 0)
    lit_start_ref[...] = lit_start
    lit_len_ref[...] = lit_len
    ls_end_ref[...] = ls_end
    off_ref[...] = off
    mlen_ref[...] = mlen
    flags_ref[...] = (has_lx & (term1 >= n)).astype(jnp.int32) | (
        (has_mx & (term2 >= n)).astype(jnp.int32) << 1)


@functools.partial(jax.jit, static_argnames=("chain_rounds", "interpret"))
def plan_spec_pallas(block, n, chain_rounds: int = CHAIN_ROUNDS,
                     interpret: bool | None = None):
    """Speculatively parse one block's candidate headers on device.

    block        : (B,) int32 compressed-payload byte values, zeroed past
                   n; B must be strictly greater than any n (the run
                   table is read at index n)
    n            : (1,) int32 true payload length
    chain_rounds : static chain-select doubling depth

    Returns seven (B,) int32 arrays (is_start, lit_start, lit_len, ls_end,
    off, mlen, flags) — field semantics documented on kernels/ref.py
    `plan_fields_ref`, validation/compaction in kernels/ops.py
    `plan_speculative`.  ``interpret=None`` resolves from the backend
    (`backend.interpret_mode`).
    """
    if interpret is None:
        interpret = interpret_mode("plan_spec")
    B = block.shape[0]
    return pl.pallas_call(
        functools.partial(_plan_spec_kernel, chain_rounds=chain_rounds),
        grid=(1,),
        in_specs=[
            pl.BlockSpec((1,), lambda i: (0,)),   # n: scalar-as-(1,)
            pl.BlockSpec((B,), lambda i: (0,)),   # full compressed block
        ],
        out_specs=[pl.BlockSpec((B,), lambda i: (0,))] * 7,
        out_shape=[jax.ShapeDtypeStruct((B,), jnp.int32)] * 7,
        interpret=interpret,
    )(n, block)
