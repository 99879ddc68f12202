"""Vectorized JAX engine of the paper's combined scheme (S1 + S2).

This is the TPU-native re-expression of the hardware architecture in Fig. 5:

  Word Shift + Hash Calculation  -> kernels.ops.hash_positions
  Hash Table (LVT, multi-port)   -> candidate resolution: because every
        position is written every cycle and reads see previous-cycle
        state, cand(p) = max{q : hash(q)=hash(p), window(q)<window(p)} — a
        per-bucket predecessor query, resolved without a sort by
        scatter-max into a (windows x entries) grid and a log-depth cummax
        (kernels.ref.scatter_candidates_ref)
  Match Searching                -> vectorized word compare (the table stores
        the 4-byte string; here: words[cand] == words[p])
  Extended Match (bounded, S2)   -> kernels.ops.confirmed_match_lengths: the
        confirm and the extension compare 4-byte words, the candidate's
        from one gather per 8 words (kernels.ref.match_words_ref)
  single-match select (S1)       -> per-window earliest-eligible selection.
        The only true sequential state is the free pointer; S2 bounds its
        reach to max_match-1 bytes, so it admits BOTH
          * a paper-faithful `lax.scan` over windows (1 "cycle"/window), and
          * an associative scan over per-window transfer tables of size
            R = max_match (beyond-paper optimization: O(log W) depth).
  Sequence Encoding              -> exact compressed size computed in-graph;
        byte emission ALSO stays in-graph on the default engine path
        (`compress_block_bytes` -> kernels.ops.emit_bytes: prefix-sum
        offsets + byte scatter on device, only final bytes cross the host
        boundary).  The host-side emitters (emitter.py vectorized,
        encoder.py loop-based) survive as the bit-identity oracles.

Both selects are bit-identical to the numpy golden model (schemes.py);
tests/test_lz4_jax.py asserts exact equality of the per-window match
records, tests/test_device_emit.py the emitted bytes.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops, ref
from .lz4_types import (
    DEFAULT_HASH_BITS,
    DEFAULT_MAX_MATCH,
    DEFAULT_PWS,
    MAX_BLOCK,
    MIN_MATCH,
    Sequence,
)

_PAD = 71  # block padding: max max_match (68) + 3 word-shift bytes

# Device-emit output buffer size per block.  The worst case compressed block
# is literals-only: 1 token + 257 extension bytes + MAX_BLOCK literals =
# MAX_BLOCK + 258; rounded up to MAX_BLOCK + 2048, a multiple of the TPU's
# 128 lanes and the (rows, OUT_CAP) output shape of every write program.
OUT_CAP = MAX_BLOCK + 2048


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class BlockRecords:
    """Per-window match records for one block — the hardware's output signals."""

    emit: jax.Array     # (W,) bool
    pos: jax.Array      # (W,) int32
    length: jax.Array   # (W,) int32
    offset: jax.Array   # (W,) int32
    size: jax.Array     # () int32 — exact compressed size of the block


def _select_sequential(valid, lengths, pws: int):
    """Paper-faithful window scan: one step per window, free-pointer carry."""
    P = valid.shape[0]
    W = P // pws
    validw = valid.reshape(W, pws)
    lenw = lengths.reshape(W, pws)
    base = (jnp.arange(W, dtype=jnp.int32) * pws)[:, None]
    posw = base + jnp.arange(pws, dtype=jnp.int32)[None, :]

    def step(fp, xs):
        v, l, pos = xs
        elig = v & (pos >= fp)
        any_e = elig.any()
        idx = jnp.argmax(elig)
        sel_pos = pos[idx]
        sel_len = l[idx]
        fp2 = jnp.where(any_e, sel_pos + sel_len, fp)
        return fp2, (any_e, sel_pos, sel_len)

    _, (emit, pos, length) = jax.lax.scan(step, jnp.int32(0), (validw, lenw, posw))
    return emit, pos, length


def _select_associative(valid, lengths, pws: int, max_match: int):
    """Beyond-paper: compose per-window free-pointer transfer tables.

    S2 bounds the free pointer entering window w to [ws, ws + R) with
    R = max_match (fp' = p + len <= ws-1 + max_match).  Each window is a
    monotone step-function on R states; composition is associative, so the
    whole selection runs in O(log W) depth.
    """
    P = valid.shape[0]
    W = P // pws
    R = max_match  # entering fp - window_start is in [0, R)
    validw = valid.reshape(W, pws)
    lenw = lengths.reshape(W, pws)
    base = jnp.arange(W, dtype=jnp.int32)[:, None] * pws
    rel = jnp.arange(pws, dtype=jnp.int32)[None, :]

    # Transfer table: for entering fp = ws + r, the resulting absolute fp'.
    r = jnp.arange(R, dtype=jnp.int32)[None, :, None]           # (1, R, 1)
    elig = validw[:, None, :] & (rel[:, None, :] >= r)           # (W, R, pws)
    any_e = elig.any(-1)                                         # (W, R)
    idx = jnp.argmax(elig, axis=-1).astype(jnp.int32)            # (W, R)
    sel_end = base + idx + jnp.take_along_axis(lenw, idx, axis=-1)
    table = jnp.where(any_e, sel_end, base + jnp.arange(R, dtype=jnp.int32)[None, :])

    def compose(t1, t2):
        # Apply t1 (earlier windows) then t2.  Tables are indexed by the
        # entering fp relative to the composite's own base, so the composite
        # keeps t1's base.  Exit fp of t1 is < base2 + R (S2 bound), so the
        # clip below is exact, not an approximation.
        tab1, base1 = t1
        tab2, base2 = t2
        r2 = jnp.clip(tab1 - base2, 0, R - 1)
        return jnp.take_along_axis(tab2, r2, axis=-1), base1

    bases = jnp.arange(W, dtype=jnp.int32)[:, None] * pws  # (W,1) broadcast vs (W,R)
    bases = jnp.broadcast_to(bases, (W, R))
    prefix_tab, _ = jax.lax.associative_scan(compose, (table, bases), axis=0)
    # Entering fp for window w = prefix over [0..w-1] evaluated at r=0.
    entering = jnp.concatenate([jnp.zeros((1,), jnp.int32), prefix_tab[:-1, 0]])
    # Reconstruct the selection for every window in parallel.
    rw = jnp.clip(entering[:, None] - base, 0, R - 1)  # (W,1)
    elig_w = validw & (rel >= rw)
    emit = elig_w.any(-1)
    idxw = jnp.argmax(elig_w, axis=-1).astype(jnp.int32)
    pos = (base + idxw[:, None])[:, 0]
    length = jnp.take_along_axis(lenw, idxw[:, None], axis=-1)[:, 0]
    return emit, pos, length


def _lit_ext(x):
    return jnp.where(x < 15, 0, 1 + (x - 15) // 255)


def _match_ext(l):
    m = l - MIN_MATCH
    return jnp.where(m < 15, 0, 1 + (m - 15) // 255)


def _plan_size(emit, pos, length, n):
    """Exact compressed size from per-window match records (in-graph)."""
    end = jnp.where(emit, pos + length, 0)
    run_end = jax.lax.cummax(end)
    prev_end = jnp.concatenate([jnp.zeros((1,), jnp.int32), run_end[:-1]])
    lit = pos - prev_end
    per = jnp.where(emit, 1 + _lit_ext(lit) + lit + 2 + _match_ext(length), 0)
    last_end = run_end[-1]
    final_lit = n - last_end
    total = per.sum() + 1 + _lit_ext(final_lit) + final_lit
    return total.astype(jnp.int32)


@functools.partial(
    jax.jit, static_argnames=("hash_bits", "max_match", "pws", "scan_impl"),
)
def compress_block_records(
    block_u8,
    n,
    hash_bits: int = DEFAULT_HASH_BITS,
    max_match: int = DEFAULT_MAX_MATCH,
    pws: int = DEFAULT_PWS,
    scan_impl: str = "sequential",
) -> BlockRecords:
    """Compress one padded block; returns per-window match records + size.

    block_u8 : (MAX_BLOCK + _PAD,) uint8 (content beyond `n` is ignored)
    n        : scalar int32 true length (0 <= n <= MAX_BLOCK)
    """
    assert block_u8.shape[0] == MAX_BLOCK + _PAD, block_u8.shape
    block = block_u8.astype(jnp.int32)
    # Zero the padding region so it can never fake matches past n.
    idx = jnp.arange(block.shape[0], dtype=jnp.int32)
    block = jnp.where(idx < n, block, 0)

    # Each stage runs under a `jax.named_scope` ("lz4.<stage>"), so the
    # per-operation events of a device trace name the stage they belong to;
    # the scope names are stable (docs/observability.md lists them).
    with jax.named_scope("lz4.hash"):
        _, hashes = ops.hash_positions(block[: MAX_BLOCK + 3], hash_bits)
    with jax.named_scope("lz4.candidates"):
        cand = ref.scatter_candidates_ref(hashes, n, hash_bits, pws)

    with jax.named_scope("lz4.extend"):
        lengths = ops.confirmed_match_lengths(block, cand, n, max_match=max_match)
        valid = lengths >= MIN_MATCH

    with jax.named_scope("lz4.select"):
        if scan_impl == "sequential":
            emit, pos, length = _select_sequential(valid, lengths, pws)
        elif scan_impl == "associative":
            emit, pos, length = _select_associative(valid, lengths, pws, max_match)
        else:
            raise ValueError(scan_impl)

        offset = pos - jnp.take(cand, pos)
        emit = emit & (length > 0)
        size = _plan_size(emit, pos, length, n)
    return BlockRecords(
        emit=emit,
        pos=jnp.where(emit, pos, -1),
        length=jnp.where(emit, length, 0),
        offset=jnp.where(emit, offset, 0),
        size=size,
    )


@functools.partial(
    jax.jit,
    static_argnames=("hash_bits", "max_match", "pws", "scan_impl", "out_cap"),
)
def compress_block_bytes(
    block_u8,
    n,
    hash_bits: int = DEFAULT_HASH_BITS,
    max_match: int = DEFAULT_MAX_MATCH,
    pws: int = DEFAULT_PWS,
    scan_impl: str = "sequential",
    out_cap: int = OUT_CAP,
):
    """Compress one padded block to FINAL BYTES, entirely in-graph.

    The device-resident emit path (docs/architecture.md §write path): the
    match-record pipeline of `compress_block_records` feeds straight into
    `kernels.ops.emit_bytes` — token byte-lengths, exclusive prefix-sum
    offsets, and the byte scatter all stay on the accelerator, so the ONLY
    host transfer per block is the (out_cap,) uint8 output buffer plus a
    size scalar (vs four (W,) record arrays for the host-emit path).

    Returns ``(out, size)``: out is (out_cap,) uint8, ``out[:size]`` is the
    compressed block, bit-identical to the host oracle
    ``emitter.emit_block(...)`` on the same records.
    """
    rec = compress_block_records(
        block_u8, n,
        hash_bits=hash_bits, max_match=max_match, pws=pws, scan_impl=scan_impl,
    )
    with jax.named_scope("lz4.emit"):
        block = block_u8.astype(jnp.int32)
        idx = jnp.arange(block.shape[0], dtype=jnp.int32)
        block = jnp.where(idx < n, block, 0)
        out, total = ops.emit_bytes(
            block, rec.emit, rec.pos, rec.length, rec.offset, n,
            out_cap=out_cap,
        )
    return out, total


# Batched form for throughput: vmap over a stack of blocks.
@functools.partial(
    jax.jit, static_argnames=("hash_bits", "max_match", "pws", "scan_impl"),
)
def compress_blocks_records(
    blocks_u8,
    ns,
    hash_bits: int = DEFAULT_HASH_BITS,
    max_match: int = DEFAULT_MAX_MATCH,
    pws: int = DEFAULT_PWS,
    scan_impl: str = "sequential",
) -> BlockRecords:
    fn = functools.partial(
        compress_block_records,
        hash_bits=hash_bits,
        max_match=max_match,
        pws=pws,
        scan_impl=scan_impl,
    )
    return jax.vmap(fn)(blocks_u8, ns)


def pad_block(data: bytes) -> tuple[np.ndarray, int]:
    buf = np.zeros(MAX_BLOCK + _PAD, dtype=np.uint8)
    buf[: len(data)] = np.frombuffer(data, dtype=np.uint8)
    return buf, len(data)


def records_to_plan(rec: BlockRecords, n: int) -> list[Sequence]:
    """Host-side: per-window records -> sequence plan (for byte emission)."""
    emit = np.asarray(rec.emit)
    pos = np.asarray(rec.pos)
    length = np.asarray(rec.length)
    offset = np.asarray(rec.offset)
    plan: list[Sequence] = []
    anchor = 0
    for w in np.nonzero(emit)[0]:
        plan.append(Sequence(anchor, int(pos[w]) - anchor, int(length[w]), int(offset[w])))
        anchor = int(pos[w]) + int(length[w])
    plan.append(Sequence(anchor, n - anchor))
    return plan
