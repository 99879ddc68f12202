"""The device stages' jitted entry points: the jnp graphs the chip runs.

Write side: `hash_positions`, `confirmed_match_lengths` and `emit_bytes`
(the stages of `core.jax_compressor`'s write graph).  Read side:
`decode_gather`, the in-graph planner `plan_speculative` / `plan_decode`,
and `crc32_bytes`.  Their elementwise bodies live in ref.py; the layout
halves (prefix sums, covering maps, validation, compaction) live here.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.lz4_types import MIN_MATCH

from . import ref


def _pad_to(x, multiple, value=0):
    P = x.shape[0]
    rem = (-P) % multiple
    if rem == 0:
        return x
    return jnp.concatenate([x, jnp.full((rem,), value, x.dtype)])


@functools.partial(jax.jit, static_argnames=("hash_bits",))
def hash_positions(block_i32, hash_bits: int = 8):
    """Word + Fibonacci hash at every position of a (B,) int32 byte block.

    The block must be padded with >= 3 trailing bytes; returns (words, hashes)
    of length B-3 (one per position that has a full 4-byte word).
    """
    B = block_i32.shape[0]
    P = B - 3
    return ref.fibhash_ref(block_i32[:P], block_i32[1 : P + 1],
                           block_i32[2 : P + 2], block_i32[3 : P + 3], hash_bits)


@functools.partial(jax.jit, static_argnames=("max_match",))
def match_lengths(block_i32, cand, valid, n, max_match: int = 36):
    """Bounded match length per position (0 where ~valid, else in [4, max_match]).

    The extension at a given confirmed mask (`ref.match_extend_ref`); the
    write graph confirms for itself (`confirmed_match_lengths`).
    """
    return ref.match_extend_ref(block_i32, cand, valid, n, max_match)


@functools.partial(jax.jit, static_argnames=("max_match",))
def confirmed_match_lengths(block_i32, cand, n, max_match: int = 36):
    """The write graph's S2 stage: 4-byte confirm, then bounded extension.

    Full match length per position: 0 where no match starts at p (no
    candidate, its first 4 bytes differ, or p > n - MF_LIMIT), else in
    [4, max_match].  Both come from one word compare, `ref.match_words_ref`.
    """
    start, extra = ref.match_words_ref(block_i32, cand, n, max_match)
    return jnp.where(start, MIN_MATCH + extra, 0)


def _ext_len(v):
    """Extension byte count for a token-nibble value (literal count or
    match_len - MIN_MATCH): 0 below 15, else 1 + (v - 15) // 255."""
    return jnp.where(v < 15, 0, 1 + (v - 15) // 255)


def _emit_layout(emit, pos, length, offset, n, out_cap: int):
    """Per-sequence output layout + covering-sequence map, all in-graph.

    The layout half of device-side emission (`emit_bytes`):
    log-depth prefix sums turn the per-window match records into exact byte
    offsets — a cummax recovers each sequence's literal anchor (as in
    `_plan_size`), a cumsum over per-sequence byte sizes places every token —
    then one scatter of sequence ids at those starts plus a cummax over
    output positions yields `seg`, the covering-sequence index of every
    output byte.  The final literals-only sequence is appended as column W.

    Returns (seg (out_cap,) int32, fields (ref.N_FIELDS, W+1) int32,
    total () int32).
    """
    emit = emit.astype(bool)
    pos = pos.astype(jnp.int32)
    length = length.astype(jnp.int32)
    offset = offset.astype(jnp.int32)
    W = emit.shape[0]

    end = jnp.where(emit, pos + length, 0)
    run_end = jax.lax.cummax(end)
    anchor = jnp.concatenate([jnp.zeros((1,), jnp.int32), run_end[:-1]])
    lit = jnp.where(emit, pos - anchor, 0)
    mlx = jnp.where(emit, length - MIN_MATCH, 0)
    lit_ext = jnp.where(emit, _ext_len(lit), 0)
    match_ext = jnp.where(emit, _ext_len(mlx), 0)
    seq_size = jnp.where(emit, 3 + lit_ext + lit + match_ext, 0)
    csum = jnp.cumsum(seq_size)
    starts = csum - seq_size

    final_start = csum[-1]
    final_anchor = run_end[-1]
    final_lit = n - final_anchor
    final_ext = _ext_len(final_lit)
    total = final_start + 1 + final_ext + final_lit

    app = lambda a, v: jnp.concatenate([a.astype(jnp.int32),
                                        jnp.asarray(v, jnp.int32)[None]])
    fields = jnp.stack([
        app(starts, final_start),            # F_START
        app(anchor, final_anchor),           # F_ANCHOR
        app(lit, final_lit),                 # F_LIT
        app(lit_ext, final_ext),             # F_LIT_EXT
        app(mlx, 0),                         # F_MLX
        app(match_ext, 0),                   # F_MATCH_EXT
        app(jnp.where(emit, offset, 0), 0),  # F_OFF
        app(emit.astype(jnp.int32), 0),      # F_HAS_MATCH
    ])

    # seg[k] = index of the sequence covering output byte k: scatter each
    # live sequence's id at its start (non-emitting windows have zero-size
    # sequences — their starts collide with a neighbour's, so they are
    # routed to a dropped out-of-range index), then a cummax forward-fills.
    live = jnp.concatenate([emit, jnp.ones((1,), bool)])
    sidx = jnp.where(live, fields[ref.F_START], out_cap)
    smap = jnp.zeros((out_cap,), jnp.int32).at[sidx].max(
        jnp.arange(W + 1, dtype=jnp.int32) + 1, mode="drop"
    )
    seg = jax.lax.cummax(smap) - 1
    return seg, fields, total.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("out_cap",))
def emit_bytes(block_i32, emit, pos, length, offset, n, out_cap: int):
    """Device-side LZ4 byte emission from per-window match records.

    block_i32 : (B,) int32 input byte values, zeroed past `n`
    emit/pos/length/offset : (W,) per-window match records (BlockRecords)
    n         : scalar int32 true block length
    out_cap   : static output buffer size; must exceed the worst-case
                compressed size (literals-only: MAX_BLOCK + 257 + 1)

    Returns ``(out, total)``: a (out_cap,) uint8 buffer whose first `total`
    bytes are the compressed block (bit-identical to
    `repro.core.emitter.emit_block`, the host oracle) and the exact size:
    the layout (prefix sums + seg map) here, the bytes from
    `ref.emit_bytes_ref`'s gathers.
    """
    seg, fields, total = _emit_layout(emit, pos, length, offset, n, out_cap)
    return ref.emit_bytes_ref(block_i32, seg, fields, total), total


def _span_map(starts, n_valid, out_cap: int):
    """Covering-span index per output position (scatter + cummax fill).

    The decode-side twin of `_emit_layout`'s seg map: scatter each live
    span's slot id at its start (padding slots — index >= `n_valid` — are
    routed to a dropped out-of-range position), then a cummax forward-fills
    so every output byte knows the last span that started at or before it.
    Returns (out_cap,) int32; -1 where no span has started yet.
    """
    S = starts.shape[0]
    slot = jnp.arange(S, dtype=jnp.int32)
    idx = jnp.where(slot < n_valid, starts, out_cap)
    smap = jnp.zeros((out_cap,), jnp.int32).at[idx].max(slot + 1, mode="drop")
    return jax.lax.cummax(smap) - 1


@functools.partial(jax.jit, static_argnames=("out_cap", "rounds"))
def decode_gather(blk_u8, lit_src, lit_dst, lit_len, match_dst, match_off,
                  n_lit, n_match, out_size, out_cap: int, rounds: int):
    """Device-side block decode from a fixed-shape `DevicePlan`.

    The read-path mirror of `emit_bytes`, same split of labour: the span
    layout (scatter + cummax covering maps, gathers of per-span fields)
    here, the pointer-doubling resolve and byte gather in
    `ref.decode_gather_ref`.

    blk_u8    : (B,) uint8 compressed-payload bytes, zeroed past the true
                payload length (B is the static payload cap; uint8 so the
                host->device upload moves payload bytes, not int32 lanes)
    lit_*     : (L,) int32 literal-run arrays (src in block, dst in output,
                length); rows >= `n_lit` are padding
    match_*   : (M,) int32 match arrays (dst in output, back-offset); rows
                >= `n_match` are padding
    out_size  : scalar int32 decoded size (0 for padding rows of a batch)
    out_cap   : static output buffer size (>= any usize, i.e. MAX_BLOCK)
    rounds    : static pointer-doubling depth; `MAX_RESOLVE_ROUNDS` (16)
                covers every valid block, fewer suffice when the plans'
                `n_waves` say so

    Returns (out_cap,) uint8 whose first `out_size` bytes are the decoded
    block — bit-identical to `execute_plan` / `execute_device_plan` (the
    host oracles) and safe under vmap (a stacked micro-batch of plans
    decodes as one dispatch, exactly like the compress side).
    """
    with jax.named_scope("lz4.gather"):
        blk_i32 = blk_u8.astype(jnp.int32)
        L = lit_src.shape[0]
        M = match_dst.shape[0]
        k = jnp.arange(out_cap, dtype=jnp.int32)

        li = _span_map(lit_dst, n_lit, out_cap)
        mi = _span_map(match_dst, n_match, out_cap)
        liC = jnp.clip(li, 0, L - 1)
        lit_end = jnp.take(lit_dst, liC) + jnp.take(lit_len, liC)
        is_lit = (li >= 0) & (k < lit_end)
        in_range = k < out_size
        moff = jnp.take(match_off, jnp.clip(mi, 0, M - 1))
        # Literal bytes (and everything past out_size) are fixed points of the
        # source map; match bytes point back by their covering match's offset.
        ptr = jnp.where(is_lit | ~in_range, k, k - moff)
        ptr = jnp.clip(ptr, 0, out_cap - 1)
        lit_blk = jnp.where(is_lit, jnp.take(lit_src, liC) + (k - jnp.take(lit_dst, liC)), 0)
        return ref.decode_gather_ref(blk_i32, lit_blk, ptr, out_size, rounds)


# --- speculative in-graph planning -----------------------------------------
#
# Buffer padding past the block cap: the speculative parser's 0xFF-run table
# is read at index n, so the (B,) buffer must be strictly longer than any
# payload.  128, one row of TPU lanes, is the planner graphs' buffer shape.
SPEC_PAD = 128

# Rows of the (SPEC_STATUS,) int32 status vector returned per block.
SPEC_ERR, SPEC_N_LIT, SPEC_N_MATCH, SPEC_OUT_SIZE, SPEC_OVERFLOW = range(5)
SPEC_STATUS = 5

# Error codes 1..8 are `core.decode_plan._ERR_MESSAGES`; 9 is the serial
# parser's "truncated block: missing token" (no valid final sequence).
SPEC_ERR_MISSING_TOKEN = 9


@functools.partial(
    jax.jit, static_argnames=("max_lit", "max_match", "out_cap"))
def plan_speculative(blk_u8, n, max_out, max_lit: int = 8448,
                     max_match: int = 8448, out_cap: int = 65536):
    """Parse one block's token stream into `DevicePlan` arrays, in-graph.

    The device-side replacement for `plan_block_fast` + `to_device_plan`:
    `ref.plan_fields_ref` decodes a candidate header at every offset and
    selects the real chain; this half then validates the chain with the
    host planner's exact error codes, lays out output offsets with a
    cumsum, and compacts the headers into fixed-shape plan arrays with one
    scatter per column.

    blk_u8  : (B,) uint8 payload bytes zeroed past `n`; B > blk_cap
              (pad with `SPEC_PAD`)
    n       : scalar int32 true payload length (<= B - 1)
    max_out : scalar int32 decoded-size limit (usize when known, else
              MAX_BLOCK) — the host planner's `max_out`
    max_lit/max_match/out_cap : static `DevicePlanCaps` shapes

    Returns ``(lit_src, lit_dst, lit_len, match_dst, match_off, match_len,
    status)``: the first six are the zero-padded `DevicePlan` columns,
    bit-identical to ``to_device_plan(plan_block_fast(...))`` for valid
    streams; ``status`` is (SPEC_STATUS,) int32 indexed by ``SPEC_*`` —
    ``status[SPEC_ERR]`` carries the host planner's error code (0 = valid),
    ``status[SPEC_OVERFLOW]`` flags caps overflow (host falls back).  The
    plan columns are garbage whenever err/overflow is set; callers must
    check status first.

    All arithmetic is int32.  That is safe even though the host planner
    sums in int64: per-position fields are < 2^25, and the first invalid
    sequence is validated against prefix sums over *earlier, valid*
    sequences only (each bounded by max_out <= 2^16), so every value that
    can decide accept/reject is exact; wrapped sums can only occur at
    positions after the first error, which never win the argmax below.
    """
    B = blk_u8.shape[0]
    n = jnp.asarray(n, jnp.int32)
    max_out = jnp.asarray(max_out, jnp.int32)
    is_start, lit_start, lit_len, ls_end, off, mlen, flags = ref.plan_fields_ref(
        blk_u8.astype(jnp.int32), n)
    started = is_start > 0
    trunc_lx = (flags & 1) > 0
    trunc_mx = (flags & 2) > 0
    nonfinal = ls_end != n

    # Output layout: cumsum of per-header contributions (zero off-chain),
    # so prev_total / before_match match the host planner's running total.
    ll = jnp.where(started, lit_len, 0)
    ml = jnp.where(started & nonfinal, mlen, 0)
    cum = jnp.cumsum(ll + ml)
    prev_total = cum - (ll + ml)
    before_match = prev_total + ll
    out_size = cum[-1]

    # Validation, in the host planner's exact priority order: per position
    # the lowest matching code wins, across positions the first bad header.
    err = jnp.zeros((B,), jnp.int32)
    checks = (
        (trunc_lx, 1),                                  # truncated lit len
        (ls_end > n, 2),                                # truncated literals
        (prev_total + lit_len > max_out, 3),            # output exceeds limit
        (nonfinal & (ls_end + 2 > n), 4),               # truncated offset
        (nonfinal & (off == 0), 5),                     # zero offset
        (nonfinal & (off > before_match), 6),           # offset beyond output
        (nonfinal & trunc_mx, 7),                       # truncated match len
        (nonfinal & (before_match + mlen > max_out), 8),  # exceeds limit
    )
    for cond, code in checks:
        err = jnp.where(started & cond & (err == 0), code, err)
    has_err = err > 0
    err_code = jnp.where(jnp.any(has_err), jnp.take(err, jnp.argmax(has_err)),
                         0)
    final_ok = jnp.any(started & (ls_end == n))
    err_code = jnp.where((err_code == 0) & ~final_ok, SPEC_ERR_MISSING_TOKEN,
                         err_code)

    # Compaction: one scatter per DevicePlan column.  Ordinal slots are
    # unique and the scattered values are non-negative for valid streams,
    # so scatter-max over a zero buffer reproduces `to_device_plan`'s
    # zero-padded columns exactly.
    litmask = started & (lit_len > 0)
    lit_ord = jnp.cumsum(litmask.astype(jnp.int32)) - 1
    n_lit = jnp.sum(litmask.astype(jnp.int32))
    lidx = jnp.where(litmask, lit_ord, max_lit)
    zL = jnp.zeros((max_lit,), jnp.int32)
    lit_src_o = zL.at[lidx].max(lit_start, mode="drop")
    lit_dst_o = zL.at[lidx].max(prev_total, mode="drop")
    lit_len_o = zL.at[lidx].max(lit_len, mode="drop")

    matchmask = started & nonfinal
    m_ord = jnp.cumsum(matchmask.astype(jnp.int32)) - 1
    n_match = jnp.sum(matchmask.astype(jnp.int32))
    midx = jnp.where(matchmask, m_ord, max_match)
    zM = jnp.zeros((max_match,), jnp.int32)
    match_dst_o = zM.at[midx].max(before_match, mode="drop")
    match_off_o = zM.at[midx].max(off, mode="drop")
    match_len_o = zM.at[midx].max(mlen, mode="drop")

    overflow = (n_lit > max_lit) | (n_match > max_match) | (out_size > out_cap)
    status = jnp.stack([err_code, n_lit, n_match, out_size,
                        overflow.astype(jnp.int32)])
    return (lit_src_o, lit_dst_o, lit_len_o,
            match_dst_o, match_off_o, match_len_o, status)


@functools.partial(
    jax.jit,
    static_argnames=("out_cap", "max_lit", "max_match", "rounds",
                     "compute_crc"))
def plan_decode(blk_u8, n, max_out, out_cap: int, max_lit: int,
                max_match: int, rounds: int, compute_crc: bool = True):
    """Fused plan + execute (+ CRC) for one block, entirely in-graph.

    Chains `plan_speculative` into `decode_gather` (and `crc32_bytes` when
    `compute_crc`), so a vmapped micro-batch of compressed payloads turns
    into decoded bytes in ONE dispatch with no host parse.  Rows whose
    status carries an error or caps overflow decode to zeros (the caller
    raises or falls back from the status vector); `rounds` should be
    `MAX_RESOLVE_ROUNDS` — with no host plan there is no `n_waves` to
    shrink it adaptively.

    Returns ``(out, status, crc)``: (out_cap,) uint8 decoded bytes,
    the (SPEC_STATUS,) int32 status from `plan_speculative`, and a ()
    uint32 CRC-32 of the decoded payload (0 when `compute_crc` is off).
    """
    (lit_src, lit_dst, lit_len, match_dst, match_off, _match_len,
     status) = plan_speculative(
        blk_u8, n, max_out, max_lit=max_lit, max_match=max_match,
        out_cap=out_cap)
    ok = (status[SPEC_ERR] == 0) & (status[SPEC_OVERFLOW] == 0)
    out_size = jnp.where(ok, status[SPEC_OUT_SIZE], 0)
    out = decode_gather(blk_u8, lit_src, lit_dst, lit_len, match_dst,
                        match_off, status[SPEC_N_LIT], status[SPEC_N_MATCH],
                        out_size, out_cap=out_cap, rounds=rounds)
    crc = crc32_bytes(out, out_size) if compute_crc else jnp.uint32(0)
    return out, status, crc


_CRC_CHUNK = 1024       # bytes per row of the chunk matmul
_CRC_PIECE = 1 << 20    # bytes per loop step; bounds the bit unpack's memory


def _gf2_pow(m, e):
    out = np.eye(32, dtype=np.int64)
    for bit in bin(e)[:1:-1]:
        out, m = (out @ m & 1 if bit == "1" else out), m @ m & 1
    return out


@functools.lru_cache(maxsize=None)
def _crc_constants(chunks: int, padded: int):
    """CRC-32 (IEEE, reflected: zlib/binascii) as GF(2) matrices, built once
    on the host and embedded in `crc32_bytes`' graph.  A register is a
    32-bit row vector, a map M takes v to v @ M mod 2, A is one zero byte.

    G (8 * CHUNK, 32): row (bit k, byte i) is the zero-init register after
    a chunk holding only that bit.  H (32 * chunks, 32): block c is
    A^(CHUNK * (chunks-1-c)), carrying chunk c's register to the piece's
    end.  Then A^piece, and A^-(2^i) for each bit of `padded`."""
    t = np.arange(256, dtype=np.uint32)
    for _ in range(8):
        t = np.where(t & 1, (t >> 1) ^ np.uint32(0xEDB88320), t >> 1)
    unit = np.uint32(1) << np.arange(32, dtype=np.uint32)
    bits = lambda r: (r[..., None] >> np.arange(32, dtype=np.uint32) & 1
                      ).astype(np.int64)
    rows = [t[unit[:8]]]
    for _ in range(_CRC_CHUNK - 1):
        rows.append(t[rows[-1] & 0xFF] ^ (rows[-1] >> 8))
    A = bits(t[unit & 0xFF] ^ (unit >> 8))
    i = np.argsort(t >> 24).astype(np.uint32)[unit >> 24]  # top bytes unique
    inv = [bits(((unit ^ t[i]) << 8) | i)]
    for _ in range(padded.bit_length() - 1):
        inv.append(inv[-1] @ inv[-1] & 1)
    H = [_gf2_pow(A, _CRC_CHUNK * c) for c in range(chunks - 1, -1, -1)]
    return (bits(np.stack(rows[::-1], axis=1)).reshape(-1, 32),
            np.concatenate(H), _gf2_pow(A, _CRC_CHUNK * chunks), np.stack(inv))


def _gf2_dot(a, m):
    """``a @ m`` over GF(2): 0/1 operands in bf16, exact integer sums in
    f32, then mod 2 (int32 bits)."""
    s = jnp.dot(a.astype(jnp.bfloat16), jnp.asarray(m, jnp.bfloat16),
                preferred_element_type=jnp.float32)
    return s.astype(jnp.int32) & 1


@jax.jit
def crc32_bytes(data_u8, n):
    """CRC-32 of ``data_u8[:n]``, entirely in-graph (GF(2) matmuls).

    data_u8 : (K,) uint8 buffer (content past `n` is ignored)
    n       : scalar int32 byte count, 0 <= n <= K

    Returns a () uint32 equal to ``binascii.crc32(bytes(data_u8[:n]))`` —
    the frame's `block_crc`.  CRC-32 is linear over GF(2): the bytes past
    `n` are zeroed, one bits x G matmul gives every 1 KiB chunk's register
    and one x H matmul carries them to the piece's end; pieces of 1 MiB
    fold in a loop.  The zero tail is undone by log2(K) steps of A^-(2^i),
    chosen by the bits of K - n, so `n` stays traced and one compiled graph
    covers every block size.  Used by the decode engine so
    `decode_to_device(verify=True)` checks integrity WITHOUT fetching the
    decoded payload to the host.
    """
    with jax.named_scope("lz4.crc"):
        K = data_u8.shape[0]
        piece = min(_CRC_PIECE, -(-K // _CRC_CHUNK) * _CRC_CHUNK)
        chunks = piece // _CRC_CHUNK
        x = _pad_to(data_u8, piece).reshape(-1, piece)
        G, H, Ap, inv = _crc_constants(chunks, x.size)
        n = jnp.asarray(n, jnp.int32)

        def fold(v, xs):
            piece_u8, start = xs
            b = jnp.where(jnp.arange(piece) < n - start, piece_u8, 0)
            b = b.reshape(chunks, 1, _CRC_CHUNK) >> jnp.arange(
                8, dtype=jnp.uint8)[:, None] & 1
            regs = _gf2_dot(b.reshape(chunks, 8 * _CRC_CHUNK), G)
            return _gf2_dot(v, Ap) ^ _gf2_dot(regs.reshape(-1), H), None

        starts = jnp.arange(x.shape[0], dtype=jnp.int32) * piece
        # The carry starts as the 0xFFFFFFFF initial register.
        v, _ = jax.lax.scan(fold, jnp.ones(32, jnp.int32), (x, starts))
        pad = x.size - n
        for i in range(inv.shape[0]):
            v = jnp.where(pad >> i & 1, _gf2_dot(v, inv[i]), v)
        return ~jnp.sum(v.astype(jnp.uint32)
                        << jnp.arange(32, dtype=jnp.uint32))
