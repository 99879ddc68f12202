"""The jnp bodies of the device stages in ops.py: the code the chip runs.

Each is checked against an independent host oracle in the tests (the NumPy
golden model, the host emitter, the serial planner and decoder).  Besides
them, `match_extend_ref` extends at a given confirmed mask; only the tests
call it (through `ops.match_lengths`), to hold the word-wide extension to
their byte-at-a-time loop.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core.lz4_types import HASH_PRIME, MF_LIMIT, MIN_MATCH, LAST_LITERALS

# Row layout of the per-sequence `fields` array consumed by `emit_bytes_ref`.
# One column per sequence: the W per-window sequences plus the final
# literals-only one.
F_START = 0       # output byte offset of the sequence's token
F_ANCHOR = 1      # input offset of the sequence's first literal
F_LIT = 2         # literal count
F_LIT_EXT = 3     # literal-length extension byte count
F_MLX = 4         # match length - MIN_MATCH (0 for the final sequence)
F_MATCH_EXT = 5   # match-length extension byte count
F_OFF = 6         # 16-bit match back-offset (0 for the final sequence)
F_HAS_MATCH = 7   # 1 where the sequence carries a match, 0 for the final one
N_FIELDS = 8


def fibhash_ref(b0, b1, b2, b3, hash_bits: int):
    """Fibonacci hash of the little-endian 4-byte word at each position.

    b0..b3 are the byte streams shifted by 0..3 positions (int32 in [0,255]).
    Returns (word_u32_as_i32, hash) — hash in [0, 2^hash_bits).
    """
    w = (
        b0.astype(jnp.uint32)
        | (b1.astype(jnp.uint32) << 8)
        | (b2.astype(jnp.uint32) << 16)
        | (b3.astype(jnp.uint32) << 24)
    )
    h = (w * jnp.uint32(HASH_PRIME)) >> jnp.uint32(32 - hash_bits)
    return w.astype(jnp.int32), h.astype(jnp.int32)


WORD = 4  # bytes the bounded extension compares per step
# Ladder rungs per candidate-side gather: 8 rows fill a TPU (8, 128) tile,
# so no gathered tile pads to 16 rows.  On a TPU v5e one 9-row gather ran
# slower than an 8-row and a 1-row one, and asked 44% more temporary memory.
GATHER_RUNGS = 8


def word_ladder(block, positions: int, rungs: int):
    """The word ladder: (rungs + 1, positions) uint32, rung k at position q
    the little-endian 4-byte word at byte q + 4k.

    Built from static slices of the block, which is zero-padded here if it
    ends before the last word does.
    """
    L = positions + WORD * rungs
    b = block.astype(jnp.uint32)
    if b.shape[0] < L + 3:
        b = jnp.pad(b, (0, L + 3 - b.shape[0]))
    words = b[:L] | (b[1 : L + 1] << 8) | (b[2 : L + 2] << 16) | (b[3 : L + 3] << 24)
    return jnp.stack([words[WORD * k : WORD * k + positions] for k in range(rungs + 1)])


def match_words_ref(block, cand, n, max_match: int):
    """4-byte confirm and bounded extension, a word at a time (the S2 datapath).

    block : (B,) int32 byte values (padded past `n` arbitrarily)
    cand  : (P,) int32 candidate position per position p (-1 where none)
    n     : scalar int32, true block length
    max_match : static python int, the match-length cap (paper: 36)

    Position p's own ladder column holds its first four bytes (rung 0) and
    the K = ceil((max_match - 4) / 4) words after them; the candidate's
    column comes from one gather of the ladder at `cand` per GATHER_RUNGS
    rungs (two at max_match 36), in place of a gather per byte.  A word
    adds 4 while every earlier word was equal; the first unequal one adds
    its count of equal low-order bytes.

    Returns ``(start, extra)``, both (P,): ``start`` is True where a match
    can start at p (a candidate whose first 4 bytes equal p's, and
    p <= n - MF_LIMIT); ``extra`` is the count of equal bytes after those
    four, ``min(first mismatch, max_extra)`` with ``max_extra`` the cap of
    ``max_match - 4`` and of the end-of-block rule (match end <= n - 5).
    Bytes past the cap never change it.
    """
    P = cand.shape[0]
    K = -(-(max_match - MIN_MATCH) // WORD)
    ladder = word_ladder(block, P, K)
    c = jnp.clip(cand, 0, P - 1)
    x = []  # the K + 1 rungs of ladder ^ candidate's ladder column
    for g in range(0, K + 1, GATHER_RUNGS):
        part = ladder[g : g + GATHER_RUNGS]
        x.extend(part ^ jnp.take(part, c, axis=1))
    p = jnp.arange(P, dtype=jnp.int32)
    start = (cand >= 0) & (x[0] == 0) & (p <= n - MF_LIMIT)
    run = jnp.zeros(P, dtype=jnp.int32)
    alive = jnp.ones(P, dtype=bool)
    for k in range(1, K + 1):
        # equal low-order bytes: the masks nest, so each equal byte adds 1
        low = sum(((x[k] & m) == 0).astype(jnp.int32) for m in (0xFF, 0xFFFF, 0xFFFFFF))
        low = low + (x[k] == 0).astype(jnp.int32)
        run = run + jnp.where(alive, low, 0)
        alive = alive & (x[k] == 0)
    max_extra = jnp.clip(n - LAST_LITERALS - (p + MIN_MATCH), 0, max_match - MIN_MATCH)
    return start, jnp.minimum(run, max_extra)


def match_extend_ref(block, cand, valid, n, max_match: int):
    """Bounded extended-match length where a 4-byte match is given.

    valid : (P,) bool, 4-byte match already confirmed at p; the other
    arguments as `match_words_ref`.  Returns (P,) int32 full match length
    (>= 4 where valid, 0 elsewhere), capped at max_match and at the
    end-of-block rule (match end <= n-5).
    """
    _, extra = match_words_ref(block, cand, n, max_match)
    return jnp.where(valid, MIN_MATCH + extra, 0)


def scatter_candidates_ref(hashes, n, hash_bits: int, pws: int):
    """Scatter-max LVT candidate resolution (no sort).

    cand(p) = max{q : hash(q)=hash(p), win(q)<win(p)}: scatter positions
    into a (windows x entries) grid — the hash table materialized over
    time — exclusive cummax along the window axis (log-depth), gather at
    (win(p), hash(p)).  The write graph's `lz4.candidates` stage
    (`jax_compressor.compress_block_records`).  Returns (P,) int32, -1
    where no candidate/invalid position.
    """
    import jax

    P = hashes.shape[0]
    E = 1 << hash_bits
    p = jnp.arange(P, dtype=jnp.int32)
    valid_pos = p <= n - MIN_MATCH
    W = P // pws
    win = p // pws
    key = jnp.where(valid_pos, win * E + hashes, W * E)  # sentinel row dropped
    table = jnp.zeros((W * E + 1,), jnp.int32).at[key].max(p + 1, mode="drop")
    tm = table[: W * E].reshape(W, E)
    run_max = jax.lax.associative_scan(jnp.maximum, tm, axis=0)
    excl = jnp.concatenate([jnp.zeros((1, E), jnp.int32), run_max[:-1]], axis=0)
    cand = excl[win, jnp.clip(hashes, 0, E - 1)] - 1
    return jnp.where(valid_pos, cand, -1)


def emit_bytes_ref(block, seg, fields, total):
    """LZ4 byte materialization: (output position -> byte) via gathers.

    The inverse-scatter formulation of block emission: instead of scattering
    each sequence's ragged pieces into the output (variable-length writes),
    every output position k looks up its covering sequence `seg[k]` and
    derives its byte from the relative offset r = k - start alone:

        r == 0                         -> token
        1 <= r <= lit_ext              -> literal-length extension byte
        lit_ext < r <= lit_ext + lit   -> literal (one gather from the input)
        r == 1 + lit_ext + lit         -> offset low byte
        r == 2 + lit_ext + lit         -> offset high byte
        r beyond                       -> match-length extension byte

    block  : (B,) int32 byte values of the input block (zeroed past n)
    seg    : (K,) int32 covering-sequence index per output position
    fields : (N_FIELDS, S) int32 per-sequence layout (see F_* rows above)
    total  : scalar int32 exact compressed size; positions >= total emit 0

    Two gathers: one column gather of the whole `fields` table at `seg`,
    whose N_FIELDS = 8 rows (in F_* order) fill a TPU (8, 128) tile, so it
    does not pad and costs about what one 1-D gather costs (GATHER_RUNGS is
    8 for the same reason; a ninth row would pad the tile to 16); and the
    literal gather from the block, whose index differs per position.

    Returns (K,) uint8.  Bit-identical to `repro.core.emitter.emit_block`
    (asserted in tests/test_device_emit.py) — purely elementwise once the
    per-sequence fields are gathered, which is what makes it a kernel shape.
    """
    K = seg.shape[0]
    k = jnp.arange(K, dtype=jnp.int32)
    st, anc, lit, le, mlx, me, off, hm = jnp.take(fields, seg, axis=1)

    r = k - st
    token = (jnp.minimum(lit, 15) << 4) | jnp.where(hm > 0, jnp.minimum(mlx, 15), 0)
    # Extension runs are (count-1) bytes of 255 followed by (value-15) % 255.
    lit_ext_byte = jnp.where(r < le, 255, (lit - 15) % 255)
    src = jnp.clip(anc + r - 1 - le, 0, block.shape[0] - 1)
    lit_byte = jnp.take(block, src)
    lit_end = 1 + le + lit
    mext_byte = jnp.where(r - (lit_end + 2) < me - 1, 255, (mlx - 15) % 255)
    b = jnp.where(r == 0, token,
        jnp.where(r <= le, lit_ext_byte,
        jnp.where(r <= le + lit, lit_byte,
        jnp.where(r == lit_end, off & 0xFF,
        jnp.where(r == lit_end + 1, (off >> 8) & 0xFF, mext_byte)))))
    return jnp.where(k < total, b, 0).astype(jnp.uint8)


def plan_fields_ref(block, n, chain_rounds: int = 16):
    """The speculative parse of `ops.plan_speculative`.

    Decode a CANDIDATE sequence header at EVERY byte offset of a compressed
    block — token nibbles, 0xFF-run literal/match length extensions, the
    16-bit back offset, the next-header position — then select the single
    chain actually reachable from offset 0.  This is the feedback-free
    formulation of `plan_block_fast`'s prepass (Sitaridi et al., arXiv
    1606.00519): every field is a pure function of its byte offset, so the
    serial parse's only residue — *which* offsets are headers — becomes a
    log-depth reachability pass over the next[] map (scatter-max union of
    the marked set through its 2^k-hop pointers, the decode-side mirror of
    `decode_gather_ref`'s pointer doubling).

    The field math reproduces `plan_block_fast` byte for byte, INCLUDING
    its clamped reads (terminator/offset bytes are fetched at
    ``min(pos, n-1)``), so candidate fields at non-header offsets — and the
    error flags of truncated headers — match what the host planner would
    compute, and the in-graph validator in `kernels.ops.plan_speculative`
    can reject malformed streams with identical error codes.

    block        : (B,) int32 byte values of the compressed payload,
                   zeroed past ``n``; B must be STRICTLY greater than any
                   n (the run table is read at index n)
    n            : scalar int32 true payload length
    chain_rounds : static doubling depth; 16 covers any chain in a 64 KB
                   block (headers are >= 3 bytes apart, so < 2^15 hops)

    Returns seven (B,) int32 arrays:
      is_start  — 1 where a sequence header actually starts
      lit_start — offset of the sequence's first literal byte
      lit_len   — literal run length
      ls_end    — offset just past the literals (= the offset field)
      off       — 16-bit back offset (clamped-garbage where truncated)
      mlen      — match length (garbage for the final sequence)
      flags     — bit 0: truncated literal-length extension,
                  bit 1: truncated match-length extension
    """
    import jax

    B = block.shape[0]
    idx = jnp.arange(B, dtype=jnp.int32)
    n = jnp.asarray(n, jnp.int32)
    inb = idx < n
    nm1 = jnp.maximum(n - 1, 0)

    # ffrun[i] = length of the 0xFF run starting at i (0 at/past n): the
    # first non-0xFF position at or after i, by a reversed cummin, minus i.
    next_notff = jax.lax.cummin(
        jnp.where((block == 255) & inb, B, idx), reverse=True)
    ffrun = next_notff - idx

    # Literal half of the header: nibble, extension run, extended length.
    lit_nib = block >> 4
    has_lx = lit_nib == 15
    r1 = jnp.take(ffrun, jnp.minimum(idx + 1, B - 1))
    term1 = idx + 1 + r1                    # extension terminator position
    t1b = jnp.take(block, jnp.minimum(term1, nm1))
    lit_len = jnp.where(has_lx, r1 * 255 + t1b + 15, lit_nib)
    lit_start = idx + 1 + jnp.where(has_lx, 1 + r1, 0)
    ls_end = lit_start + lit_len

    # Match half: offset bytes at ls_end, extension run after them.
    m_nib = block & 15
    has_mx = m_nib == 15
    o0 = jnp.minimum(ls_end, nm1)
    off = jnp.take(block, o0) | (jnp.take(block, jnp.minimum(o0 + 1, nm1)) << 8)
    r2 = jnp.take(ffrun, jnp.minimum(ls_end + 2, n))
    term2 = ls_end + 2 + r2
    t2b = jnp.take(block, jnp.minimum(term2, nm1))
    mlen = jnp.where(has_mx, r2 * 255 + t2b + 19, m_nib + 4)
    nxt = ls_end + 2 + jnp.where(has_mx, r2 + 1, 0)

    flags = (has_lx & (term1 >= n)).astype(jnp.int32) \
        | ((has_mx & (term2 >= n)).astype(jnp.int32) << 1)

    # Chain select: headers are >= 3 bytes, so next[] strictly advances and
    # every chain exits through the sentinel fixed point at n.  mark holds
    # the set reachable from 0 in < 2^k hops; each round unions in the
    # 2^k-hop successors (one scatter-max) and squares the pointer map.
    jump = jnp.where(inb, jnp.minimum(nxt, n), idx)
    mark = (idx == 0).astype(jnp.int32)
    for _ in range(chain_rounds):
        mark = mark.at[jump].max(mark, mode="drop")
        jump = jnp.take(jump, jump)
    is_start = jnp.where(inb, mark, 0)
    return is_start, lit_start, lit_len, ls_end, off, mlen, flags


def decode_gather_ref(block, lit_blk, ptr, total, rounds: int):
    """Device-side block decode: transitive-source resolve + ONE byte gather.

    The read-path mirror of `emit_bytes_ref`: instead of executing match
    copies in stream order (serial feedback through the output buffer),
    every output byte k carries its IMMEDIATE source — itself for literal
    bytes (a fixed point of the source map), ``k - offset`` for match
    bytes — and the transitive source is resolved by pointer doubling:
    after r rounds of ``ptr = ptr[ptr]`` every dependency chain of depth
    <= 2^r terminates at a literal byte.  `rounds` is static (the decode
    engine picks it from the micro-batch's plan depth, worst case
    ceil(log2(MAX_BLOCK)) = 16), so the whole decode is `rounds` + 2
    gathers with no data-dependent control flow — the shape GPULZ and
    Sitaridi et al. reach for massively-parallel decompression.

    block   : (B,) int32 byte values of the COMPRESSED block (zero-padded)
    lit_blk : (K,) int32 per-output-byte literal source index into `block`
              (valid where the byte's resolved pointer lands — i.e. at
              literal positions; arbitrary elsewhere)
    ptr     : (K,) int32 per-output-byte immediate source position
    total   : scalar int32 decoded size; positions >= total emit 0

    Returns (K,) uint8.  Bit-identical to `repro.core.decode_plan.
    execute_plan` / `execute_device_plan` (asserted in tests).
    """
    K = ptr.shape[0]
    k = jnp.arange(K, dtype=jnp.int32)
    for _ in range(rounds):
        ptr = jnp.take(ptr, ptr)
    b = jnp.take(block, jnp.take(lit_blk, ptr))
    return jnp.where(k < total, b, 0).astype(jnp.uint8)
