"""Vectorized JAX engine of the paper's combined scheme (S1 + S2).

This is the TPU-native re-expression of the hardware architecture in Fig. 5:

  Word Shift + Hash Calculation  -> kernels.ops.hash_positions (Pallas/jnp)
  Hash Table (LVT, multi-port)   -> candidate resolution: because every
        position is written every cycle and reads see previous-cycle
        state, cand(p) = max{q : hash(q)=hash(p), window(q)<window(p)} — a
        per-bucket predecessor query.  Four bit-identical impls
        (`candidate_impl`): "sort" (argsort + segment ops), "sortkey"
        (packed-key sort), "scatter" (scatter-max + log-depth cummax, no
        sort), and "fused" (the whole hash->LVT->match-extend datapath as
        ONE Pallas kernel with a VMEM-resident table written/read in
        window order — kernels/fused_compress.py; jnp twin
        kernels/ref.fused_ref).  "auto" (the default) resolves per
        backend (`resolve_candidate_impl`): the measured-fastest impl on
        CPU, the expected accelerator shapes off-CPU.
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

All variants are bit-identical to the numpy golden model (schemes.py) and to
each other; tests/test_lz4_jax.py asserts exact equality of the per-window
match records, tests/test_device_emit.py the emitted bytes.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops
from .lz4_types import (
    DEFAULT_HASH_BITS,
    DEFAULT_MAX_MATCH,
    DEFAULT_PWS,
    LAST_LITERALS,
    MAX_BLOCK,
    MIN_MATCH,
    Sequence,
)

_PAD = 71  # block padding: max max_match (68) + 3 word-shift bytes

# The candidate-resolution implementations selectable via `candidate_impl`
# (all bit-identical at the match-record level; tests/test_lz4_jax.py,
# tests/test_fused_compress.py).
CANDIDATE_IMPLS = ("sort", "sortkey", "scatter", "fused")


def resolve_candidate_impl(candidate_impl: str = "auto",
                           backend: str | None = None,
                           use_pallas: bool = False) -> str:
    """Resolve ``"auto"`` to the best impl for a backend.

    On CPU the choice is MEASURED (BENCH_engine_batched.json
    `candidate_impl`, docs/tuning.md): the packed-key value sort wins
    (~1.4x over argsort at micro_batch=32 — half the sort payload, no gathers; it also beats
    scatter's 8 MB grid at every micro-batch on the reference container).
    Off CPU the choices are the expected accelerator shapes, not yet
    benchmarked on real hardware: the scatter-max formulation (log-depth
    cummax, no sort) on GPU and on TPU without Pallas; with
    ``use_pallas=True`` on TPU, the fused single-pass kernel that keeps
    the whole datapath in VMEM.  "fused" is only auto-selected when the
    Pallas kernel would actually run — its jnp twin is the scatter
    formulation plus extra gathers, so auto-picking it without Pallas
    would be strictly worse than "scatter".  Concrete impl names pass
    through unchanged, so callers can always pin one.
    """
    if candidate_impl == "auto":
        backend = backend or jax.default_backend()
        if backend == "tpu":
            return "fused" if use_pallas else "scatter"
        return "scatter" if backend == "gpu" else "sortkey"
    if candidate_impl not in CANDIDATE_IMPLS:
        raise ValueError(
            f"candidate_impl must be 'auto' or one of {CANDIDATE_IMPLS}, "
            f"got {candidate_impl!r}"
        )
    return candidate_impl

# Device-emit output buffer size per block.  The worst case compressed block
# is literals-only: 1 token + 257 extension bytes + MAX_BLOCK literals =
# MAX_BLOCK + 258; padded up to a lane-aligned multiple of the emit kernel's
# tile (2048) so the Pallas path needs no re-padding.
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


def _candidates_scatter(hashes, n, hash_bits: int, pws: int):
    """Scatter-max LVT candidate resolution (beyond-paper optimization).

    cand(p) = max{q : hash(q)=hash(p), win(q)<win(p)} computed WITHOUT the
    64K-element argsort: scatter-max positions into a (windows x entries)
    grid (this IS the hash table, materialized over time), exclusive cummax
    along the window axis (log-depth), then gather at (win(p), hash(p)).
    Identical output to _candidates; ~2.5x less memory traffic (see
    EXPERIMENTS.md §Perf).  The formulation itself lives in
    `kernels.ref.scatter_candidates_ref` — it is also stage 2 of the fused
    datapath's jnp twin, and sharing one definition keeps the staged impl
    and the twin from drifting.
    """
    from repro.kernels.ref import scatter_candidates_ref

    return scatter_candidates_ref(hashes, n, hash_bits, pws)


def _candidates_sortkey(hashes, n, hash_bits: int, pws: int):
    """Key-packed sort candidate resolution (beyond-paper optimization).

    Because P = 65536 = 2^16, (hash, position) packs into ONE int32 key:
    `h << 16 | p`.  Sorting values (jnp.sort) instead of argsort halves the
    sort payload (no index array to permute) and eliminates the two gathers
    that argsort-based resolution needs; both hash and position are recovered
    from the sorted key by bit ops.  Bit-identical to _candidates.
    """
    P = hashes.shape[0]
    assert P & (P - 1) == 0, "key packing requires power-of-two P"
    p = jnp.arange(P, dtype=jnp.int32)
    valid_pos = p <= n - MIN_MATCH
    h = jnp.where(valid_pos, hashes, 1 << hash_bits)
    skey = jnp.sort(h * P + p)
    h_s = skey >> 16
    p_s = skey & (P - 1)
    w_s = p_s // pws
    prev_h = jnp.concatenate([jnp.full((1,), -1, h_s.dtype), h_s[:-1]])
    prev_w = jnp.concatenate([jnp.full((1,), -1, w_s.dtype), w_s[:-1]])
    prev_p = jnp.concatenate([jnp.full((1,), -1, p_s.dtype), p_s[:-1]])
    same_hash = h_s == prev_h
    head = ~(same_hash & (w_s == prev_w))
    group_id = jnp.cumsum(head.astype(jnp.int32)) - 1
    head_cand = jnp.where(head & same_hash, prev_p, -1)
    group_val = jnp.zeros((P,), jnp.int32).at[group_id].add(
        jnp.where(head, head_cand + 1, 0)
    )
    cand_s = jnp.take(group_val, group_id) - 1
    cand = jnp.zeros((P,), jnp.int32).at[p_s].set(cand_s)
    return cand


def _candidates(hashes, n, hash_bits: int, pws: int):
    """Sort-based LVT candidate resolution. hashes: (P,) int32."""
    P = hashes.shape[0]
    p = jnp.arange(P, dtype=jnp.int32)
    # Positions without a full 4-byte word get a sentinel bucket so they can
    # neither find nor become candidates.
    valid_pos = p <= n - MIN_MATCH
    h = jnp.where(valid_pos, hashes, 1 << hash_bits)
    key = h * P + p  # unique; sorts by (hash, position)
    order = jnp.argsort(key).astype(jnp.int32)
    h_s = jnp.take(h, order)
    w_s = order // pws
    prev_h = jnp.concatenate([jnp.full((1,), -1, h_s.dtype), h_s[:-1]])
    prev_w = jnp.concatenate([jnp.full((1,), -1, w_s.dtype), w_s[:-1]])
    prev_p = jnp.concatenate([jnp.full((1,), -1, order.dtype), order[:-1]])
    same_hash = h_s == prev_h
    head = ~(same_hash & (w_s == prev_w))
    group_id = jnp.cumsum(head.astype(jnp.int32)) - 1
    head_cand = jnp.where(head & same_hash, prev_p, -1)
    # Each group has exactly one head: scatter head candidate, gather back.
    group_val = jnp.zeros((P,), jnp.int32).at[group_id].add(
        jnp.where(head, head_cand + 1, 0)
    )
    cand_s = jnp.take(group_val, group_id) - 1
    cand = jnp.zeros((P,), jnp.int32).at[order].set(cand_s)
    return cand


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
    jax.jit,
    static_argnames=(
        "hash_bits", "max_match", "pws", "use_pallas", "scan_impl", "candidate_impl",
    ),
)
def compress_block_records(
    block_u8,
    n,
    hash_bits: int = DEFAULT_HASH_BITS,
    max_match: int = DEFAULT_MAX_MATCH,
    pws: int = DEFAULT_PWS,
    use_pallas: bool = False,
    scan_impl: str = "sequential",
    candidate_impl: str = "auto",
) -> BlockRecords:
    """Compress one padded block; returns per-window match records + size.

    block_u8 : (MAX_BLOCK + _PAD,) uint8 (content beyond `n` is ignored)
    n        : scalar int32 true length (0 <= n <= MAX_BLOCK)
    """
    assert block_u8.shape[0] == MAX_BLOCK + _PAD, block_u8.shape
    candidate_impl = resolve_candidate_impl(candidate_impl,
                                            use_pallas=use_pallas)
    block = block_u8.astype(jnp.int32)
    # Zero the padding region so it can never fake matches past n.
    idx = jnp.arange(block.shape[0], dtype=jnp.int32)
    block = jnp.where(idx < n, block, 0)

    # Each stage runs under a `jax.named_scope` ("lz4.<stage>"), so the
    # per-operation events of a device trace name the stage they belong to;
    # the scope names are stable (docs/observability.md lists them).
    if candidate_impl == "fused":
        # Single-pass datapath: hash, LVT candidate, word compare, and the
        # bounded extension come back from ONE kernel (or its jnp twin) —
        # no intermediate hash/word/candidate arrays round-trip through
        # the graph, and no sort anywhere.
        with jax.named_scope("lz4.match"):
            cand, lengths = ops.fused_match_candidates(
                block, n, positions=MAX_BLOCK, hash_bits=hash_bits, pws=pws,
                max_match=max_match, use_pallas=use_pallas,
            )
            valid = lengths >= MIN_MATCH
    else:
        with jax.named_scope("lz4.hash"):
            _, hashes = ops.hash_positions(block[: MAX_BLOCK + 3], hash_bits,
                                           use_pallas=use_pallas)
        cand_fn = {
            "sort": _candidates,
            "sortkey": _candidates_sortkey,
            "scatter": _candidates_scatter,
        }[candidate_impl]
        with jax.named_scope("lz4.candidates"):
            cand = cand_fn(hashes, n, hash_bits, pws)

        with jax.named_scope("lz4.extend"):
            lengths = ops.confirmed_match_lengths(block, cand, n, max_match=max_match,
                                                  use_pallas=use_pallas)
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
    static_argnames=(
        "hash_bits", "max_match", "pws", "use_pallas", "scan_impl",
        "candidate_impl", "out_cap",
    ),
)
def compress_block_bytes(
    block_u8,
    n,
    hash_bits: int = DEFAULT_HASH_BITS,
    max_match: int = DEFAULT_MAX_MATCH,
    pws: int = DEFAULT_PWS,
    use_pallas: bool = False,
    scan_impl: str = "sequential",
    candidate_impl: str = "auto",
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
        hash_bits=hash_bits, max_match=max_match, pws=pws,
        use_pallas=use_pallas, scan_impl=scan_impl,
        candidate_impl=candidate_impl,
    )
    with jax.named_scope("lz4.emit"):
        block = block_u8.astype(jnp.int32)
        idx = jnp.arange(block.shape[0], dtype=jnp.int32)
        block = jnp.where(idx < n, block, 0)
        out, total = ops.emit_bytes(
            block, rec.emit, rec.pos, rec.length, rec.offset, n,
            out_cap=out_cap, use_pallas=use_pallas,
        )
    return out, total


# Batched form for throughput: vmap over a stack of blocks.
@functools.partial(
    jax.jit,
    static_argnames=(
        "hash_bits", "max_match", "pws", "use_pallas", "scan_impl", "candidate_impl",
    ),
)
def compress_blocks_records(
    blocks_u8,
    ns,
    hash_bits: int = DEFAULT_HASH_BITS,
    max_match: int = DEFAULT_MAX_MATCH,
    pws: int = DEFAULT_PWS,
    use_pallas: bool = False,
    scan_impl: str = "sequential",
    candidate_impl: str = "auto",
) -> BlockRecords:
    fn = functools.partial(
        compress_block_records,
        hash_bits=hash_bits,
        max_match=max_match,
        pws=pws,
        use_pallas=use_pallas,
        scan_impl=scan_impl,
        candidate_impl=candidate_impl,
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


def compress_bytes(
    data: bytes,
    hash_bits: int = DEFAULT_HASH_BITS,
    max_match: int = DEFAULT_MAX_MATCH,
    use_pallas: bool = False,
    scan_impl: str = "sequential",
) -> list[bytes]:
    """Deprecated: use :class:`repro.core.engine.LZ4Engine`.

    Thin compatibility wrapper over the batched engine; still returns the
    historical list-of-raw-LZ4-blocks shape (no frame, no passthrough).
    """
    import warnings

    from .engine import LZ4Engine

    warnings.warn(
        "compress_bytes is deprecated; use LZ4Engine.compress (framed) or "
        "LZ4Engine.compress_to_blocks", DeprecationWarning, stacklevel=2,
    )
    eng = LZ4Engine(
        hash_bits=hash_bits, max_match=max_match,
        use_pallas=use_pallas, scan_impl=scan_impl,
    )
    return eng.compress_to_blocks(data)
