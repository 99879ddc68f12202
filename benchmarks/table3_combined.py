"""Paper Table III: combined scheme (single-match + cap 36) vs GitHub software
LZ4, over hash-table sizes.  The combined scheme here is the JAX engine
itself (vectorized, jit), proving the production path achieves the paper's
ratios; its records are golden-model-exact (tests/test_lz4_jax.py).

Claim reproduced: combined attenuation ~5-12%, growing with table size.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core import compress_greedy, plan_size
from repro.core.jax_compressor import compress_block_records, pad_block

from .common import ENTRY_SWEEP, bits, corpus_ratio, corpus_subset, save_json


def _jax_size(block: bytes, hb: int) -> int:
    buf, n = pad_block(block)
    rec = compress_block_records(
        jnp.asarray(buf), jnp.int32(n), hash_bits=hb, max_match=36
    )
    return int(rec.size)


def run(fast: bool = True) -> dict:
    blocks = corpus_subset(fast)
    rows = []
    for entries in ENTRY_SWEEP:
        hb = bits(entries)
        github = corpus_ratio(lambda b: plan_size(compress_greedy(b, hash_bits=hb)), blocks)
        combined = corpus_ratio(lambda b: _jax_size(b, hb), blocks)
        rows.append({
            "entries": entries,
            "github": round(github, 4),
            "combined": round(combined, 4),
            "attenuation_pct": round(100 * (github - combined) / github, 3),
        })
    out = {
        "table": "III",
        "paper_attenuation_range_pct": [4.93, 11.68],
        "rows": rows,
        "grows_with_entries": rows[-1]["attenuation_pct"] > rows[0]["attenuation_pct"],
    }
    save_json("table3", out)
    return out


if __name__ == "__main__":
    from repro import compile_cache

    compile_cache.enable()
    import json

    print(json.dumps(run(), indent=1))
