"""Paper Table I: single-match-per-window vs multi-match compression ratio,
swept over hash-table sizes (64..8192), PWS=8, 64 KB blocks.

Claim reproduced: attenuation is small (sub-%-to-few-%) and GROWS with the
number of hash-table entries (more candidates -> more multi-match windows).
"""
from __future__ import annotations

from repro.core import compress_greedy, compress_windowed, plan_size

from .common import ENTRY_SWEEP, bits, corpus_ratio, corpus_subset, save_json


def run(fast: bool = True) -> dict:
    blocks = corpus_subset(fast)
    rows = []
    for entries in ENTRY_SWEEP:
        hb = bits(entries)
        multi = corpus_ratio(lambda b: plan_size(compress_greedy(b, hash_bits=hb)), blocks)
        single = corpus_ratio(
            lambda b: plan_size(compress_windowed(b, hash_bits=hb, max_match=None).sequences),
            blocks,
        )
        rows.append({
            "entries": entries,
            "multi_match": round(multi, 4),
            "single_match": round(single, 4),
            "attenuation_pct": round(100 * (multi - single) / multi, 3),
        })
    out = {
        "table": "I",
        "paper_attenuation_range_pct": [0.86, 5.39],
        "rows": rows,
        "trend_ok": all(
            rows[i]["attenuation_pct"] <= rows[i + 1]["attenuation_pct"] + 0.6
            for i in range(len(rows) - 1)
        ),
    }
    save_json("table1", out)
    return out


if __name__ == "__main__":
    from repro import compile_cache

    compile_cache.enable()
    import json

    print(json.dumps(run(), indent=1))
