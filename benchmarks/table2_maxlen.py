"""Paper Table II: compression ratio of the length-capped extended-match stage
(caps 12/20/36/68 vs unbounded) over hash-table sizes.

Claim reproduced: the ratio loss SHRINKS as the cap grows (the paper picks 36
as the ratio/hardware-cost sweet spot).
"""
from __future__ import annotations

from repro.core import compress_greedy, plan_size

from .common import ENTRY_SWEEP, bits, corpus_ratio, corpus_subset, save_json

CAPS = [None, 12, 20, 36, 68]


def run(fast: bool = True) -> dict:
    blocks = corpus_subset(fast)
    rows = []
    for entries in ENTRY_SWEEP:
        hb = bits(entries)
        row = {"entries": entries}
        for cap in CAPS:
            r = corpus_ratio(
                lambda b: plan_size(compress_greedy(b, hash_bits=hb, max_match=cap)),
                blocks,
            )
            row["no_limit" if cap is None else f"limit_{cap}"] = round(r, 4)
        rows.append(row)
    # attenuation at cap=36 (paper: 4.46%..8.23%)
    att36 = [
        100 * (r["no_limit"] - r["limit_36"]) / r["no_limit"] for r in rows
    ]
    out = {
        "table": "II",
        "paper_attenuation_36_range_pct": [4.46, 8.23],
        "rows": rows,
        "attenuation_36_pct": [round(a, 3) for a in att36],
        "monotone_in_cap": all(
            r["limit_12"] <= r["limit_20"] <= r["limit_36"] <= r["limit_68"] <= r["no_limit"]
            for r in rows
        ),
    }
    save_json("table2", out)
    return out


if __name__ == "__main__":
    from repro import compile_cache

    compile_cache.enable()
    import json

    print(json.dumps(run(), indent=1))
