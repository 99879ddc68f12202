"""A closed loop with one caller: the next request goes out when the last one
returns, on items dealt by `bench.traffic.stream` (shuffled rounds, so every
seed sends the same multiset of requests in another order)."""
from __future__ import annotations

import sys
import time

from bench.traffic import stream


def window(op, mix: dict, seed: int, n: int, seconds: float, limit: int | None):
    if mix.get("callers") != 1:
        raise ValueError(f"closed loop: {mix.get('callers')!r} callers; this loop has one")
    items = stream(seed, n)
    lat, failed, done_bytes = [], 0, 0
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds and (limit is None or len(lat) + failed < limit):
        item = next(items)
        t = time.perf_counter()
        try:
            done_bytes += op.request(item)
        except Exception as e:  # a failed request is counted, and fails the run
            failed += 1
            print(f"bench: request for item {item} failed: {e!r}", file=sys.stderr, flush=True)
            continue
        lat.append(time.perf_counter() - t)
    return lat, failed, done_bytes
