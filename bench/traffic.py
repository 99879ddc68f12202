"""The general traffic generator: a mix file in, a request stream out.

A mix is a JSON file `bench/traffic/<mix>.json` of parameters.  It names
the ``op`` that serves a request (`bench/ops/<op>.py`) and the ``loop`` that
sends them (`bench/loops/<loop>.py`) with the loop's parameters; the rest
are the op's sizes.  Requests pick an item (an offset, a frame, a page) out
of the ``n`` the cell's set-up made; `stream` deals the items out as
shuffled rounds, so every seed sends the same multiset of requests in
another order.
"""
from __future__ import annotations

import json
import os

import numpy as np

from bench.payload import sub_seeds

HERE = os.path.dirname(os.path.abspath(__file__))


def load_mix(name: str, root: str = HERE) -> dict:
    with open(os.path.join(root, "traffic", f"{name}.json")) as f:
        mix = json.load(f)
    for key in ("op", "loop"):
        if not isinstance(mix.get(key), str):
            raise ValueError(f"mix {name}: no {key!r}")
    return mix


def stream(seed: int, n: int):
    """Item indices in [0, n): shuffled rounds of all n, endless."""
    rng = np.random.default_rng(sub_seeds(seed, 2)[1])
    while True:
        yield from (int(i) for i in rng.permutation(n))
