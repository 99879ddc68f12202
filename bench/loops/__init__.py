"""How a cell's requests are sent, one module per kind of loop.

A mix names its ``loop``; `load` imports `bench/loops/<loop>.py`, so a new
kind of loop (open arrivals, bursts, many callers) is a new file, and a mix
that uses a loop already here is data alone.  A loop module has

    window(op, mix, seed, n, seconds, limit) -> (latencies_s, failed, user_bytes)

which sends requests for items in [0, n) to ``op.request`` until ``seconds``
have passed or ``limit`` requests were sent (``None``: no limit), with the
loop's parameters read from ``mix``, and returns the completed requests'
seconds, the count of failed requests and the user bytes done.  It refuses a
mix whose parameters it cannot generate.
"""
from __future__ import annotations

import importlib
import re
from types import ModuleType


def load(name: str) -> ModuleType:
    """The module `bench/loops/<name>.py`."""
    if not re.fullmatch(r"[a-z][a-z0-9_]*", name):
        raise ValueError(f"bench: loop name {name!r} is not a module name")
    return importlib.import_module(f"bench.loops.{name}")
