"""The operations a cell drives through the system under test, one module each.

A mix names its ``op``; `load` imports `bench/ops/<op>.py` and returns its
``Op`` class, so a new operation is a new file.  An op object makes its
payload, builds the program's own objects at the configuration's settings,
warms exactly the shapes its requests use, serves one request at a time, and
afterwards compares what the requests produced with the plain reference
(`bench/reference.py`).  Its interface:

- ``Op(cfg, mix, seed, devices, control=False)``;
- ``make_payload()``, on a host thread, while the main thread runs
  ``build()`` and ``warm()``;
- ``ready(payload)``, the rest of set-up; returns the number of items the
  requests pick from;
- ``request(item)``, one request; returns the user bytes it moved;
- ``check()``, the numbers compared, each with its limit:
  ``{name: {"value": v, "limit": l}}``; the run is correct when every value
  is at most its limit.

``control=True`` runs the control the mix names under ``control``, which
the check must refuse: the program at a setting that breaks one guarantee of
the configuration, or for a configuration that states a precision the plain
reference in the program's place at the precision below it.  The
benchmark's own runs never use it; `bench/control.py` does.

The helpers below are shared by the op modules.
"""
from __future__ import annotations

import importlib
import re

from bench import reference as ref

MAX_BLOCK = ref.MAX_BLOCK


def load(name: str) -> type:
    """The ``Op`` class of `bench/ops/<name>.py`."""
    if not re.fullmatch(r"[a-z][a-z0-9_]*", name):
        raise ValueError(f"bench: op name {name!r} is not a module name")
    return importlib.import_module(f"bench.ops.{name}").Op


def limits(**values) -> dict:
    """Exact comparisons: every number compared has the limit 0."""
    return {k: {"value": int(v), "limit": 0} for k, v in values.items()}


def on_device(arr, device) -> bool:
    import jax

    return isinstance(arr, jax.Array) and arr.devices() == {device}


def content_byte(payload: bytes, raw: bool, at: int = 0) -> int:
    """Index of a payload byte that the block's content copies verbatim: byte
    ``at`` of a raw block, the first literal of a compressed one."""
    if raw:
        return at
    i, lit = 1, payload[0] >> 4
    if lit == 15:
        while payload[i] == 255:
            i += 1
        i += 1
    return i


def corrupt(frame: bytes, block: int, at: int = 0) -> bytes:
    """``frame`` with one content byte of block ``block`` flipped: the frame
    still parses and decodes, to content that differs from the original."""
    blocks = ref.parse_frame(frame)["blocks"]
    b = blocks[block]
    pos = b["offset"] + content_byte(b["payload"], b["raw"], at)
    out = bytearray(frame)
    out[pos] ^= 0x5A
    return bytes(out)
