"""`OffloadedCacheReader(blob, to_device=True, verify=True).read_leaf` of one
page's K and V, from paused sessions offloaded in set-up."""
from __future__ import annotations

import random

import numpy as np

from bench import reference as ref
from bench.ops import MAX_BLOCK, corrupt, limits, on_device
from bench.payload import kv_cache, sub_seeds


class Op:
    def __init__(self, cfg: dict, mix: dict, seed: int, devices: list, control: bool = False):
        self.cfg, self.mix, self.seed, self.devices = cfg, mix, seed, devices
        self.kv = cfg["kv_cache"]
        self.control = mix["control"] if control else None
        self.page = mix["page_slots"]
        # A seeded reservoir of the window's pages: holding every page read
        # would grow the heap and device memory through the window.
        self.keep = mix["check_sample_pages"]
        self.done: list[tuple[int, object, object]] = []
        self.served = 0
        self.pick = random.Random(sub_seeds(seed, 4)[3])

    def make_payload(self) -> None:
        return None

    def build(self) -> None:
        pass

    def warm(self) -> None:
        pass

    def _leaf(self, name: str) -> int:
        """Index of layer leaf ``name`` among the cache tree's leaves."""
        import jax

        paths = [jax.tree_util.keystr(p) for p, _ in
                 jax.tree_util.tree_flatten_with_path(self.cache)[0]]
        return paths.index(f"['layers'][0]['0']['{name}']")

    @property
    def pages(self) -> int:
        """Live pages: layers x sessions x filled pages per session."""
        kv = self.kv
        return kv["num_hidden_layers"] * kv["sessions"] * (kv["filled"] // self.page)

    def _span(self, item: int) -> tuple[tuple[int, int, int], int, int]:
        kv, per = self.kv, self.kv["filled"] // self.page
        layer, rest = divmod(item, kv["sessions"] * per)
        session, page = divmod(rest, per)
        row = kv["num_key_value_heads"] * kv["head_dim"]
        start = ((layer * kv["sessions"] + session) * kv["slots"] + page * self.page) * row
        return (layer, session, page), start, self.page * row

    def ready(self, _pool) -> int:
        import jax

        from repro.serving.engine import offload_cache

        self.cache = jax.block_until_ready(kv_cache(self.kv, self.seed))
        self.k, self.v = self._leaf("k"), self._leaf("v")
        self.blob, _ = offload_cache(self.cache)
        self.reader = self._reader(self.blob)
        for item in range(self.pages):  # every page once: its blocks' paths and slices
            self._read(self.reader, item)
        return self.pages

    def _reader(self, blob):
        if self.control:
            return ReferencePages(blob, self.control["dtype"], self.devices[0])
        from repro.serving.engine import OffloadedCacheReader

        return OffloadedCacheReader(blob, to_device=True, verify=True)

    def _read(self, reader, item: int):
        import jax

        _, start, count = self._span(item)
        return jax.block_until_ready((reader.read_leaf(self.k, start, count),
                                      reader.read_leaf(self.v, start, count)))

    def request(self, item: int) -> int:
        k, v = self._read(self.reader, item)
        self.served += 1
        if len(self.done) < self.keep:
            self.done.append((item, k, v))
        else:
            j = self.pick.randrange(self.served)
            if j < self.keep:
                self.done[j] = (item, k, v)
        return k.nbytes + v.nbytes

    def check(self) -> dict:
        src = {"k": np.asarray(self.cache["layers"][0]["0"]["k"]),
               "v": np.asarray(self.cache["layers"][0]["0"]["v"])}
        bad = off = 0
        for item, k, v in self.done:
            (layer, session, page), _, _ = self._span(item)
            rows = slice(page * self.page, (page + 1) * self.page)
            for name, got in (("k", k), ("v", v)):
                off += not on_device(got, self.devices[0])
                want = src[name][layer, session, rows].reshape(-1)
                bad += np.asarray(got).tobytes() != want.tobytes()
        rng = np.random.default_rng(sub_seeds(self.seed, 3)[2])
        item = int(rng.integers(self.pages))
        _, start, _ = self._span(item)
        nbytes = src["k"].dtype.itemsize
        block, at = divmod(start * nbytes, MAX_BLOCK)
        treedef, blobs = self.blob
        blobs = list(blobs)
        blobs[self.k] = dict(blobs[self.k], frame=corrupt(blobs[self.k]["frame"], block, at))
        accepted = 0
        try:
            self._read(self._reader([treedef, blobs]), item)
            accepted = 1
        except Exception:  # any refusal of the damaged page is what is asked
            pass
        return limits(pages_bad=bad, off_device=off, corrupt_accepted=accepted)


class ReferencePages:
    """The control of a page read: the plain reference reader (frame table,
    raw payload or reference decode, no integrity check) in the program's
    place, its pages rounded through ``dtype``, a precision below the one
    the configuration states, on their way to the device."""

    def __init__(self, blob, dtype: str, device):
        _, self.blobs = blob
        self.tables = [None] * len(self.blobs)
        self.dtype, self.device = dtype, device

    def read_leaf(self, i: int, start: int, count: int):
        import jax

        b = self.blobs[i]
        if self.tables[i] is None:
            self.tables[i] = ref.parse_frame(b["frame"])["blocks"]
        kind = np.dtype(b["dtype"])
        lo, hi = start * kind.itemsize, (start + count) * kind.itemsize
        out, pos = bytearray(), 0
        for blk in self.tables[i]:
            end = pos + blk["usize"]
            if end > lo and pos < hi:
                data = blk["payload"] if blk["raw"] else ref.decode_block(blk["payload"], blk["usize"])
                out += data[max(lo - pos, 0): min(hi, end) - pos]
            pos = end
        page = jax.device_put(np.frombuffer(bytes(out), kind), self.device)
        return page.astype(self.dtype).astype(kind)
