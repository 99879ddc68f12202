"""`LZ4Engine.compress` of objects cut from the corpus pool."""
from __future__ import annotations

import numpy as np

from bench import reference as ref
from bench.ops import MAX_BLOCK, limits
from bench.payload import corpus_pool, sub_seeds


class Op:
    def __init__(self, cfg: dict, mix: dict, seed: int, devices: list, control: bool = False):
        self.scheme = {k: cfg["scheme"][k] for k in ("hash_bits", "max_match", "pws")}
        self.settings = dict(self.scheme, micro_batch=cfg["engine"]["micro_batch"])
        if control:
            self.settings.update(mix["control"])
        self.cfg, self.mix, self.seed, self.devices = cfg, mix, seed, devices
        self.obj, self.align = mix["object_bytes"], mix["align_bytes"]
        self.done: list[tuple[int, bytes]] = []

    def make_payload(self) -> bytes:
        p = self.cfg["payload"]
        return corpus_pool(self.seed, p["pool_bytes"], p["corpus_seeds"])

    def build(self) -> None:
        from repro.core import LZ4Engine

        self.engine = LZ4Engine(**self.settings)

    def warm(self) -> None:
        """The write graph at its micro-batch, and every length the drain
        fetches a compressed block with: one micro-batch of blocks whose
        compressed sizes step through the fetch lengths, then each length on
        an (uncommitted, as the write graph's output is) zero buffer."""
        import jax.numpy as jnp

        from repro.core.engine import FETCH_QUANTUM, fetch_row_prefix
        from repro.core.jax_compressor import OUT_CAP

        rows = self.settings["micro_batch"]
        rng = np.random.default_rng(0)
        blocks = []
        for j in range(rows):
            noise = min(j % 17, 16) * 4096
            blocks.append(rng.integers(0, 256, noise, np.uint8).tobytes()
                          + bytes(MAX_BLOCK - noise))
        self.engine.compress(b"".join(blocks))
        out = jnp.zeros((rows, OUT_CAP), jnp.uint8)
        for size in range(FETCH_QUANTUM, OUT_CAP + FETCH_QUANTUM, FETCH_QUANTUM):
            fetch_row_prefix(out, 0, min(size, OUT_CAP))

    def ready(self, pool: bytes) -> int:
        self.pool = pool
        return (len(pool) - self.obj) // self.align + 1

    def request(self, item: int) -> int:
        off = item * self.align
        frame = self.engine.compress(self.pool[off: off + self.obj])
        self.done.append((off, frame))
        return self.obj

    def frames(self) -> list[tuple[int, dict | None]]:
        out = []
        for off, frame in self.done:
            try:
                out.append((off, ref.parse_frame(frame)))
            except ref.FormatError:
                out.append((off, None))
        return out

    def check(self) -> dict:
        n_blocks = -(-self.obj // MAX_BLOCK)
        frame_bad, sample = 0, []
        for off, f in self.frames():
            data = self.pool[off: off + self.obj]
            if f is None or len(f["blocks"]) != n_blocks or f["version"] != 3:
                frame_bad += n_blocks
                continue
            for i, b in enumerate(f["blocks"]):
                chunk = data[i * MAX_BLOCK: (i + 1) * MAX_BLOCK]
                ok = b["usize"] == len(chunk) and b["crc"] == ref.crc32(chunk)
                if ok and b["raw"]:
                    ok = b["payload"] == chunk
                elif ok:
                    try:
                        ok = ref.decode_block(b["payload"], b["usize"]) == chunk
                    except ref.FormatError:
                        ok = False
                frame_bad += not ok
                sample.append((chunk, b))
        rng = np.random.default_rng(sub_seeds(self.seed, 3)[2])
        take = rng.choice(len(sample), min(len(sample), self.mix["check_sample_blocks"]),
                          replace=False) if sample else []
        scheme_bad = 0
        for j in take:
            chunk, b = sample[j]
            want = ref.encode_paper_block(chunk, **self.scheme)
            if b["raw"]:
                scheme_bad += len(want) < len(chunk)
            else:
                scheme_bad += b["payload"] != want
        return limits(frame_bad_blocks=frame_bad, scheme_bad_blocks=scheme_bad)
