"""`default_decode_engine().decode_to_device(frame, verify=True)` of frames
written from the corpus pool in set-up.

The pool is drawn from the mix's ``pool_seed``, the same for every run: a
frame's decode work depends on its bytes (each micro-batch runs as many
pointer-doubling rounds as its deepest block needs, rounded up to a power of
two), so a pool drawn from the run's seed would measure another amount of
work in each run.  The run's seed deals the order of the requests
(`bench.traffic.stream`) and picks the frame and block the integrity probe
damages.
"""
from __future__ import annotations

import numpy as np

from bench import reference as ref
from bench.ops import corrupt, limits, on_device
from bench.payload import corpus_pool, sub_seeds


class Op:
    def __init__(self, cfg: dict, mix: dict, seed: int, devices: list, control: bool = False):
        self.cfg, self.mix, self.seed, self.devices = cfg, mix, seed, devices
        self.scheme = {k: cfg["scheme"][k] for k in ("hash_bits", "max_match", "pws")}
        self.verify = True
        if control:
            self.verify = mix["control"]["verify"]
        self.frame_bytes = mix["frame_bytes"]
        self.done: list[tuple[int, object]] = []

    def make_payload(self) -> bytes:
        p = self.cfg["payload"]
        return corpus_pool(self.mix["pool_seed"], p["pool_bytes"], p["corpus_seeds"])

    def build(self) -> None:
        from repro.core import LZ4Engine
        from repro.core.decode_engine import default_decode_engine

        self.writer = LZ4Engine(**self.scheme, micro_batch=self.cfg["engine"]["micro_batch"])
        self.engine = default_decode_engine()

    def warm(self) -> None:
        # The write graph's shape is a frame's, whatever its bytes: compiling
        # it here overlaps the compile, most of a first run's set-up, with
        # the payload thread.
        self.writer.compress(bytes(self.frame_bytes))

    def ready(self, pool: bytes) -> int:
        import jax

        self.pool = pool
        n = len(pool) // self.frame_bytes
        self.frames = [self.writer.compress(pool[i * self.frame_bytes: (i + 1) * self.frame_bytes])
                       for i in range(n)]
        # Every frame once through the device decode (its round buckets and
        # slices), and the in-graph CRC once.
        for fr in self.frames:
            jax.block_until_ready(self.engine.decode_to_device(fr, verify=False))
        jax.block_until_ready(self.engine.decode_to_device(self.frames[0], verify=self.verify))
        self.fallback0 = self.engine.totals.fallback_blocks
        return n

    def request_frame(self, frame: bytes):
        import jax

        return jax.block_until_ready(self.engine.decode_to_device(frame, verify=self.verify))

    def request(self, item: int) -> int:
        self.done.append((item, self.request_frame(self.frames[item])))
        return self.frame_bytes

    def check(self) -> dict:
        fallback = self.engine.totals.fallback_blocks - self.fallback0
        bad = off = 0
        for item, arr in self.done:
            off += not on_device(arr, self.devices[0])
            want = self.pool[item * self.frame_bytes: (item + 1) * self.frame_bytes]
            bad += np.asarray(arr).tobytes() != want
        # The probe alters a compressed block: its CRC is the one checked on
        # the device, which is what ``verify`` buys.
        rng = np.random.default_rng(sub_seeds(self.seed, 3)[2])
        item = int(rng.integers(len(self.frames)))
        blocks = ref.parse_frame(self.frames[item])["blocks"]
        block = int(rng.choice([i for i, b in enumerate(blocks) if not b["raw"]]))
        accepted = 0
        try:
            self.request_frame(corrupt(self.frames[item], block))
            accepted = 1
        except Exception:  # any refusal of the damaged frame is what is asked
            pass
        return limits(restore_bad=bad, off_device=off, host_fallback_blocks=fallback,
                      corrupt_accepted=accepted)
