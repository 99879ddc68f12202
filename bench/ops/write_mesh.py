"""`LZ4Engine(mesh=...).compress` of objects cut from a corpus ring, on a mesh
of the cell's chips.

Request ``k`` compresses the pool rotated to the ``k``-th aligned offset, so
an object the size of the pool holds each pool block once.  The check reads
the version-4 frames with `bench/reference_v4.py`: the table and payloads
against the input, the shard column against the partition rule, a sample of
blocks against the paper's writer, and whether each request ran as one
sharded program over all the cell's chips.
"""
from __future__ import annotations

import os
import subprocess
import sys
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from bench import reference as ref
from bench import reference_v4 as ref4
from bench.ops import MAX_BLOCK, limits
from bench.ops import write
from bench.payload import sub_seeds


ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CORPUS_SEED = ("import sys; from bench.corpus import corpus_files; "
               "sys.stdout.buffer.write(b''.join(corpus_files(int(sys.argv[1])).values()))")


class Op(write.Op):
    def __init__(self, cfg: dict, mix: dict, seed: int, devices: list, control: bool = False):
        if cfg["frame_version"] != ref4.VERSION:
            raise ValueError(f"bench: no reference for frame version {cfg['frame_version']}")
        super().__init__(cfg, mix, seed, devices, control)
        self.placed: list[bool] = []

    def make_payload(self) -> bytes:
        """`corpus_pool`'s bytes, its corpus seeds made side by side, one
        fresh interpreter each (no JAX in them): 13 seeds one after another
        take ~20 s of a set-up that also warms four chips."""
        p = self.cfg["payload"]
        procs = [subprocess.Popen([sys.executable, "-c", CORPUS_SEED, str(s)], cwd=ROOT,
                                  stdout=subprocess.PIPE)
                 for s in sub_seeds(self.seed, p["corpus_seeds"])]
        parts = [proc.communicate()[0] for proc in procs]
        if any(proc.returncode for proc in procs):
            raise RuntimeError("bench: a corpus seed failed to generate")
        pool = b"".join(parts)
        if len(pool) < p["pool_bytes"]:
            raise ValueError(f"{len(procs)} corpus seeds give {len(pool)} bytes, "
                             f"fewer than the pool's {p['pool_bytes']}")
        return pool[: p["pool_bytes"]]

    def build(self) -> None:
        from repro.core import LZ4Engine
        from repro.distributed.sharding import make_mesh

        m = self.cfg["mesh"]
        self.mesh = make_mesh(tuple(m["shape"]), tuple(m["axes"]), devices=self.devices)
        self.engine = LZ4Engine(mesh=self.mesh, shard_axes=tuple(m["shard_axes"]),
                                **self.settings)
        blocks = -(-self.obj // MAX_BLOCK)
        per_shard = -(-blocks // self.engine.shards)
        self.steps = -(-per_shard // self.settings["micro_batch"])

    def warm(self) -> None:
        """The sharded write program at its micro-batch (one step of zero
        blocks: the compiled shape is all that matters) and, beside its
        compile on another thread, every length the drain fetches a
        compressed block with, on every chip: a zero buffer laid over the
        mesh as the write program's output is, fetched from each chip's own
        piece."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec

        from repro.core.engine import FETCH_QUANTUM, fetch_row_prefix
        from repro.core.jax_compressor import OUT_CAP

        rows, shards = self.settings["micro_batch"], self.engine.shards
        out = jax.device_put(np.zeros((rows * shards, OUT_CAP), np.uint8),
                             NamedSharding(self.mesh, PartitionSpec(self.engine.shard_axes)))

        def every_length():
            for piece in out.addressable_shards:
                for size in range(FETCH_QUANTUM, OUT_CAP + FETCH_QUANTUM, FETCH_QUANTUM):
                    fetch_row_prefix(piece.data, 0, min(size, OUT_CAP))

        with ThreadPoolExecutor(1) as pool:
            fetched = pool.submit(every_length)
            self.engine.compress(bytes(rows * shards * MAX_BLOCK))
            fetched.result()

    def ready(self, pool: bytes) -> int:
        if not self.align <= self.obj <= len(pool) or len(pool) % self.align:
            raise ValueError("bench: the ring must hold whole aligned items and one object")
        self.pool, self.ring = pool, pool + pool[: self.obj]
        return len(pool) // self.align

    def request(self, item: int) -> int:
        off = item * self.align
        frame = self.engine.compress(self.ring[off: off + self.obj])
        st = self.engine.stats
        self.done.append((off, frame))
        self.placed.append(st.shards == len(self.devices) and st.dispatches == self.steps)
        return self.obj

    def check(self) -> dict:
        n_blocks = -(-self.obj // MAX_BLOCK)
        shards = len(self.devices)
        rule = ref4.shard_ids(n_blocks, shards)
        frame_bad = shard_bad = 0
        sample, crcs, decodes = [], {}, {}
        ring = memoryview(self.ring)
        for off, frame in self.done:
            try:
                f = ref4.parse_frame(frame)
            except ref.FormatError:
                f = None
            if f is None or len(f["blocks"]) != n_blocks:
                frame_bad += n_blocks
                shard_bad += n_blocks
                continue
            if f["shard_count"] != shards:
                shard_bad += n_blocks
            else:
                shard_bad += sum(b["shard"] != s for b, s in zip(f["blocks"], rule))
            for i, b in enumerate(f["blocks"]):
                at = off + i * MAX_BLOCK
                chunk = ring[at: min(at + MAX_BLOCK, off + self.obj)]
                key = (at % len(self.pool), len(chunk))
                if key not in crcs:
                    crcs[key] = ref.crc32(chunk)
                ok = b["usize"] == len(chunk) and b["crc"] == crcs[key]
                if ok and b["raw"]:
                    ok = b["payload"] == chunk
                elif ok:
                    # One pool block recurs in every object: decode each
                    # distinct payload of it once.
                    dkey = key + (b["payload"],)
                    if dkey not in decodes:
                        try:
                            decodes[dkey] = ref.decode_block(b["payload"], b["usize"]) == chunk
                        except ref.FormatError:
                            decodes[dkey] = False
                    ok = decodes[dkey]
                frame_bad += not ok
                sample.append((chunk, b))
        rng = np.random.default_rng(sub_seeds(self.seed, 3)[2])
        take = rng.choice(len(sample), min(len(sample), self.mix["check_sample_blocks"]),
                          replace=False) if sample else []
        scheme_bad = 0
        for j in take:
            chunk, b = sample[j]
            want = ref.encode_paper_block(bytes(chunk), **self.scheme)
            if b["raw"]:
                scheme_bad += len(want) < len(chunk)
            else:
                scheme_bad += b["payload"] != want
        on_mesh = set(self.mesh.devices.flat) == set(self.devices)
        off_mesh = sum(not (p and on_mesh) for p in self.placed)
        return limits(frame_bad_blocks=frame_bad, shard_bad_blocks=shard_bad,
                      scheme_bad_blocks=scheme_bad, off_mesh=off_mesh)
