#!/usr/bin/env python
"""Smoke run of the LZ4 write and read paths on TPU chips.

    python chip_smoke.py             # one chip: write, read and KV-cache paths
    python chip_smoke.py --chips 4   # the sharded fabric on four chips, only

Everything runs in this one process, through the entry points a user calls:
`LZ4Engine`, `LZ4DecodeEngine`, and the serving KV-cache offload, at the
paper's settings (hash_bits 8, max_match 36, pws 8, 64 KB blocks).  Every
output is checked byte for byte against the repository's plain references:
`compress_windowed` + `encode_block` for the writer, `decode_frame_serial`
for the reader.  Any failed check exits non-zero.

When JAX finds no TPU the script exits non-zero and prints no result.  The
last line of standard output is one JSON object::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

The timings printed on the way are those of a smoke run on the named device,
not benchmark numbers: they include compilation unless marked warm.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor

ROOT = os.path.dirname(os.path.abspath(__file__))

MIB = 1 << 20
HASH_BITS, MAX_MATCH, PWS = 8, 36, 8   # the paper's settings
# Sizes cut from 64 MiB and 2048 tokens to keep a run well inside 1200 s on
# one v5e: these take ~350 s there, about half of it in the per-block
# in-graph CRC scan of the verified device decodes.
WRITE_BYTES = 32 * MIB                 # write/read input, from the corpus
SAMPLE_BLOCKS = 32                     # seeded sample (plus first and last)
KV_ARCH, KV_TOKENS = "qwen3-1.7b", 1024
FABRIC_BLOCKS_PER_CHIP = 64


def log(msg: str) -> None:
    print(msg, flush=True)


COMPILES = [0]  # XLA backend compilations so far in this process


def count_compiles() -> None:
    import jax

    def on_event(event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            COMPILES[0] += 1

    jax.monitoring.register_event_duration_secs_listener(on_event)


def fail(msg: str) -> None:
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def check(cond: bool, msg: str) -> None:
    if not cond:
        fail(msg)


def tpu_devices(count: int):
    """The first ``count`` TPU devices; exit non-zero when there are none."""
    import jax

    try:
        devs = jax.devices()
    except RuntimeError as e:
        devs, err = [], e
    else:
        err = None
    if not devs or devs[0].platform != "tpu":
        found = devs[0].platform if devs else f"no backend ({err})"
        print(f"chip_smoke: no TPU found (JAX platform: {found})",
              file=sys.stderr, flush=True)
        sys.exit(2)
    if len(devs) < count:
        print(f"chip_smoke: {count} TPU chips needed, JAX sees {len(devs)}",
              file=sys.stderr, flush=True)
        sys.exit(2)
    return devs


def corpus_input(seed: int, nbytes: int) -> bytes:
    """``nbytes`` of the Calgary-substitute corpus, over seeds seed, seed+1, ..."""
    from repro.core.corpus import corpus_files

    parts, have, s = [], 0, seed
    while have < nbytes:
        for f in corpus_files(s).values():
            parts.append(f)
            have += len(f)
        corpus_files.cache_clear()
        s += 1
    return b"".join(parts)[:nbytes]


def sample_blocks(n_blocks: int, seed: int, k: int = SAMPLE_BLOCKS) -> list[int]:
    """A seeded sample of ``k`` block indices, plus the first and the last."""
    import numpy as np

    rng = np.random.default_rng(seed)
    pick = rng.choice(n_blocks, size=min(k, n_blocks), replace=False)
    return sorted({0, n_blocks - 1, *map(int, pick)})


def check_writer(frame: bytes, data: bytes, sample: list[int]) -> None:
    """Each sampled block's payload against the plain reference writer."""
    from repro.core import compress_windowed, encode_block, frame_info
    from repro.core.lz4_types import MAX_BLOCK

    blocks = frame_info(frame)["blocks"]
    check(len(blocks) == -(-len(data) // MAX_BLOCK), "block count")
    for i in sample:
        b = blocks[i]
        chunk = data[i * MAX_BLOCK: (i + 1) * MAX_BLOCK]
        payload = frame[b["offset"]: b["offset"] + b["csize"]]
        ref = encode_block(chunk, compress_windowed(
            chunk, hash_bits=HASH_BITS, pws=PWS, max_match=MAX_MATCH).sequences)
        if b["raw"]:
            check(len(ref) >= len(chunk), f"block {i}: raw but reference is smaller")
            check(payload == chunk, f"block {i}: raw payload differs from input")
        else:
            check(payload == ref, f"block {i}: payload differs from reference")


def check_reader_oracle(frame: bytes, data: bytes, sample: list[int]) -> None:
    """A sub-frame of the sampled blocks through `decode_frame_serial`."""
    from repro.core import decode_frame_serial, encode_frame, frame_info
    from repro.core.lz4_types import MAX_BLOCK

    blocks = [frame_info(frame)["blocks"][i] for i in sample]
    sub = encode_frame([frame[b["offset"]: b["offset"] + b["csize"]] for b in blocks],
                       [b["usize"] for b in blocks], [b["raw"] for b in blocks],
                       checksums=[b["crc"] for b in blocks])
    want = b"".join(data[i * MAX_BLOCK: (i + 1) * MAX_BLOCK] for i in sample)
    check(decode_frame_serial(sub) == want, "sub-frame differs under decode_frame_serial")


def on_device(arr, dev) -> bool:
    import jax

    return isinstance(arr, jax.Array) and arr.devices() == {dev}


def write_read_phase(dev, data: bytes, seed: int) -> None:
    """Compress ``data`` with `LZ4Engine`, check it, and decode it three ways."""
    import jax
    import numpy as np

    from repro.core import LZ4DecodeEngine, LZ4Engine

    eng = LZ4Engine(hash_bits=HASH_BITS, max_match=MAX_MATCH, pws=PWS,
                    micro_batch=32)
    c0, t0 = COMPILES[0], time.perf_counter()
    frame = eng.compress(data)
    dt = time.perf_counter() - t0
    st = eng.stats
    log(f"write: {len(data)} B -> {len(frame)} B (ratio {len(data) / len(frame):.4f}), "
        f"{st.blocks} blocks, {st.raw_blocks} raw, {st.dispatches} dispatches, "
        f"host_bytes {st.host_bytes}, {COMPILES[0] - c0} compiles, {dt:.3f} s")

    sample = sample_blocks(st.blocks, seed)
    t0 = time.perf_counter()
    check_writer(frame, data, sample)
    log(f"write check: {len(sample)} blocks equal encode_block(compress_windowed(...)) "
        f"({time.perf_counter() - t0:.3f} s on the host)")

    dec = LZ4DecodeEngine(executor="device")
    c0, t0 = COMPILES[0], time.perf_counter()
    out = dec.decode(frame)
    dt = time.perf_counter() - t0
    check(out == data, "device-executor decode differs from input")
    check(dec.stats.fallback_blocks == 0, f"{dec.stats.fallback_blocks} blocks fell back to host")
    log(f"read: LZ4DecodeEngine(executor='device').decode == input, "
        f"{dec.stats.dispatches} dispatches, {dec.stats.device_blocks} device blocks, "
        f"fallback 0, host_bytes {dec.stats.host_bytes}, {COMPILES[0] - c0} compiles, "
        f"{dt:.3f} s")

    for plan_on_device in (False, True):
        dec = LZ4DecodeEngine(executor="device", plan_on_device=plan_on_device)
        c0, t0 = COMPILES[0], time.perf_counter()
        arr = jax.block_until_ready(dec.decode_to_device(frame, verify=True))
        dt = time.perf_counter() - t0
        st = dec.stats
        check(on_device(arr, dev), f"plan_on_device={plan_on_device}: result not on {dev}")
        check(st.host_bytes == 0, f"plan_on_device={plan_on_device}: host_bytes {st.host_bytes}")
        check(st.fallback_blocks == 0,
              f"plan_on_device={plan_on_device}: {st.fallback_blocks} host fallbacks")
        check(np.asarray(arr).tobytes() == data,
              f"plan_on_device={plan_on_device}: decode_to_device differs from input")
        log(f"read: decode_to_device(verify=True, plan_on_device={plan_on_device}) == input "
            f"on {dev.device_kind}, {st.dispatches} dispatches, host_bytes 0, "
            f"fallback 0, {COMPILES[0] - c0} compiles, {dt:.3f} s")

    t0 = time.perf_counter()
    check_reader_oracle(frame, data, sample)
    log(f"read oracle: sub-frame of {len(sample)} blocks == input under "
        f"decode_frame_serial ({time.perf_counter() - t0:.3f} s)")


def kv_cache(arch: str, tokens: int, seed: int, filled: int):
    """The decode KV cache of ``arch`` at ``tokens`` slots, in the layout
    `lm.init_cache` / `lm.prefill` build and `offload_cache` takes: K and V
    of the first ``filled`` slots seeded normals (bf16), the rest empty."""
    import jax
    import jax.numpy as jnp

    from repro.configs.base import get_config
    from repro.models import lm

    cfg = get_config(arch)
    cache = lm.init_cache(cfg, batch=1, cache_len=tokens, dtype=jnp.bfloat16)
    entry = cache["layers"][0]["0"]
    live = (jnp.arange(tokens) < filled)[None, None, :, None, None]
    kk, kv = jax.random.split(jax.random.PRNGKey(seed))
    for name, key in (("k", kk), ("v", kv)):
        x = jax.random.normal(key, entry[name].shape, jnp.float32)
        entry[name] = jnp.where(live, x, 0).astype(jnp.bfloat16)
    pos = jnp.where(jnp.arange(tokens) < filled, jnp.arange(tokens), -1)
    entry["pos"] = jnp.broadcast_to(pos.astype(jnp.int32), entry["pos"].shape)
    cache["pos"] = jnp.int32(filled)
    return cache


def kv_phase(dev, seed: int, tokens: int = KV_TOKENS, arch: str = KV_ARCH) -> None:
    """`offload_cache` then `restore_cache(to_device=True, verify=True)`."""
    import jax
    import numpy as np

    from repro.core.decode_engine import default_decode_engine
    from repro.serving.engine import offload_cache, restore_cache

    filled = tokens * 3 // 4
    cache = kv_cache(arch, tokens, seed, filled)
    leaves = jax.tree.leaves(cache)
    nbytes = sum(x.nbytes for x in leaves)
    eng = default_decode_engine()
    before = (eng.totals.fallback_blocks, eng.totals.host_bytes)

    c0, t0 = COMPILES[0], time.perf_counter()
    blob, stats = offload_cache(cache)
    t_off = time.perf_counter() - t0
    t0 = time.perf_counter()
    restored = jax.block_until_ready(restore_cache(blob, to_device=True, verify=True))
    t_res = time.perf_counter() - t0

    after = (eng.totals.fallback_blocks, eng.totals.host_bytes)
    check(after == before, f"restore: host fallback/host_bytes moved {before} -> {after}")
    got = jax.tree.leaves(restored)
    check(len(got) == len(leaves), "restored tree has a different leaf count")
    for i, (a, b) in enumerate(zip(leaves, got)):
        check(on_device(b, dev), f"leaf {i}: not on {dev}")
        check(a.shape == b.shape and a.dtype == b.dtype, f"leaf {i}: shape/dtype")
        check(np.asarray(a).tobytes() == np.asarray(b).tobytes(), f"leaf {i}: bits differ")
    log(f"kv: {arch} cache, {tokens} slots ({filled} filled), {len(leaves)} leaves, "
        f"{nbytes} B -> {stats['compressed']} B (ratio {stats['ratio']:.4f}); "
        f"offload {t_off:.3f} s, restore to {dev.device_kind} {t_res:.3f} s, "
        f"{COMPILES[0] - c0} compiles in both; "
        f"every leaf bit-equal on device, fallback 0, host_bytes 0")


def corpus_while(warm, seed: int, nbytes: int) -> bytes:
    """`corpus_input` on a host thread while ``warm()`` runs the first
    dispatches, whose compilation releases the GIL."""
    t0 = time.perf_counter()
    with ThreadPoolExecutor(1) as pool:
        fut = pool.submit(corpus_input, seed, nbytes)
        warm()
        data = fut.result()
    log(f"setup: {len(data)} B of corpus (seeds from {seed}) and first compiles, "
        f"{time.perf_counter() - t0:.3f} s")
    return data


def single_chip(seed: int) -> None:
    from repro.core import LZ4DecodeEngine, LZ4Engine
    from repro.core.lz4_types import MAX_BLOCK

    dev = tpu_devices(1)[0]

    def warm():
        frame = LZ4Engine(micro_batch=32).compress(bytes(32 * MAX_BLOCK))
        for plan_on_device in (False, True):
            LZ4DecodeEngine(executor="device",
                            plan_on_device=plan_on_device).decode_to_device(frame)

    data = corpus_while(warm, seed, WRITE_BYTES)
    write_read_phase(dev, data, seed)
    kv_phase(dev, seed)


def four_chips(seed: int) -> None:
    from benchmarks.sharded_fabric import CHECKS, fabric_check
    from repro.core import LZ4Engine
    from repro.core.lz4_types import MAX_BLOCK
    from repro.distributed.sharding import make_mesh

    devs = tpu_devices(4)[:4]
    mesh = make_mesh((4,), ("data",), devices=devs)

    def warm():
        LZ4Engine(mesh=mesh).compress(bytes(4 * 32 * MAX_BLOCK))
        LZ4Engine().compress(bytes(32 * MAX_BLOCK))

    data = corpus_while(warm, seed, 4 * FABRIC_BLOCKS_PER_CHIP * MAX_BLOCK)
    pt = fabric_check(mesh, data)
    for name in CHECKS:
        check(pt[name], f"fabric: {name}")
    # Every chip held a shard's working set (the backend's own count).
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    check(None in peaks or min(peaks) > 0, f"fabric: a chip shows no memory use {peaks}")
    log(f"fabric: {pt['blocks']} blocks over {pt['devices']} {devs[0].device_kind} chips, "
        f"frame identical to shards=4 oracle and per-shard single-device frames, "
        f"mesh decode and serial decode == input; operands on devices "
        f"{pt['operand_devices']}, results on {pt['result_devices']}; peak bytes "
        f"per chip {peaks}; compress {pt['first_compress_s']:.3f} s, "
        f"{pt['dispatches']} dispatches; mesh decode {pt['decode_s']:.3f} s")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs the sharded fabric on four chips and nothing else")
    ap.add_argument("--seed", type=int, default=0, help="seed of every input")
    args = ap.parse_args(argv)

    import jax

    tpu_devices(args.chips)
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    try:
        from repro import compile_cache
    except ImportError as e:
        fail(f"the repository's sources are not next to chip_smoke.py ({e})")
    log(f"chip_smoke on {jax.devices()[0].device_kind} x {len(jax.devices())}; "
        f"compile cache {compile_cache.enable()}")
    count_compiles()
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips(args.seed)
    else:
        single_chip(args.seed)
    log(f"done in {time.perf_counter() - t0:.3f} s")
    d = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind, "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
