"""The four-chip write cell on four virtual CPU devices, at a size a test can
hold, and the version-4 reference it is checked with.

The cell runs in one subprocess, since the virtual devices must be asked
for before JAX starts: the program's path comes out correct with no compile
in the window, and the control and each fault planted under the timed path
come out not correct.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from bench import harness
from bench import reference_v4 as ref4

_RUNS = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import copy, json, struct, time
    import jax
    from bench import harness
    from bench import reference_v4 as ref4

    harness.enable_cache = lambda: "off in tests"
    spec = harness.load_json(harness.ROOT, "BENCHMARK.json")
    _, cfg, mix = harness.cell(spec, "calgary.write-4chip")
    cfg, mix = copy.deepcopy(cfg), copy.deepcopy(mix)
    cfg["payload"].update(pool_bytes=16 * 65536, corpus_seeds=1)
    cfg["engine"]["micro_batch"] = 2
    mix.update(object_bytes=16 * 65536, check_sample_blocks=4)

    def set_shard(frame, k, shard):
        out = bytearray(frame)
        struct.pack_into("<I", out, 21 + 16 * k + 12, shard)  # entry k's shard id
        return bytes(out)

    def shard_altered(frame):
        f = ref4.parse_frame(frame)
        return set_shard(frame, 0, f["blocks"][0]["shard"] + 1)

    def shards_swapped(frame):
        f = ref4.parse_frame(frame)
        ids = [b["shard"] for b in f["blocks"]]
        k = ids.index(1)  # the first block of shard 1; block k - 1 is shard 0's last
        return set_shard(set_shard(frame, k - 1, ids[k]), k, ids[k - 1])

    def payload_flipped(frame):
        b = next(b for b in ref4.parse_frame(frame)["blocks"] if not b["raw"])
        out = bytearray(frame)
        out[b["offset"] + b["csize"] - 1] ^= 1
        return bytes(out)

    from repro.core import LZ4Engine
    compress = LZ4Engine.compress
    real_setup = harness.setup
    out = {}
    for case, fault in [("program", None), ("control", None),
                        ("shard_altered", shard_altered),
                        ("shards_swapped", shards_swapped),
                        ("payload_flipped", payload_flipped)]:
        marks = {}

        def setup(op, fault=fault, marks=marks):
            n = real_setup(op)
            if fault is not None:
                LZ4Engine.compress = lambda self, data: fault(compress(self, data))
            marks["compiles"] = harness.Compiles.count()
            return n

        harness.setup = setup
        try:
            r = harness.run("calgary.write-4chip", 2**33 + 17, 0.5, False, time.perf_counter(),
                            control=case == "control", spec=spec, devices=jax.devices()[:4],
                            cfg=cfg, mix=mix)
        finally:
            LZ4Engine.compress = compress
        r["compiles_after_setup"] = harness.Compiles.count() - marks["compiles"]
        out[case] = r
    print("RESULT:" + json.dumps(out))
""")


@pytest.fixture(scope="module")
def runs():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=os.pathsep.join(
        [harness.ROOT, os.path.join(harness.ROOT, "src")]))
    p = subprocess.run([sys.executable, "-c", _RUNS], cwd=harness.ROOT, env=env,
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    line = next(l for l in p.stdout.splitlines() if l.startswith("RESULT:"))
    return json.loads(line[len("RESULT:"):])


def test_program_is_correct_on_four_devices(runs):
    r = runs["program"]
    assert r["correct"] and r["attempted"] > 0 and r["failed"] == 0
    assert r["compiles_after_setup"] == 0
    assert r["device"]["count"] == 4 and "setup_s" in r["metrics"]
    assert "write_MiBps" in r["metrics"]
    assert set(r["checks"]) == {"frame_bad_blocks", "shard_bad_blocks", "scheme_bad_blocks",
                                "off_mesh"}
    assert all(c["value"] == 0 == c["limit"] for c in r["checks"].values())


@pytest.mark.parametrize("case, check", [("control", "scheme_bad_blocks"),
                                         ("shard_altered", "shard_bad_blocks"),
                                         ("shards_swapped", "shard_bad_blocks"),
                                         ("payload_flipped", "frame_bad_blocks")])
def test_control_and_planted_faults_are_not_correct(runs, case, check):
    r = runs[case]
    assert not r["correct"], r["checks"]
    assert r["checks"][check]["value"] > r["checks"][check]["limit"]


@pytest.mark.parametrize("n_blocks, shards", [(256, 4), (16, 4), (7, 4), (10, 4), (2, 4),
                                              (0, 4), (5, 3), (1, 1)])
def test_reference_reads_the_programs_v4_frames(n_blocks, shards):
    """The reference's parser and partition rule agree with the program's
    version-4 writer and its `partition_blocks`, for even and uneven splits
    and for fewer blocks than shards."""
    from repro.core.frame import encode_frame, frame_info
    from repro.distributed.fabric import partition_blocks

    rule = ref4.shard_ids(n_blocks, shards)
    want = [sl.shard for sl in partition_blocks(n_blocks, shards) for _ in range(sl.count)]
    assert rule == want
    payloads = [bytes([k % 251]) * (k % 5 + 1) for k in range(n_blocks)]
    raws = [k % 2 == 0 for k in range(n_blocks)]
    frame = encode_frame(payloads, [len(p) for p in payloads], raws,
                         checksums=[k * 7919 for k in range(n_blocks)],
                         shards=rule, shard_count=shards)
    f, info = ref4.parse_frame(frame), frame_info(frame)
    assert f["version"] == info["version"] == 4
    assert f["shard_count"] == info["shard_count"] == shards
    assert f["content_size"] == sum(len(p) for p in payloads)
    assert [b["shard"] for b in f["blocks"]] == [b["shard"] for b in info["blocks"]] == rule
    for b, i, p, raw in zip(f["blocks"], info["blocks"], payloads, raws):
        assert (b["usize"], b["csize"], b["raw"], b["crc"], b["offset"]) == \
            (i["usize"], i["csize"], i["raw"], i["crc"], i["offset"])
        assert b["payload"] == p and b["raw"] == raw


def test_pool_is_the_corpus_pool_of_the_seed():
    """The op makes its corpus seeds in parallel; the bytes are
    `corpus_pool`'s, in its order."""
    from bench.ops import write_mesh
    from bench.payload import corpus_pool

    _, cfg, mix = harness.cell(harness.load_json(harness.ROOT, "BENCHMARK.json"),
                               "calgary.write-4chip")
    cfg = dict(cfg, payload=dict(cfg["payload"], pool_bytes=2 << 20, corpus_seeds=2))
    op = write_mesh.Op(cfg, mix, 2**33 + 5, devices=[])
    assert op.make_payload() == corpus_pool(2**33 + 5, 2 << 20, 2)


def test_reference_refuses_other_versions_and_truncation():
    from repro.core.frame import encode_frame

    from bench.reference import FormatError

    v3 = encode_frame([b"abc"], [3], [True], checksums=[1])
    v4 = encode_frame([b"abc"], [3], [True], checksums=[1], shards=[0], shard_count=1)
    with pytest.raises(FormatError, match="version 3"):
        ref4.parse_frame(v3)
    for cut in (1, 4, len(v4) - 21):
        with pytest.raises(FormatError):
            ref4.parse_frame(v4[:-cut])
