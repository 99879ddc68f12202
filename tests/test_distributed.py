"""Distribution tests: sharding rules, cache specs, and a subprocess dry-run
smoke on fake devices (the main pytest process keeps its single device)."""
import json
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs.base import SHAPES, get_config, cells, LONG_CONTEXT_ARCHS
from repro.distributed import sharding as sh


class TestSpecRules:
    def test_param_specs_cover_tree(self):
        from repro.models import lm

        cfg = get_config("internlm2-1.8b")
        params_s = jax.eval_shape(lambda: lm.init_params(jax.random.PRNGKey(0), cfg))
        mesh = sh.single_device_mesh()
        specs = sh.param_specs(params_s, fsdp=True, mesh=mesh)
        n_leaves = len(jax.tree.leaves(params_s))
        n_specs = len(jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P)))
        assert n_specs == n_leaves

    def test_tp_on_heads_and_ff(self):
        from repro.models import lm

        cfg = get_config("qwen3-1.7b")
        params_s = jax.eval_shape(lambda: lm.init_params(jax.random.PRNGKey(0), cfg))
        mesh = sh.single_device_mesh()
        specs = sh.param_specs(params_s, fsdp=True, mesh=mesh)
        layer = specs["segments"][0]["layers"]["0"]
        assert layer["attn"]["wq"]["w"] == P(None, "data", "model")
        assert layer["attn"]["wo"]["w"] == P(None, "model", "data")
        assert layer["mlp"]["w_gate"]["w"] == P(None, "data", "model")
        assert specs["embed"] == P("model", "data")

    def test_moe_expert_specs(self):
        from repro.models import lm

        cfg = get_config("mixtral-8x7b")
        params_s = jax.eval_shape(lambda: lm.init_params(jax.random.PRNGKey(0), cfg))
        specs = sh.param_specs(params_s, fsdp=True, mesh=sh.single_device_mesh())
        layer = specs["segments"][0]["layers"]["0"]
        assert layer["moe"]["w1"]["w"] == P(None, None, "data", "model")
        assert layer["moe"]["w2"]["w"] == P(None, None, "model", "data")

    def test_sanitize_drops_uneven(self):
        import types

        from repro.launch.steps import sanitize_spec

        mesh = types.SimpleNamespace(shape={"data": 16, "model": 16, "pod": 2})
        # whisper vocab 51865 is odd -> model axis must be dropped
        assert sanitize_spec(P("model", None), (51865, 768), mesh) == P(None, None)
        assert sanitize_spec(P("model", None), (92544, 768), mesh) == P("model", None)
        # tuple axes: 256-way sharding of 524288 divides, 1500 does not
        assert sanitize_spec(P(None, ("data", "model")), (1, 524288), mesh) == \
            P(None, ("data", "model"))
        assert sanitize_spec(P(None, ("data", "model")), (1, 1500), mesh) == P(None, None)

    def test_cell_enumeration(self):
        cs = cells()
        assert len(cs) == 35  # 30 + 5 long-context
        skipped = [c for c in cells(include_skipped=True) if c not in cs]
        assert all(s[1] == "long_500k" and s[0] not in LONG_CONTEXT_ARCHS for s in skipped)
        assert len(cells(include_skipped=True)) == 40


_SUBPROC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
    import json
    import jax
    from repro.configs.base import get_config, SHAPES, ShapeConfig
    from repro.distributed.sharding import use_mesh
    from repro.launch import steps as steps_mod
    from repro.launch.dryrun import parse_collectives, _lower_cell
    import dataclasses

    from repro.distributed.sharding import make_mesh
    mesh = make_mesh((2, 2, 4), ("pod", "data", "model"))
    cfg = dataclasses.replace(
        get_config("{arch}").reduced(), fsdp=True,
        d_model=128, n_heads=8, head_dim=16, d_ff=256 if get_config("{arch}").d_ff else 0,
        vocab_size=1024,
    )
    shape = ShapeConfig("t", seq_len=64, global_batch=8, mode="{mode}")
    with use_mesh(mesh):
        lowered = _lower_cell(cfg, shape, mesh)
        compiled = lowered.compile()
        coll = parse_collectives(compiled.as_text())
        print("RESULT:" + json.dumps({{
            "ok": True,
            "n_coll": sum(v["count"] for v in coll.values()),
            "ops": sorted(coll.keys()),
        }}))
""")


@pytest.mark.parametrize("arch,mode", [
    ("internlm2-1.8b", "train"),
    ("mixtral-8x7b", "train"),
    ("gemma2-9b", "decode"),
    ("xlstm-125m", "prefill"),
])
def test_subprocess_multipod_smoke(arch, mode):
    """Reduced configs compile against a (pod,data,model) mesh with real
    collectives — proves the sharding rules are coherent end to end."""
    code = _SUBPROC.format(arch=arch, mode=mode)
    proc = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, timeout=500,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "HOME": "/root", "JAX_PLATFORMS": "cpu"},
        cwd="/root/repo",
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT:")][0]
    result = json.loads(line[len("RESULT:"):])
    assert result["ok"]
    if mode == "train":
        assert result["n_coll"] > 0  # DP gradient reduction must exist


# ---------------------------------------------------------------------------
# Sharded compression fabric (distributed/fabric.py, frame v4).
# ---------------------------------------------------------------------------

from repro.core.decode_engine import FrameReader, LZ4DecodeEngine  # noqa: E402
from repro.core.engine import LZ4Engine  # noqa: E402
from repro.core.frame import VERSION_V4, decode_frame_serial, frame_info  # noqa: E402
from repro.core.lz4_types import MAX_BLOCK  # noqa: E402
from repro.distributed import fabric  # noqa: E402


def _fabric_corpus(n_blocks: int, seed: int = 0) -> bytes:
    """Adversarial mixed corpus spanning exactly ``n_blocks`` 64 KB blocks:
    RLE runs, structured text, and an incompressible tail."""
    import random

    rng = random.Random(seed)
    total = (n_blocks - 1) * MAX_BLOCK + MAX_BLOCK // 3
    parts, n = [], 0
    while n < total:
        kind = rng.randrange(3)
        if kind == 0:
            piece = bytes([rng.randrange(256)]) * rng.randrange(100, 9000)
        elif kind == 1:
            piece = (b"the quick brown fox %d " % rng.randrange(1000)) * \
                rng.randrange(10, 300)
        else:
            piece = bytes(rng.randrange(256) for _ in range(
                rng.randrange(500, 8000)))
        parts.append(piece)
        n += len(piece)
    return b"".join(parts)[:total]


class TestPartitionBlocks:
    def test_balanced_and_contiguous(self):
        sls = fabric.partition_blocks(10, 4)
        assert [s.count for s in sls] == [3, 3, 2, 2]
        assert sls[0].start == 0 and sls[-1].stop == 10
        for a, b in zip(sls, sls[1:]):
            assert a.stop == b.start

    def test_even_split(self):
        assert [s.count for s in fabric.partition_blocks(8, 4)] == [2, 2, 2, 2]

    def test_more_shards_than_blocks(self):
        sls = fabric.partition_blocks(2, 5)
        assert [s.count for s in sls] == [1, 1, 0, 0, 0]

    def test_zero_blocks(self):
        assert all(s.count == 0 for s in fabric.partition_blocks(0, 3))

    def test_invalid_shards(self):
        with pytest.raises(ValueError):
            fabric.partition_blocks(4, 0)


class TestHostPathFabric:
    """Host-partition path: runs on a single device, writes the same v4
    container the mesh path does (and IS the mesh path's oracle)."""

    @pytest.mark.parametrize("shards", [1, 2, 4])
    def test_round_trip_v4(self, shards):
        data = _fabric_corpus(5, seed=shards)
        eng = LZ4Engine(shards=shards)
        frame = eng.compress(data)
        info = frame_info(frame)
        assert info["version"] == VERSION_V4
        assert info["shard_count"] == shards
        assert decode_frame_serial(frame) == data
        assert LZ4DecodeEngine().decode(frame) == data
        assert eng.stats.shards == shards

    @pytest.mark.parametrize("n_blocks,shards", [(5, 2), (7, 4), (3, 8)])
    def test_uneven_blocks(self, n_blocks, shards):
        """blocks % shards != 0: trailing shards own fewer (or zero) blocks."""
        data = _fabric_corpus(n_blocks, seed=n_blocks)
        frame = LZ4Engine(shards=shards).compress(data)
        info = frame_info(frame)
        assert info["block_count"] == n_blocks
        counts = [0] * shards
        for b in info["blocks"]:
            counts[b["shard"]] += 1
        assert counts == [s.count for s in
                          fabric.partition_blocks(n_blocks, shards)]
        assert decode_frame_serial(frame) == data

    def test_per_shard_byte_identity(self):
        """The core invariant: each shard's blocks are byte-identical to a
        single-device engine run on that shard's slice of the input."""
        data = _fabric_corpus(6, seed=42)
        shards = 3
        frame = LZ4Engine(shards=shards).compress(data)
        single = LZ4Engine()
        chunks = [data[i: i + MAX_BLOCK]
                  for i in range(0, len(data), MAX_BLOCK)]
        for sl in fabric.partition_blocks(len(chunks), shards):
            piece = b"".join(chunks[sl.start: sl.stop])
            assert fabric.shard_subframe(frame, sl.shard) == \
                single.compress(piece)

    def test_read_range_across_shard_boundary(self):
        data = _fabric_corpus(6, seed=7)
        frame = LZ4Engine(shards=3).compress(data)
        r = FrameReader(frame)
        # shard boundary after block 2 (6 blocks / 3 shards = 2 each)
        b = 2 * MAX_BLOCK
        for start, length in [(b - 100, 200), (0, len(data)),
                              (b - 1, 2), (4 * MAX_BLOCK - 10, 20)]:
            assert r.read_range(start, length) == data[start: start + length]

    def test_empty_input(self):
        frame = LZ4Engine(shards=2).compress(b"")
        assert frame_info(frame)["version"] == VERSION_V4
        assert decode_frame_serial(frame) == b""

    def test_compress_to_blocks_matches_unsharded(self):
        data = _fabric_corpus(5, seed=9)
        assert LZ4Engine(shards=4).compress_to_blocks(data) == \
            LZ4Engine().compress_to_blocks(data)

    def test_unsharded_stays_v3(self):
        assert frame_info(LZ4Engine().compress(b"x" * 1000))["version"] == 3


class TestFabricConfigValidation:
    def test_shard_axes_without_mesh(self):
        with pytest.raises(ValueError, match="requires mesh"):
            LZ4Engine(shard_axes=("data",))
        with pytest.raises(ValueError, match="requires mesh"):
            LZ4DecodeEngine(shard_axes=("data",))

    def test_bad_shards(self):
        with pytest.raises(ValueError, match="shards"):
            LZ4Engine(shards=0)

    def test_unknown_axis(self):
        mesh = sh.single_device_mesh()
        with pytest.raises(ValueError, match="not in mesh"):
            LZ4Engine(mesh=mesh, shard_axes=("nope",))
        with pytest.raises(ValueError, match="not in mesh"):
            LZ4DecodeEngine(mesh=mesh, shard_axes=("nope",))

    def test_mesh_shard_count_matches_mesh(self):
        mesh = sh.single_device_mesh()
        eng = LZ4Engine(mesh=mesh)
        assert eng.shards == 1  # 1x1x1 mesh
        with pytest.raises(ValueError, match="!= mesh shard count"):
            LZ4Engine(mesh=mesh, shards=4)


_FABRIC_SUBPROC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax
    from repro.core.engine import LZ4Engine
    from repro.core.decode_engine import FrameReader, LZ4DecodeEngine
    from repro.core.frame import decode_frame_serial, frame_info
    from repro.core.lz4_types import MAX_BLOCK
    from repro.distributed.sharding import make_mesh
    from tests.test_distributed import _fabric_corpus

    assert len(jax.devices()) == 8
    results = {}
    for shape, axes in [((1, 1), ("data", "model")),
                        ((2, 1), ("data", "model")),
                        ((2, 2), ("data", "model")),
                        ((1, 8), ("data", "model"))]:
        mesh = make_mesh(shape, axes)
        S = shape[0] * shape[1]
        # 7 blocks: uneven against every multi-shard count here
        data = _fabric_corpus(7, seed=S)
        eng = LZ4Engine(mesh=mesh)
        assert eng.shards == S
        frame = eng.compress(data)
        info = frame_info(frame)
        assert info["version"] == 4 and info["shard_count"] == S
        # byte-identity: mesh frame == host-partition oracle frame
        oracle = LZ4Engine(shards=S).compress(data)
        assert frame == oracle, f"mesh != oracle for {shape}"
        # serial oracle round trip
        assert decode_frame_serial(frame) == data
        # sharded decode round trip + cross-shard read_range
        dec = LZ4DecodeEngine(mesh=mesh)
        assert dec.decode(frame) == data
        r = FrameReader(frame, engine=dec)
        b = 2 * MAX_BLOCK
        assert r.read_range(b - 50, 100) == data[b - 50: b + 50]
        results[str(shape)] = {"shards": S,
                               "dispatches": eng.stats.dispatches,
                               "decode_dispatches": dec.stats.dispatches}
    print("RESULT:" + json.dumps({"ok": True, "meshes": results}))
""")


def test_subprocess_mesh_fabric():
    """shard_map compress/decode over mesh shapes (1x1, 2x1, 2x2, 1x8) on 8
    fake devices: v4 round trips, mesh output byte-identical to the
    host-partition oracle, read_range spans crossing shard boundaries."""
    proc = subprocess.run(
        [sys.executable, "-c", _FABRIC_SUBPROC],
        capture_output=True, text=True, timeout=500,
        env={"PYTHONPATH": "src:.", "PATH": "/usr/bin:/bin",
             "HOME": "/root", "JAX_PLATFORMS": "cpu"},
        cwd="/root/repo",
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT:")][0]
    result = json.loads(line[len("RESULT:"):])
    assert result["ok"]
    assert set(result["meshes"]) == {"(1, 1)", "(2, 1)", "(2, 2)", "(1, 8)"}
