"""The sharded fabric's mesh write path on four virtual CPU devices.

The stack of each step is put straight onto its shards and every payload is
fetched from the chip that holds its row.  These tests pin what that must
keep: mesh frames byte-identical to the host-partition oracle
(`LZ4Engine(shards=n)`), no compile on a second compress of a warm shape,
and the spans and counter that name the put and the per-shard drain.  The
mesh runs in one subprocess, since the virtual devices must be asked for
before JAX starts.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# (mesh shape, shard axes, blocks, micro-batch): both axes of a 2x2 mesh,
# one axis of it (the output is then replicated over the other), a 1x4 mesh
# with a partial last step, and fewer blocks than shards.
CASES = {
    "2x2": ((2, 2), ("data", "model"), 7, 2),
    "2x2-data": ((2, 2), ("data",), 5, 2),
    "1x4": ((1, 4), ("data", "model"), 9, 2),
    "2x2-few": ((2, 2), ("data", "model"), 3, 2),
}

_RUNS = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json, sys
    import jax
    from repro import obs
    from repro.core.engine import LZ4Engine
    from repro.distributed.sharding import make_mesh
    from tests.test_distributed import _fabric_corpus

    compiles = [0]
    jax.monitoring.register_event_duration_secs_listener(
        lambda e, d, **k: compiles.__setitem__(
            0, compiles[0] + (e == "/jax/core/compile/backend_compile_duration")))
    out = {}
    for name, (shape, axes, n_blocks, mb) in json.loads(sys.argv[1]).items():
        mesh = make_mesh(tuple(shape), ("data", "model"))
        data = _fabric_corpus(n_blocks, seed=n_blocks)
        eng = LZ4Engine(mesh=mesh, shard_axes=tuple(axes), micro_batch=mb)
        frame = eng.compress(data)
        oracle = LZ4Engine(shards=eng.shards, micro_batch=mb).compress(data)
        c0 = compiles[0]
        obs.configure(enabled=True)
        obs.reset()
        again = eng.compress(data)
        spans = obs.tracer().finished()
        counters = obs.registry().snapshot()["counters"]
        obs.configure(enabled=False)
        out[name] = {
            "shards": eng.shards, "same_as_oracle": frame == oracle == again,
            "second_compiles": compiles[0] - c0, "dispatches": eng.stats.dispatches,
            "raw_blocks": eng.stats.raw_blocks, "host_bytes": eng.stats.host_bytes,
            "puts": [s["args"] for s in spans if s["name"] == "fabric.put"],
            "drains": [s["args"] for s in spans if s["name"] == "compress.drain"],
            "drain_bytes": counters.get("fabric.drain_bytes"),
        }
    print("RESULT:" + json.dumps(out))
""")


@pytest.fixture(scope="module")
def runs():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([os.path.join(ROOT, "src"), ROOT]))
    p = subprocess.run([sys.executable, "-c", _RUNS, json.dumps(CASES)], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    line = next(l for l in p.stdout.splitlines() if l.startswith("RESULT:"))
    return json.loads(line[len("RESULT:"):])


@pytest.mark.parametrize("case", sorted(CASES))
def test_mesh_frame_is_the_oracles(runs, case):
    shape, axes, _, _ = CASES[case]
    r = runs[case]
    assert r["shards"] == (4 if len(axes) == 2 else shape[0])
    assert r["same_as_oracle"]


@pytest.mark.parametrize("case", sorted(CASES))
def test_second_compress_compiles_nothing(runs, case):
    assert runs[case]["second_compiles"] == 0


@pytest.mark.parametrize("case", sorted(CASES))
def test_put_and_per_shard_drain_are_traced(runs, case):
    _, _, n_blocks, _ = CASES[case]
    r = runs[case]
    assert len(r["puts"]) == r["dispatches"] >= 1
    assert all(p["shards"] == r["shards"] for p in r["puts"])
    # One fetch per compressed block, each from a shard of the mesh.
    assert len(r["drains"]) == n_blocks - r["raw_blocks"]
    assert {d["shard"] for d in r["drains"]} <= set(range(r["shards"]))
    # The counter holds the fetched payload bytes: at least each block's
    # compressed size, rounded up to 4 KiB, and no more than the call moved.
    payload = sum(d["bytes"] for d in r["drains"])
    assert payload <= r["drain_bytes"] <= min(payload + 4096 * len(r["drains"]),
                                             r["host_bytes"])


def test_sharded_programs_have_names_of_their_own():
    """A profiler trace names a program ``jit_<function>``: the fabric's
    sharded programs read as `jit_fabric_compress`, `jit_fabric_decode` and
    `jit_fabric_plan_decode`, apart from the one-chip graphs."""
    from repro.distributed import fabric
    from repro.distributed.sharding import single_device_mesh

    mesh, axes = single_device_mesh(), ("data",)
    assert fabric._sharded_compress_compiled(
        mesh, axes, 8, 36, 8, "sequential").__name__ == "fabric_compress"
    assert fabric._sharded_decode_compiled(mesh, axes, 1024, 2).__name__ == \
        "fabric_decode"
    assert fabric._sharded_plan_decode_compiled(mesh, axes, 1024, 64, 64, 2).__name__ \
        == "fabric_plan_decode"
