"""Compile-only checks against a described TPU v5e (no chip needed).

The TPU compiler is installed with JAX, and it compiles for a chip that is
described rather than attached.  These tests compile the programs the chip
runs, at the real 64 KB block size and the paper's settings (hash_bits 8,
max_match 36, pws 8), so a change the chip's compiler would refuse fails
here instead of on the chip:

  * the engines' default graphs: the vmapped write graph at micro_batch 32,
    the device decode graphs (`decode_gather`, `plan_decode`) at
    micro_batch 8, and the in-graph CRC-32 of one decoded row;
  * the sharded fabric's write graph over a four-chip mesh;
  * the write graph's emit stage keeps to two gathers (its field table in
    one column gather, and the literal bytes).

The topology is described inside a module fixture, never at import, and the
persistent compilation cache is off while these compiles run.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.core.decode_engine import (_device_decode_compiled,
                                      _device_plan_decode_compiled)
from repro.core.decode_plan import MAX_RESOLVE_ROUNDS, DevicePlanCaps
from repro.core.engine import _batched_compiled
from repro.core.jax_compressor import _PAD, OUT_CAP
from repro.core.lz4_types import MAX_BLOCK
from repro.kernels import ops

HASH_BITS, MAX_MATCH, PWS = 8, 36, 8
CAPS = DevicePlanCaps()


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.fixture(scope="module")
def write_program(one_chip):
    """The engine's dispatch on a TPU: scatter candidates, sequential select,
    device emit, donated input, 32 blocks per micro-batch."""
    fn = _batched_compiled(HASH_BITS, MAX_MATCH, PWS, "sequential", True, True)
    return fn.lower(_spec(one_chip, (32, MAX_BLOCK + _PAD), jnp.uint8),
                    _spec(one_chip, (32,))).compile()


def test_default_write_graph_compiles(write_program):
    out, size = write_program.out_info
    assert out.shape == (32, OUT_CAP) and size.shape == (32,)


def test_emit_stage_gathers_field_table_once(write_program):
    """The emit stage gathers twice: the 8-row per-sequence field table as
    one column gather, and the literal bytes; not once per field.

    Optimization can drop a gather's scope name (op_name "gather"), so the
    gathers one per output position (last dimension OUT_CAP) are counted
    too: only the emit stage makes them."""
    named, wide = [], []
    for line in write_program.as_text().splitlines():
        if " gather(" not in line:
            continue
        shape = re.search(r"= (\w+\[[\d,]*\])", line).group(1)
        if "/lz4.emit/" in line:
            named.append(shape)
        if shape.endswith(f",{OUT_CAP}]"):
            wide.append(shape)
    want = sorted([f"s32[32,8,{OUT_CAP}]", f"s32[32,{OUT_CAP}]"])
    assert sorted(named) == want
    assert sorted(wide) == want


@pytest.mark.parametrize("graph", ["decode_gather", "plan_decode"])
def test_device_decode_graph_compiles(one_chip, graph):
    m = 8
    if graph == "decode_gather":
        fn = _device_decode_compiled(CAPS.out_cap, MAX_RESOLVE_ROUNDS)
        args = ([_spec(one_chip, (m, CAPS.blk_cap), jnp.uint8)]
                + [_spec(one_chip, (m, CAPS.max_lit))] * 3
                + [_spec(one_chip, (m, CAPS.max_match))] * 2
                + [_spec(one_chip, (m,))] * 3)
    else:
        fn = _device_plan_decode_compiled(CAPS.out_cap, CAPS.max_lit,
                                          CAPS.max_match, MAX_RESOLVE_ROUNDS,
                                          True)
        args = [_spec(one_chip, (m, CAPS.blk_cap + ops.SPEC_PAD), jnp.uint8),
                _spec(one_chip, (m,)), _spec(one_chip, (m,))]
    c = fn.lower(*args).compile()
    out = c.out_info if graph == "decode_gather" else c.out_info[0]
    assert out.shape == (m, CAPS.out_cap)


def test_device_crc_graph_compiles(one_chip):
    """The in-graph CRC-32 of a decoded 64 KB row (the verified restore's
    per-block check), with its byte count traced."""
    c = ops.crc32_bytes.lower(_spec(one_chip, (CAPS.out_cap,), jnp.uint8),
                              _spec(one_chip, ())).compile()
    assert c.out_info.shape == () and c.out_info.dtype == jnp.uint32


def test_fabric_write_graph_compiles_on_four_chips(topo, one_chip):
    """The sharded fabric's dispatch over a 4-chip mesh: each chip gets its
    own rows, and the program holds no collective."""
    from jax.sharding import Mesh

    from repro.distributed import fabric

    mesh = Mesh(topo.devices[:4], ("data",))
    fn = fabric._sharded_compress_compiled(mesh, ("data",), HASH_BITS,
                                           MAX_MATCH, PWS, "sequential")
    rows = NamedSharding(mesh, PartitionSpec("data"))
    c = fn.lower(_spec(rows, (4 * 32, MAX_BLOCK + _PAD), jnp.uint8),
                 _spec(rows, (4 * 32,))).compile()
    assert {d.id for d in c.input_shardings[0][0].device_set} == \
        {d.id for d in topo.devices[:4]}
    text = c.as_text()
    assert "all-gather" not in text and "all-reduce" not in text

