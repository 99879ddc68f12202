"""Dry-run tooling tests: HLO parsers, cell bookkeeping, probe linearity."""
import json
import subprocess
import sys
import textwrap
import types

import pytest
from jax.sharding import PartitionSpec as P

from repro.launch.dryrun import _shape_bytes, parse_collectives, parse_dot_bytes
from repro.launch.roofline import model_param_count
from repro.configs.base import get_config

HLO = textwrap.dedent("""
    %x = f32[16,128]{1,0} parameter(0)
    %ag = f32[16,2048]{1,0} all-gather(f32[16,128]{1,0} %x), replica_groups={{0,1}}, dimensions={1}
    %ar = (bf16[256]{0}, bf16[256]{0}) all-reduce(bf16[256]{0} %a, bf16[256]{0} %b), to_apply=%sum
    %rs = f32[8,128]{1,0} reduce-scatter(f32[128,128]{1,0} %y), dimensions={0}
    %cp = u8[1024]{0} collective-permute(u8[1024]{0} %z), source_target_pairs={{0,1}}
    %d = f32[64,32]{1,0} dot(f32[64,16]{1,0} %p, f32[16,32]{1,0} %q), lhs_contracting_dims={1}
    %notacoll = f32[4]{0} add(f32[4]{0} %m, f32[4]{0} %n)
""")


class TestParsers:
    def test_shape_bytes(self):
        assert _shape_bytes("f32[16,128]{1,0}") == 16 * 128 * 4
        assert _shape_bytes("(bf16[256]{0}, s8[4]{0})") == 256 * 2 + 4
        assert _shape_bytes("pred[]") == 1  # scalar => dims empty

    def test_parse_collectives(self):
        stats = parse_collectives(HLO)
        assert stats["all-gather"]["count"] == 1
        assert stats["all-gather"]["bytes"] == 16 * 2048 * 4
        assert stats["all-reduce"]["bytes"] == 2 * 256 * 2
        assert stats["reduce-scatter"]["count"] == 1
        assert stats["collective-permute"]["bytes"] == 1024
        assert "dot" not in stats and "add" not in stats

    def test_parse_dot_bytes(self):
        # operands + result of the dot line only
        assert parse_dot_bytes(HLO) == (64 * 32 + 64 * 16 + 16 * 32) * 4

    def test_shape_bytes_scalar_pred(self):
        assert _shape_bytes("pred[1,1,256]{1,0,2}") == 256


class TestModelFlops:
    def test_param_counts_close_to_nominal(self):
        # analytic N within 40% of the arch's nominal size (non-embedding
        # N differs from marketing numbers; this guards gross errors)
        nominal = {
            "internlm2-1.8b": 1.8e9, "qwen3-1.7b": 1.7e9, "minicpm-2b": 2.4e9,
            "gemma2-9b": 9e9, "mixtral-8x7b": 46e9, "mixtral-8x22b": 140e9,
        }
        for arch, n in nominal.items():
            total, active = model_param_count(get_config(arch))
            assert 0.5 * n < total < 1.6 * n, (arch, total)
            assert active <= total

    def test_moe_active_fraction(self):
        total, active = model_param_count(get_config("mixtral-8x7b"))
        assert active < 0.45 * total  # top-2 of 8 experts dominate params


def test_probe_linearity_subprocess():
    """Per-layer cost deltas are linear in repeats (the probe assumption)."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
        import json, dataclasses
        import jax
        from repro.launch.dryrun import _with_repeats, _lower_cell, _cost_of
        from repro.configs.base import get_config, ShapeConfig
        from repro.distributed.sharding import use_mesh

        from repro.distributed.sharding import make_mesh
        mesh = make_mesh((4, 4), ("data", "model"))
        cfg = dataclasses.replace(
            get_config("internlm2-1.8b"), d_model=256, n_heads=8, head_dim=32,
            n_kv_heads=4, d_ff=512, vocab_size=2048, fsdp=True)
        shape = ShapeConfig("t", seq_len=256, global_batch=8, mode="train")
        with use_mesh(mesh):
            f = [_cost_of(_lower_cell(_with_repeats(cfg, [r]), shape, mesh).compile())["flops"]
                 for r in (2, 3, 4)]
        d1, d2 = f[1] - f[0], f[2] - f[1]
        print("RESULT:" + json.dumps({"d1": d1, "d2": d2}))
    """)
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, timeout=500,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "HOME": "/root",
             "JAX_PLATFORMS": "cpu"},
        cwd="/root/repo",
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT:")][0]
    r = json.loads(line[len("RESULT:"):])
    assert r["d1"] > 0
    assert abs(r["d1"] - r["d2"]) / r["d1"] < 0.05, r
