"""The harness end to end on the CPU, at a size a test can hold.

Each cell runs through `harness.run` with its own operation, traffic
generator and check, on the CPU and at a tiny size: the program's path comes
out correct, the control (the mix's ``control`` settings, which break one
guarantee of the configuration) comes out not correct, and so does each
fault planted in the program underneath the timed path.
"""
import copy
import json
import os
import shutil
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

from bench import harness

SPEC = harness.load_json(harness.ROOT, "BENCHMARK.json")
CELLS = ["calgary.write", "calgary.restore", "kv-mixtral.resume"]


@pytest.fixture(autouse=True)
def no_persistent_cache(monkeypatch):
    """Keep the process's JAX configuration as the other tests expect it."""
    monkeypatch.setattr(harness, "enable_cache", lambda: "off in tests")


def tiny(workload: str) -> tuple[dict, dict]:
    _, cfg, mix = harness.cell(SPEC, workload)
    cfg, mix = copy.deepcopy(cfg), copy.deepcopy(mix)
    if "payload" in cfg:
        cfg["payload"].update(pool_bytes=4 * 65536, corpus_seeds=1)
        cfg["engine"]["micro_batch"] = 2
    if "kv_cache" in cfg:
        cfg["kv_cache"].update(num_hidden_layers=2, num_key_value_heads=2, head_dim=64,
                               slots=64, filled=48)
    mix.update(object_bytes=2 * 65536, check_sample_blocks=2, frame_bytes=2 * 65536,
               page_slots=16)
    return cfg, mix


def run(workload: str, control: bool = False, trace: bool = False) -> dict:
    cfg, mix = tiny(workload)
    return harness.run(workload, 2**33 + 17, 0.5, trace, time.perf_counter(), control=control,
                       spec=SPEC, devices=jax.devices()[:1], cfg=cfg, mix=mix)


@pytest.mark.parametrize("workload", CELLS)
def test_program_is_correct(workload):
    r = run(workload)
    assert r["correct"] and r["attempted"] > 0 and r["failed"] == 0
    assert list(r)[-1] == "checks" and all(c["limit"] == 0 for c in r["checks"].values())
    assert "setup_s" in r["metrics"]
    assert r["device"]["platform"] == "cpu" and r["device"]["count"] == 1


@pytest.mark.parametrize("workload", CELLS)
def test_control_is_not_correct(workload):
    r = run(workload, control=True)
    assert not r["correct"]
    assert any(c["value"] > c["limit"] for c in r["checks"].values())


def test_traced_run_reads_spans():
    r = run("kv-mixtral.resume", trace=True)
    assert r["correct"] and "resume.verify_ms_per_req" in r["metrics"]
    assert "setup_s" not in r["metrics"]


def _flip_first_payload_byte(frame: bytes) -> bytes:
    from bench.reference import parse_frame

    b = parse_frame(frame)["blocks"][0]
    out = bytearray(frame)
    out[b["offset"] + b["csize"] - 1] ^= 1
    return bytes(out)


FAULTS = {
    # An answer altered where it is produced.
    "write_frame_altered": ("calgary.write", "repro.core.LZ4Engine.compress",
                            lambda orig: lambda self, data: _flip_first_payload_byte(orig(self, data))),
    # Half of the batch left out.
    "write_half_the_blocks": ("calgary.write", "repro.core.LZ4Engine.compress",
                              lambda orig: lambda self, data: orig(self, data[: len(data) // 2])),
    "restore_array_altered": ("calgary.restore", "repro.core.LZ4DecodeEngine.decode_to_device",
                              lambda orig: lambda self, frame, verify=True:
                              orig(self, frame, verify).at[7].add(1)),
    "resume_page_altered": ("kv-mixtral.resume", "repro.serving.engine.OffloadedCacheReader.read_leaf",
                            lambda orig: lambda self, i, start=0, count=None:
                            orig(self, i, start, count).at[3].add(1)),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_fault_under_the_timed_path_is_not_correct(fault, monkeypatch):
    workload, target, wrap = FAULTS[fault]
    mod, cls, meth = target.rsplit(".", 2)
    owner = getattr(__import__(mod, fromlist=[cls]), cls)
    orig = getattr(owner, meth)
    # Set-up runs the program as it is; the fault is planted for the window.
    real_setup = harness.setup

    def setup(op):
        n = real_setup(op)
        monkeypatch.setattr(owner, meth, wrap(orig))
        return n

    monkeypatch.setattr(harness, "setup", setup)
    r = run(workload)
    assert not r["correct"], r["checks"]


def test_run_refuses_a_machine_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(harness.ROOT, "bench", "run.py"),
                        "--workload", "calgary.write", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=harness.ROOT, env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
    assert "no TPU" in p.stderr


def test_run_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", "calgary.write",
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=tmp_path, env=dict(env, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_result_line_is_json():
    r = run("calgary.restore")
    line = json.dumps(r)
    assert json.loads(line)["correct"] is True
    assert np.isfinite(r["metrics"]["restore_MiBps"]["value"])
