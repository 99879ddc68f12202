"""The reader path's per-layer metrics in a traced resume on the CPU.

A traced `kv-mixtral.resume` run at a test size whose pages sit in raw
blocks reads the reader path's spans (`decode.upload`, `decode.slice`,
`serving.view`) and its upload counter (`decode.upload_bytes`).
"""
import time

import jax
import pytest

from bench import harness
from bench.tests.test_harness import SPEC, tiny


@pytest.fixture(autouse=True)
def no_persistent_cache(monkeypatch):
    """Keep the process's JAX configuration as the other tests expect it."""
    monkeypatch.setattr(harness, "enable_cache", lambda: "off in tests")


def test_traced_resume_reads_the_reader_path():
    """32 filled slots of 8 heads x 128 bf16 fill the first 64 KiB block of
    each (layer, session) and come out raw; the empty half compresses.  So
    each leaf read of a page uploads one raw block: two per request."""
    from repro.core.lz4_types import MAX_BLOCK

    cfg, mix = tiny("kv-mixtral.resume")
    cfg["kv_cache"].update(num_key_value_heads=8, head_dim=128, slots=64, filled=32)
    r = harness.run("kv-mixtral.resume", 2**33 + 17, 0.5, True, time.perf_counter(),
                    spec=SPEC, devices=jax.devices()[:1], cfg=cfg, mix=mix)
    m = r["metrics"]
    assert r["correct"]
    assert m["resume.upload_ms_per_req"]["value"] > 0
    assert m["resume.view_ms_per_req"]["value"] > 0
    assert m["resume.upload_KiB_per_req"]["value"] == 2 * MAX_BLOCK / 1024
    assert "resume.verify_ms_per_req" in m
