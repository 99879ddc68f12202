"""The trace reduction on a small sample recorded from a TPU v5e trace.

The events below are copied from one chip trace of a verified 1 MiB restore
followed by a 1 MiB compress (program names with their fingerprints, start
and duration in ns on the trace's clock, host spans from the `python`
line), cut to a few events.
"""
import pytest

from bench import devtrace
from bench.harness import Ctx, read_metric

GATHER, CRC = "jit_decode_gather(11074048816955298893)", "jit_crc32_bytes(3874667208462470551)"
RAW = {
    "devices": {"/device:TPU:0": [
        (devtrace.program(GATHER), 112241294, 60197783),
        (devtrace.program("jit_decode_gather(8417499092019198892)"), 172450552, 93372937),
        (devtrace.program("jit_dynamic_slice(16697230985994273329)"), 265830257, 2368),
        (devtrace.program(CRC), 265840000, 145000000),
        (devtrace.program("jit_compress_block_bytes(6990746844350544125)"), 2650000000, 450000000),
        # Outside the window: clipped away.
        (devtrace.program(CRC), 3100000000, 145000000),
    ]},
    "host": [
        ("bench.window", 46854835, 3017659649),
        ("decode.total", 46942535, 2556092683),
        ("decode.plan", 46973375, 4657059),
        ("compress.total", 2603698558, 460769976),
        ("compress.pad", 2603700000, 40000000),
    ],
}


def test_program_drops_the_fingerprint():
    assert devtrace.program(GATHER) == "jit_decode_gather"
    assert devtrace.program("jit__row_prefix(1)") == "jit__row_prefix"


def test_union_merges_overlaps():
    assert devtrace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]


def test_reduce_busy_idle_programs_and_launches():
    red = devtrace.reduce(RAW, chips=1)
    lo, hi = 46854835, 46854835 + 3017659649
    assert red["window_s"] == pytest.approx((hi - lo) / 1e9)
    dev = red["devices"][0]
    # The compress program runs past the window's end and is clipped to it.
    busy_ns = 60197783 + 93372937 + 2368 + 145000000 + (hi - 2650000000)
    assert dev["busy_s"] == pytest.approx(busy_ns / 1e9)
    assert dev["launches"] == 5
    assert dev["programs"]["jit_decode_gather"] == pytest.approx((60197783 + 93372937) / 1e9)
    assert dev["programs"]["jit_crc32_bytes"] == pytest.approx(0.145)
    assert sum(b - a for a, b in dev["gaps"]) / 1e9 == pytest.approx(red["window_s"] - dev["busy_s"])


def test_breakdown_names_gaps_by_the_innermost_host_span():
    bd = devtrace.breakdown(devtrace.reduce(RAW, chips=1))
    assert bd["device_ops"][0] == ["jit_compress_block_bytes", pytest.approx(0.414514484)]
    red = devtrace.reduce(RAW, chips=1)
    # Every gap lies inside decode.total and outside the shorter decode.plan.
    assert bd["idle_gaps"] == [["decode.total", pytest.approx(
        red["window_s"] - red["devices"][0]["busy_s"])]]


def test_reduce_without_window_or_device_is_nothing():
    assert devtrace.reduce({"devices": RAW["devices"], "host": []}, 1) is None
    assert devtrace.reduce({"devices": {}, "host": RAW["host"]}, 1) is None


def test_device_readers_on_the_sample():
    red = devtrace.reduce(RAW, chips=1)
    ctx = Ctx(workload="calgary.restore", chips=1, elapsed_s=3.0, setup_s=1.0,
              latencies_s=[2.5], user_bytes=1 << 20, requests=1, spans=[], trace=red,
              op=None, peaks={"hbm_bytes_per_s": 819e9})
    busy = red["devices"][0]["busy_s"]
    assert read_metric("device_idle_pct.restore", ctx) == pytest.approx(
        100 * (1 - busy / red["window_s"]))
    crc, gather = 0.145, (60197783 + 93372937) / 1e9
    assert read_metric("restore.device_ms_per_MiB", ctx) == pytest.approx(1e3 * (crc + gather))
    total = sum(red["devices"][0]["programs"].values())
    assert read_metric("restore.crc_pct", ctx) == pytest.approx(100 * crc / total)
    assert read_metric("resume.device_ops_per_req", ctx) == 5
