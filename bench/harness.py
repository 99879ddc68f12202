"""Runs one cell once: set-up, the measured window, the check, the result.

Everything a cell is made of is found by name: the cell in `BENCHMARK.json`,
its configuration in `bench/configs/<config>.json`, its traffic mix in
`bench/traffic/<mix>.json`, whose ``op`` names the operation in
`bench/ops/<op>.py` and whose ``loop`` names the loop that sends the
requests in `bench/loops/<loop>.py`, and each metric in
`bench/metrics/<metric>.py`, whose ``read(ctx)`` returns the number or None.
A later cell, configuration, operation, loop or metric is added as files and
entries; none of this module changes for it.

Set-up, from process start to the first request, builds the payload from the
seed on a host thread while the main thread warms the cell's shapes; nothing
compiles inside the window (the count is printed).  With ``trace`` the run records the program's spans
and a profiler trace of the first ``trace_requests`` requests of the window
and reports the per-layer metrics over them; without it, the end-to-end
metrics over the whole window.
"""
from __future__ import annotations

import importlib.util
import json
import os
import shutil
import sys
import threading
import time

from bench import devtrace, loops, ops
from bench.traffic import load_mix

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
MIB = 1 << 20
PAPER_GBPS = 16.10  # the paper's FPGA write throughput (arXiv:2409.12433)


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def cell(spec: dict, name: str) -> tuple[dict, dict, dict]:
    """The workload entry, its configuration file and its traffic mix."""
    work = {w["name"]: w for w in spec["workloads"]}
    if name not in work:
        raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json")
    w = work[name]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    return w, load_json(ROOT, conf["file"]), load_mix(w["traffic"])


def metrics_of(spec: dict, workload: str, group: str) -> list[dict]:
    """The cell's metrics of ``group`` (``end_to_end`` or ``per_layer``)."""
    return [m for m in spec[group] if workload in m.get("workloads", [workload])]


def read_metric(name: str, ctx: "Ctx"):
    path = os.path.join(BENCH, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def peaks(kind: str) -> dict:
    table = load_json(BENCH, "peaks.json")["devices"]
    if kind not in table:
        raise ValueError(f"bench: no peaks for device kind {kind!r} in bench/peaks.json")
    return table[kind]


def tpu_devices(n: int) -> list:
    import jax

    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"bench: JAX finds no accelerator ({e})") from e
    if not devs or devs[0].platform != "tpu":
        raise NoChip(f"bench: JAX finds no TPU (platform {devs[0].platform if devs else None})")
    if len(devs) < n:
        raise NoChip(f"bench: the cell needs {n} TPU chips, JAX sees {len(devs)}")
    return devs[:n]


def enable_cache() -> str:
    """JAX's persistent compilation cache, where the program keeps it
    (`repro.compile_cache`: ``JAX_COMPILATION_CACHE_DIR``, else inside the
    checkout), for every program however fast it compiles."""
    import jax

    from repro import compile_cache

    path = compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


class Compiles:
    """XLA compilations in this process, counted from JAX's own events."""

    _count = 0
    _registered = False

    @classmethod
    def count(cls) -> int:
        if not cls._registered:
            import jax

            def on_event(event: str, duration: float, **_) -> None:
                if event == "/jax/core/compile/backend_compile_duration":
                    cls._count += 1

            jax.monitoring.register_event_duration_secs_listener(on_event)
            cls._registered = True
        return cls._count


class Ctx:
    """What a metric reader sees of one run."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    def span_s(self, name: str) -> float:
        return sum(s["dur_ns"] for s in self.spans if s["name"] == name) / 1e9

    def program_s(self, *names: str) -> float:
        """Device seconds of the named programs, summed over the chips."""
        return sum(s for d in self.trace["devices"] for n, s in d["programs"].items()
                   if n in names)


def setup(op) -> int:
    """The payload on a host thread while the main thread builds and warms;
    returns the number of items the requests pick from."""
    box: dict = {}

    def make():
        try:
            box["payload"] = op.make_payload()
        except BaseException as e:  # re-raised on the main thread below
            box["error"] = e

    t = threading.Thread(target=make, name="bench-payload")
    t.start()
    try:
        op.build()
        op.warm()
    finally:
        t.join()
    if "error" in box:
        raise box["error"]
    return op.ready(box["payload"])


def run(workload: str, seed: int, seconds: float, trace: bool, t_start: float,
        control: bool = False, spec: dict | None = None, devices: list | None = None,
        cfg: dict | None = None, mix: dict | None = None) -> dict:
    """One run of one cell; returns the result object.

    ``control`` switches on the mix's control path (`bench/ops/`).  The
    tests pass ``devices`` to run off the chip, and ``cfg``/``mix`` to run a
    cell at a size a test can hold.
    """
    spec = spec or load_json(ROOT, "BENCHMARK.json")
    w, cfg0, mix0 = cell(spec, workload)
    cfg, mix = cfg or cfg0, mix or mix0
    if devices is None:
        devices = tpu_devices(w["chips"])
    import jax

    from repro import obs

    kind = devices[0].device_kind
    peak = peaks(kind) if devices[0].platform == "tpu" else {}
    cache = enable_cache()
    log(f"bench: {workload} seed {seed} on {len(devices)} x {kind}; compile cache {cache}")
    Compiles.count()

    op = ops.load(mix["op"])(cfg, mix, seed, devices, control=control)
    loop = loops.load(mix["loop"])
    n = setup(op)
    limit = mix["trace_requests"] if trace else None
    trace_dir = os.path.join(ROOT, ".bench_runs", "trace")
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        obs.configure(enabled=True, jax_annotations=True)
        obs.reset()
        devtrace.start(trace_dir)
    c0 = Compiles.count()
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    with jax.profiler.TraceAnnotation(devtrace.WINDOW):
        lat, failed, done_bytes = loop.window(op, mix, seed, n, seconds, limit)
    elapsed = time.perf_counter() - t0
    compiles = Compiles.count() - c0
    red = spans = None
    if trace:
        devtrace.stop()
        spans = obs.tracer().finished()
        obs.configure(enabled=False, jax_annotations=False)
        red = devtrace.reduce(devtrace.load(trace_dir), len(devices))
        shutil.rmtree(trace_dir, ignore_errors=True)
    peak_bytes = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices)

    ctx = Ctx(workload=workload, chips=len(devices), elapsed_s=elapsed, setup_s=setup_s,
              latencies_s=lat, user_bytes=done_bytes, requests=len(lat), spans=spans or [],
              trace=red, op=op, peaks=peak)
    group = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in metrics_of(spec, workload, group):
        v = read_metric(m["name"], ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    log(f"bench: {len(lat) + failed} requests ({failed} failed) in {elapsed:.3f} s; "
        f"{done_bytes} user bytes; compiles inside the window: {compiles}; "
        f"set-up {setup_s:.3f} s; peak device memory {peak_bytes} B")
    if lat:
        log(f"bench: request seconds min {min(lat):.6f} median {sorted(lat)[len(lat) // 2]:.6f} "
            f"max {max(lat):.6f}")
    if "write_MiBps" in metrics:
        log(f"bench: write_MiBps {metrics['write_MiBps']['value']:.4f} beside the paper's "
            f"{PAPER_GBPS} Gb/s = {PAPER_GBPS * 1e9 / 8 / MIB:.1f} MiB/s")
    device = {"platform": devices[0].platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": int(peak_bytes)}
    if red is not None:
        device["busy_s"] = sum(d["busy_s"] for d in red["devices"]) / len(red["devices"])
        device["window_s"] = red["window_s"]

    checks = op.check()
    correct = failed == 0 and all(c["value"] <= c["limit"] for c in checks.values())
    result = {"correct": correct, "attempted": len(lat) + failed, "failed": failed,
              "metrics": metrics, "device": device}
    if red is not None:
        result["breakdown"] = devtrace.breakdown(red)
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    return result
