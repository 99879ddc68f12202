"""The profiler trace of a traced run, and its reduction to numbers.

`start`/`stop` bracket the traced requests; `load` reads the `.xplane.pb`
into plain lists; `reduce` turns those into what the per-layer metrics read.
The reduction is plain arithmetic on ``(name, start_ns, duration_ns)``
tuples, so `bench/tests/test_devtrace.py` checks it on a recorded sample.

What a TPU trace holds (seen in one by hand): a plane ``/device:TPU:<i>``
per chip, whose line ``XLA Modules`` has one event per program run, named
``<jit name>(<fingerprint>)``, and whose line ``XLA Ops`` has one event per
operation, down to each step of a loop (millions for a CRC of a few MiB, so
they are not read).  Host planes carry the program's spans as
`jax.profiler.TraceAnnotation` events on the same clock.
"""
from __future__ import annotations

import glob
import os
import re

WINDOW = "bench.window"
PROGRAMS_LINE = "XLA Modules"
_FINGERPRINT = re.compile(r"\(\d+\)$")
_SPAN = re.compile(r"^[a-z_]+(\.[a-z_]+)+$")  # the program's spans: "decode.verify"
NAMED_GAPS = 1000


def start(path: str) -> None:
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(path, profiler_options=opts)


def stop() -> None:
    import jax

    jax.profiler.stop_trace()


def program(name: str) -> str:
    """A program event's name without its fingerprint."""
    return _FINGERPRINT.sub("", name)


def load(path: str) -> dict:
    """``{"devices": {plane: [(program, start_ns, dur_ns)]}, "host": [...]}``
    from the newest trace under ``path``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not files:
        return {"devices": {}, "host": []}
    devices, host = {}, []
    for plane in ProfileData.from_file(files[-1]).planes:
        if plane.name.startswith("/device:") and not plane.name.startswith("/device:CPU"):
            for line in plane.lines:
                if line.name == PROGRAMS_LINE:
                    devices[plane.name] = [(program(e.name), e.start_ns, e.duration_ns)
                                           for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.duration_ns) for e in line.events
                            if _SPAN.match(e.name))
    return {"devices": dict(sorted(devices.items())), "host": host}


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Sorted, merged ``(start, end)`` intervals."""
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def name_gaps(host: list, gaps: list[tuple[float, float]]) -> dict[str, float]:
    """Idle seconds by the innermost host span open in the middle of each
    gap; past the ``NAMED_GAPS`` longest, gaps count as "short gaps"."""
    import numpy as np

    spans = [(n, s, d) for n, s, d in host if n != WINDOW]
    starts = np.array([s for _, s, _ in spans], np.float64)
    ends = starts + np.array([d for _, _, d in spans], np.float64)
    out: dict[str, float] = {}
    for k, (a, b) in enumerate(sorted(gaps, key=lambda g: g[0] - g[1])):
        name = "short gaps"
        if k < NAMED_GAPS:
            t = (a + b) / 2
            open_ = np.nonzero((starts <= t) & (t < ends))[0]
            name = spans[open_[np.argmin(ends[open_] - starts[open_])]][0] if len(open_) \
                else "no span"
        out[name] = out.get(name, 0.0) + (b - a) / 1e9
    return out


def reduce(raw: dict, chips: int) -> dict | None:
    """Busy time, programs and idle gaps of the first ``chips`` devices over
    the traced window (the host span ``bench.window``)."""
    win = [(s, s + d) for name, s, d in raw["host"] if name == WINDOW]
    planes = list(raw["devices"].values())[:chips]
    if not win or not planes:
        return None
    lo, hi = win[0]
    devices = []
    for events in planes:
        inside = [(n, max(s, lo), min(s + d, hi)) for n, s, d in events
                  if s + d > lo and s < hi]
        busy = union([(a, b) for _, a, b in inside])
        programs: dict[str, float] = {}
        for n, a, b in inside:
            programs[n] = programs.get(n, 0.0) + (b - a) / 1e9
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
        devices.append({"busy_s": sum(b - a for a, b in busy) / 1e9,
                        "programs": programs, "launches": len(inside), "gaps": gaps})
    return {"window_s": (hi - lo) / 1e9, "devices": devices, "host": raw["host"]}


def breakdown(red: dict, top: int = 10) -> dict:
    """The programs that took most device time, and the idle time by the
    host span open during it, both summed over the traced chips."""
    progs: dict[str, float] = {}
    idle: dict[str, float] = {}
    for dev in red["devices"]:
        for n, s in dev["programs"].items():
            progs[n] = progs.get(n, 0.0) + s
        for n, s in name_gaps(red["host"], dev["gaps"]).items():
            idle[n] = idle.get(n, 0.0) + s
    rank = lambda d: [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {"device_ops": rank(progs), "idle_gaps": rank(idle)}
