#!/usr/bin/env python
"""Per-stage pipeline breakdown from a `repro.obs` telemetry bundle.

Reads the artifact directory `obs.dump_artifacts` (or
`benchmarks.common.dump_telemetry`) writes —

    trace.json     Chrome trace-event JSON (Perfetto-loadable)
    metrics.json   metrics registry snapshot
    events.jsonl   per-span JSONL log          (optional here)
    metrics.prom   Prometheus text exposition  (optional here)

— and prints the per-stage breakdown table: for every span name, the call
count, total/mean time, p50/p99 of the span durations, and share of the
traced wall clock.  This is the artifact BENCH entries and perf PRs embed:
`compress.dispatch` vs `compress.wait` vs `compress.drain` tells you
whether the write path is device-bound or drain-bound; `decode.plan` vs
`decode.execute` vs `decode.verify` does the same for the read path.

``--check`` schema-validates the bundle instead (CI runs this):
trace.json must be Chrome trace-event shaped, metrics.json
must be a versioned registry snapshot.  Exit 0 iff valid.

Usage:
    python tools/trace_report.py experiments/telemetry/engine_batched
    python tools/trace_report.py <dir> --check
    python tools/trace_report.py <dir> --json       # breakdown as JSON

Stdlib only.  See docs/observability.md.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

REQUIRED_EVENT_KEYS = {"name", "ph", "pid", "tid"}


def load_bundle(path: str) -> tuple[dict, dict]:
    """(trace, metrics) from a bundle dir or a single trace.json path."""
    if os.path.isdir(path):
        trace_path = os.path.join(path, "trace.json")
        metrics_path = os.path.join(path, "metrics.json")
    else:
        trace_path = path
        metrics_path = os.path.join(os.path.dirname(path), "metrics.json")
    with open(trace_path) as f:
        trace = json.load(f)
    metrics = {}
    if os.path.exists(metrics_path):
        with open(metrics_path) as f:
            metrics = json.load(f)
    return trace, metrics


# ---------------------------------------------------------------------------
# --check: schema validation
# ---------------------------------------------------------------------------

def check_trace(trace) -> list[str]:
    errors = []
    if not isinstance(trace, dict) or "traceEvents" not in trace:
        return ["trace.json: not a Chrome trace-event object "
                "(missing 'traceEvents')"]
    events = trace["traceEvents"]
    if not isinstance(events, list):
        return ["trace.json: 'traceEvents' is not a list"]
    n_complete = 0
    for i, ev in enumerate(events):
        if not isinstance(ev, dict) or not REQUIRED_EVENT_KEYS <= ev.keys():
            errors.append(f"trace.json: event {i} missing keys "
                          f"{sorted(REQUIRED_EVENT_KEYS - set(ev))}")
            continue
        if ev["ph"] == "X":
            n_complete += 1
            for k in ("ts", "dur"):
                if not isinstance(ev.get(k), (int, float)):
                    errors.append(
                        f"trace.json: complete event {i} ({ev['name']!r}) "
                        f"has non-numeric {k!r}")
            if isinstance(ev.get("dur"), (int, float)) and ev["dur"] < 0:
                errors.append(f"trace.json: event {i} has negative dur")
    if n_complete == 0:
        errors.append("trace.json: no complete ('ph': 'X') span events — "
                      "was the producer run with REPRO_OBS=1?")
    return errors


def check_metrics(metrics) -> list[str]:
    if not metrics:
        return ["metrics.json: missing or empty"]
    errors = []
    if not isinstance(metrics.get("schema_version"), int):
        errors.append("metrics.json: missing integer 'schema_version'")
    m = metrics.get("metrics")
    if not isinstance(m, dict):
        return errors + ["metrics.json: missing 'metrics' object"]
    for section in ("counters", "gauges", "histograms"):
        if not isinstance(m.get(section), dict):
            errors.append(f"metrics.json: metrics.{section} is not an object")
    for name, h in (m.get("histograms") or {}).items():
        if not isinstance(h, dict) or "count" not in h or "buckets" not in h:
            errors.append(f"metrics.json: histogram {name!r} missing "
                          "count/buckets")
    return errors


# ---------------------------------------------------------------------------
# breakdown
# ---------------------------------------------------------------------------

def _pct(sorted_vals: list[float], q: float) -> float:
    """Nearest-rank percentile of an already-sorted list."""
    if not sorted_vals:
        return 0.0
    k = min(len(sorted_vals) - 1, max(0, round(q * (len(sorted_vals) - 1))))
    return sorted_vals[int(k)]


def breakdown(trace: dict) -> dict:
    """Group complete events by span name -> timing summary (ms)."""
    spans: dict[str, list[float]] = {}
    t_min, t_max = float("inf"), float("-inf")
    for ev in trace.get("traceEvents", []):
        if ev.get("ph") != "X":
            continue
        spans.setdefault(ev["name"], []).append(ev["dur"] / 1e3)
        t_min = min(t_min, ev["ts"])
        t_max = max(t_max, ev["ts"] + ev["dur"])
    wall_ms = (t_max - t_min) / 1e3 if spans else 0.0
    stages = {}
    for name, durs in spans.items():
        durs.sort()
        total = sum(durs)
        stages[name] = {
            "count": len(durs),
            "total_ms": round(total, 3),
            "mean_ms": round(total / len(durs), 4),
            "p50_ms": round(_pct(durs, 0.50), 4),
            "p99_ms": round(_pct(durs, 0.99), 4),
            "max_ms": round(durs[-1], 4),
            "pct_of_wall": round(100 * total / wall_ms, 1) if wall_ms else 0.0,
        }
    return {
        "wall_ms": round(wall_ms, 3),
        "dropped_events": trace.get("otherData", {}).get("dropped_events", 0),
        "stages": dict(sorted(stages.items(),
                              key=lambda kv: -kv[1]["total_ms"])),
    }


def print_breakdown(b: dict, metrics: dict) -> None:
    head = (f"{'stage':<26} {'count':>7} {'total ms':>10} {'mean ms':>9} "
            f"{'p50 ms':>9} {'p99 ms':>9} {'% wall':>7}")
    print(f"traced wall clock: {b['wall_ms']:.1f} ms"
          + (f"  (DROPPED {b['dropped_events']} events)"
             if b["dropped_events"] else ""))
    print(head)
    print("-" * len(head))
    for name, s in b["stages"].items():
        print(f"{name:<26} {s['count']:>7} {s['total_ms']:>10.1f} "
              f"{s['mean_ms']:>9.3f} {s['p50_ms']:>9.3f} {s['p99_ms']:>9.3f} "
              f"{s['pct_of_wall']:>6.1f}%")
    m = metrics.get("metrics") or {}
    counters = m.get("counters") or {}
    if counters:
        print()
        print(f"{'counter':<34} {'value':>14}")
        print("-" * 49)
        for name, v in sorted(counters.items()):
            print(f"{name:<34} {v:>14}")
    gauges = m.get("gauges") or {}
    if gauges:
        print()
        print(f"{'gauge':<34} {'value':>14}")
        print("-" * 49)
        for name, v in sorted(gauges.items()):
            print(f"{name:<34} {v:>14}")
    hists = m.get("histograms") or {}
    if hists:
        print()
        print(f"{'histogram':<30} {'count':>7} {'p50':>12} {'p90':>12} "
              f"{'p99':>12}")
        print("-" * 76)
        for name, h in sorted(hists.items()):
            def fmt(x):
                return "-" if x is None else f"{x:.6g}"
            print(f"{name:<30} {h['count']:>7} {fmt(h.get('p50')):>12} "
                  f"{fmt(h.get('p90')):>12} {fmt(h.get('p99')):>12}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("bundle", help="telemetry bundle dir (or trace.json path)")
    ap.add_argument("--check", action="store_true",
                    help="schema-validate the bundle and exit")
    ap.add_argument("--json", action="store_true",
                    help="print the breakdown as JSON instead of a table")
    args = ap.parse_args(argv)

    try:
        trace, metrics = load_bundle(args.bundle)
    except (OSError, json.JSONDecodeError) as e:
        print(f"FAIL: cannot load bundle: {e}", file=sys.stderr)
        return 1

    if args.check:
        errors = check_trace(trace) + check_metrics(metrics)
        if errors:
            print(f"FAIL: {len(errors)} schema problem(s):", file=sys.stderr)
            for e in errors:
                print(f"  {e}", file=sys.stderr)
            return 1
        n = sum(1 for ev in trace["traceEvents"] if ev.get("ph") == "X")
        print(f"OK: {n} span events, "
              f"{len((metrics.get('metrics') or {}).get('counters') or {})} "
              "counters — bundle is schema-valid")
        return 0

    b = breakdown(trace)
    if args.json:
        print(json.dumps({"breakdown": b,
                          "metrics": metrics.get("metrics")}, indent=1))
    else:
        print_breakdown(b, metrics)
    return 0


if __name__ == "__main__":
    sys.exit(main())
