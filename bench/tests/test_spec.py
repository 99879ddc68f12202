"""`BENCHMARK.json` keeps to its contract, and every name in it has its file."""
import json
import os
import re

import pytest

from bench import harness, loops, ops
from bench.traffic import load_mix

SPEC = harness.load_json(harness.ROOT, "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
KEYS = {
    "top": {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"},
    "config": {"name", "source", "file", "reduced", "why"},
    "workload": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def one_line(s: str) -> bool:
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_keys_names_and_units():
    assert set(SPEC) == KEYS["top"]
    assert len(json.dumps(SPEC)) < 64 * 1024
    assert SPEC["command"] == ["python3", "bench/run.py"] and SPEC["paths"] == ["bench"]
    assert isinstance(SPEC["run_seconds"], int) and 1 <= SPEC["run_seconds"] <= 51
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in SPEC[group]]
        assert len(names) == len(set(names)), group
        for e in SPEC[group]:
            assert NAME.match(e["name"]), e["name"]
            allowed = KEYS[group[:-1] if group.endswith("s") else group]
            extra = {"workloads"} if group in ("end_to_end", "per_layer") else set()
            assert allowed <= set(e) <= allowed | extra, e["name"]
            if "unit" in e:
                assert UNIT.match(e["unit"]) and e["better"] in ("lower", "higher")
    metric_names = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(metric_names) == len(set(metric_names))


def test_configs_and_cells_are_files():
    configs = {c["name"]: c for c in SPEC["configs"]}
    used = set()
    pairs = set()
    for w in SPEC["workloads"]:
        assert w["config"] in configs and w["chips"] in (1, 4) and one_line(w["why"])
        assert NAME.match(w["traffic"])
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        used.add(w["config"])
        mix = load_mix(w["traffic"])
        op, loop = ops.load(mix["op"]), loops.load(mix["loop"])
        assert all(callable(getattr(op, f)) for f in
                   ("make_payload", "build", "warm", "ready", "request", "check"))
        assert callable(loop.window)
    assert used == set(configs)
    assert sum(w["chips"] == 4 for w in SPEC["workloads"]) <= max(1, len(SPEC["workloads"]) // 2)
    files = [c["file"] for c in configs.values()]
    assert len(files) == len(set(files))
    for c in configs.values():
        assert c["file"].startswith("bench/configs/") and one_line(c["source"]) and one_line(c["why"])
        body = harness.load_json(harness.ROOT, c["file"])
        assert body["name"] == c["name"] and body["reduced"] == c["reduced"]
        assert len(c["reduced"]) <= 16 and all(NAME.match(k) for k in c["reduced"])
        assert not any(k.endswith(("_dim", "_rank", "_size")) for k in c["reduced"])


def test_metrics_have_readers_and_cells():
    cells = {w["name"] for w in SPEC["workloads"]}
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e and one_line(m["layer"])
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        for w in m.get("workloads", []):
            assert w in cells
            assert w in e2e[m["moves"]].get("workloads", [w])
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert os.path.isfile(os.path.join(harness.BENCH, "metrics", m["name"] + ".py")), m["name"]
    for w in cells:
        assert any(w in m.get("workloads", [w]) for m in SPEC["per_layer"]), w
        assert sum(w in m.get("workloads", [w]) for m in SPEC["end_to_end"]) >= 2, w


@pytest.mark.parametrize("kind, name", [("op", "no_such_op"), ("loop", "no_such_loop"),
                                        ("op", "../harness"), ("loop", "Closed")])
def test_ops_and_loops_are_found_by_name_only(kind, name):
    load = ops.load if kind == "op" else loops.load
    with pytest.raises((ImportError, ValueError)):
        load(name)


def test_closed_loop_refuses_more_callers():
    with pytest.raises(ValueError, match="callers"):
        loops.load("closed").window(None, {"callers": 2}, 0, 1, 1.0, None)


def test_a_full_check_fits_its_time():
    """2 + 14 runs per cell, each run_seconds + 60 s, 180 s of compiling per
    cell and 1200 s spare must fit in 43200 s with 24 cells."""
    assert (2 + 14 * 24) * (SPEC["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


@pytest.mark.parametrize("name", [m["name"] for m in SPEC["per_layer"]])
def test_a_reader_finds_nothing_without_a_trace(name):
    ctx = harness.Ctx(workload="", chips=1, elapsed_s=1.0, setup_s=1.0, latencies_s=[],
                      user_bytes=0, requests=0, spans=[], trace=None, op=None, peaks={})
    assert harness.read_metric(name, ctx) is None
