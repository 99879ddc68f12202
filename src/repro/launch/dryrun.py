import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this lowers the REAL step function (train_step for train
shapes, prefill/decode for serving shapes) against ShapeDtypeStruct inputs
carrying full NamedShardings — no array is ever allocated — then compiles and
records:

  * memory_analysis()  (per-device bytes; analytic fallback on CPU backends)
  * cost_analysis()    (per-device FLOPs / bytes accessed)
  * the collective schedule parsed from the post-SPMD HLO text
    (all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute)

Results are one JSON per cell under experiments/dryrun/.

Usage:
  python -m repro.launch.dryrun --arch mixtral-8x7b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --mesh both --skip-existing
"""
import argparse
import json
import re
import sys
import time
import traceback

import jax
import numpy as np

from repro.configs.base import SHAPES, ModelConfig, cells, get_config
from repro.distributed.sharding import use_mesh
from repro.launch.mesh import make_production_mesh
from repro.launch import steps as steps_mod
from repro.optim import adamw

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..", "experiments", "dryrun")

_COLL_RE = re.compile(
    r"=\s*([a-z0-9\[\],{}() ]*?)\b"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(", re.IGNORECASE,
)
_SHAPE_RE = re.compile(r"(f64|f32|bf16|f16|f8e4m3fn|f8e5m2|s64|s32|s16|s8|u64|u32|u16|u8|pred)\[([0-9,]*)\]")

_DTYPE_BYTES = {
    "f64": 8, "s64": 8, "u64": 8, "f32": 4, "s32": 4, "u32": 4,
    "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
    "f8e4m3fn": 1, "f8e5m2": 1, "s8": 1, "u8": 1, "pred": 1,
}


def _shape_bytes(type_str: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.group(1), m.group(2)
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def parse_collectives(hlo_text: str) -> dict:
    """Sum per-device result bytes of every collective op in the HLO."""
    stats: dict[str, dict] = {}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        op = m.group(2).lower()
        # result type(s): everything on the line up to the opcode
        head = line.split("=", 1)
        res_bytes = _shape_bytes(head[1].split(m.group(2))[0]) if len(head) > 1 else 0
        s = stats.setdefault(op, {"count": 0, "bytes": 0})
        s["count"] += 1
        s["bytes"] += res_bytes
    return stats


def _tree_bytes(tree) -> int:
    return sum(int(np.prod(x.shape)) * x.dtype.itemsize for x in jax.tree.leaves(tree))


# ---------------------------------------------------------------------------
# Cost probes: XLA cost_analysis counts while-loop (scan) bodies ONCE, so the
# full-config numbers undercount per-layer work by the trip count.  We lower
# the same cell with segment repeats (1, then 1+e_s) and UNROLLED layer scans,
# giving the exact fixed cost + per-layer deltas; the roofline totals are
#   total = cost(repeats=1) + sum_s (repeats_s - 1) * delta_s.
# ---------------------------------------------------------------------------

def _with_repeats(cfg: ModelConfig, reps: list[int]) -> ModelConfig:
    import dataclasses

    segs = tuple(
        dataclasses.replace(s, repeats=r) for s, r in zip(cfg.segments, reps)
    )
    return dataclasses.replace(
        cfg, segments=segs, unroll_layers=True,
        n_layers=sum(len(s.unit) * s.repeats for s in segs),
    )


def _lower_cell(cfg: ModelConfig, shape, mesh, optimized: bool = False):
    if optimized:
        cfg = steps_mod.optimized_config(cfg, shape, mesh)
    if shape.mode == "train":
        params_sh, opt_sh, batch_sh = steps_mod.train_state_structs(cfg, shape, mesh)
        fn = steps_mod.make_train_step(cfg, adamw.AdamWConfig())
        return jax.jit(fn, donate_argnums=(0, 1)).lower(params_sh, opt_sh, batch_sh)
    cfg = steps_mod.serving_config(cfg, mesh)
    if shape.mode == "prefill":
        params_sh, _, batch_sh = steps_mod.train_state_structs(cfg, shape, mesh)
        fn = steps_mod.make_prefill_step(cfg, cache_len=shape.seq_len + 128)
        return jax.jit(fn).lower(params_sh, batch_sh)
    params_sh, cache_sh, tokens_sh = steps_mod.decode_state_structs(cfg, shape, mesh)
    fn = steps_mod.make_decode_step(cfg)
    return jax.jit(fn, donate_argnums=(1,)).lower(params_sh, cache_sh, tokens_sh)


_DOT_RE = re.compile(r"=\s*[a-z0-9\[\],{} ]+?\s(dot|convolution)\(")


def parse_dot_bytes(hlo_text: str) -> int:
    """Operand+result bytes of every dot — the fused-TPU memory-term floor.

    XLA:CPU barely fuses elementwise chains, so raw `bytes accessed` reflects
    CPU lowering, not TPU HBM traffic; on TPU everything except matmul
    streams, collectives and layer-boundary tensors lives in fused kernels.
    """
    total = 0
    for line in hlo_text.splitlines():
        if not _DOT_RE.search(line):
            continue
        for m in _SHAPE_RE.finditer(line):
            n = 1
            if m.group(2):
                for d in m.group(2).split(","):
                    n *= int(d)
            total += n * _DTYPE_BYTES[m.group(1)]
    return total


def _cost_of(compiled) -> dict:
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    hlo = compiled.as_text()
    coll = parse_collectives(hlo)
    return {
        "flops": float((cost or {}).get("flops", 0.0)),
        "bytes": float((cost or {}).get("bytes accessed", 0.0)),
        "dot_bytes": float(parse_dot_bytes(hlo)),
        "coll_bytes": float(sum(v["bytes"] for v in coll.values())),
        "coll": coll,
    }


def _coll_totals(coll: dict) -> dict:
    return {op: float(v["bytes"]) for op, v in coll.items()}


def probe_costs(cfg: ModelConfig, shape, mesh) -> dict:
    """Extrapolated per-device cost totals for the full depth.

    Baseline at repeats=2 (XLA's SPMD strategy is stable for >=2 unrolled
    layers; repeats=1 triggers different global decisions), increment one
    segment to 3: total = cost(2) + (R_s - 2) * delta_s, verified linear in
    tests/test_dryrun.py.
    """
    nseg = len(cfg.segments)
    base_reps = [2] * nseg
    base = _cost_of(_lower_cell(_with_repeats(cfg, base_reps), shape, mesh).compile())
    keys = ("flops", "bytes", "dot_bytes", "coll_bytes")
    total = {k: base[k] for k in keys}
    coll_total = _coll_totals(base["coll"])
    deltas = []
    for s in range(nseg):
        reps = list(base_reps)
        reps[s] += 1
        probe = _cost_of(_lower_cell(_with_repeats(cfg, reps), shape, mesh).compile())
        delta = {k: probe[k] - base[k] for k in keys}
        delta_coll = {
            op: probe["coll"].get(op, {"bytes": 0})["bytes"] - coll_total.get(op, 0.0)
            for op in set(coll_total) | set(probe["coll"])
        }
        deltas.append({**delta, "coll": delta_coll})
        extra = cfg.segments[s].repeats - 2
        for k in keys:
            total[k] = max(0.0, total[k] + extra * delta[k])
        for op, b in delta_coll.items():
            coll_total[op] = max(0.0, coll_total.get(op, 0.0) + extra * b)
    total["coll_bytes"] = sum(coll_total.values())
    return {"base": {k: base[k] for k in keys}, "deltas": deltas,
            "total": total, "coll_by_op": coll_total}


def run_cell(arch: str, shape_name: str, multi_pod: bool, verbose: bool = True,
             optimized: bool = False) -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    if optimized:
        with use_mesh(mesh):
            cfg = steps_mod.optimized_config(cfg, shape, mesh)
    chips = mesh.size
    rec = {
        "arch": arch, "shape": shape_name, "optimized": optimized,
        "mesh": "2x16x16" if multi_pod else "16x16", "chips": chips,
        "mode": shape.mode, "time": time.time(),
    }
    t0 = time.time()
    with use_mesh(mesh):
        lowered = _lower_cell(cfg, shape, mesh)
        scfg = cfg if shape.mode == "train" else steps_mod.serving_config(cfg, mesh)
        params_s = jax.eval_shape(
            lambda: __import__("repro.models.lm", fromlist=["lm"]).init_params(
                jax.random.PRNGKey(0), scfg
            )
        )
        rec["param_bytes"] = _tree_bytes(params_s)
        if shape.mode == "train":
            rec["opt_bytes"] = 2 * rec["param_bytes"]
        if shape.mode == "decode":
            import functools as _ft

            from repro.models import lm as _lm

            cache_s = jax.eval_shape(
                _ft.partial(_lm.init_cache, scfg, shape.global_batch, shape.seq_len)
            )
            rec["cache_bytes"] = _tree_bytes(cache_s)
        rec["lower_s"] = time.time() - t0
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = time.time() - t1

        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        rec["cost"] = {
            k: float(v)
            for k, v in (cost or {}).items()
            if isinstance(v, (int, float)) and (
                k in ("flops", "bytes accessed", "optimal_seconds")
                or k.startswith("bytes accessed")
            )
        }
        try:
            mem = compiled.memory_analysis()
            if mem is not None:
                rec["memory"] = {
                    a: int(getattr(mem, a))
                    for a in (
                        "argument_size_in_bytes", "output_size_in_bytes",
                        "temp_size_in_bytes", "generated_code_size_in_bytes",
                        "alias_size_in_bytes",
                    )
                    if hasattr(mem, a)
                }
        except Exception as e:  # pragma: no cover - backend dependent
            rec["memory_error"] = str(e)
        hlo = compiled.as_text()
        rec["collectives"] = parse_collectives(hlo)
        rec["hlo_bytes"] = len(hlo)
        if not multi_pod:  # roofline table is single-pod only (see spec)
            t2 = time.time()
            rec["probe"] = probe_costs(cfg, shape, mesh)
            rec["probe_s"] = time.time() - t2
    if verbose:
        coll = sum(v["bytes"] for v in rec["collectives"].values())
        print(
            f"[dryrun] {arch} {shape_name} {rec['mesh']}: "
            f"lower {rec['lower_s']:.1f}s compile {rec['compile_s']:.1f}s "
            f"flops/dev {rec['cost'].get('flops', 0):.3e} "
            f"coll/dev {coll/1e6:.1f}MB",
            flush=True,
        )
    return rec


def run_lz4_cell(multi_pod: bool, scan_impl: str = "associative",
                 blocks: int = 8192, hash_bits: int = 8,
                 verbose: bool = True) -> dict:
    """Dry-run the paper's own workload: the LZ4 engine over a sharded batch
    of 64 KB blocks (embarrassingly parallel over all mesh axes).

    The associative-scan selection keeps the whole program while-loop-free,
    so cost_analysis is exact (no probe extrapolation needed).
    """
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from jax.sharding import NamedSharding

    from repro.core.jax_compressor import _PAD, compress_blocks_records
    from repro.core.lz4_types import MAX_BLOCK

    mesh = make_production_mesh(multi_pod=multi_pod)
    axes = tuple(mesh.axis_names)
    rec = {
        "arch": "lz4-engine", "shape": f"blocks{blocks}_{scan_impl}",
        "mesh": "2x16x16" if multi_pod else "16x16", "chips": mesh.size,
        "mode": "compress", "time": time.time(),
        "bytes_per_step": blocks * MAX_BLOCK,
    }
    with use_mesh(mesh):
        sh = NamedSharding(mesh, P(axes))
        blocks_sh = jax.ShapeDtypeStruct((blocks, MAX_BLOCK + _PAD), jnp.uint8, sharding=sh)
        ns_sh = jax.ShapeDtypeStruct((blocks,), jnp.int32, sharding=sh)

        def step(bufs, ns):
            out = compress_blocks_records(
                bufs, ns, hash_bits=hash_bits, scan_impl=scan_impl,
            )
            return out.size.astype(jnp.int64).sum(), out.emit.sum()

        t0 = time.time()
        lowered = jax.jit(step).lower(blocks_sh, ns_sh)
        compiled = lowered.compile()
        rec["compile_s"] = time.time() - t0
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        rec["cost"] = {
            "flops": float((cost or {}).get("flops", 0.0)),
            "bytes": float((cost or {}).get("bytes accessed", 0.0)),
        }
        rec["collectives"] = parse_collectives(compiled.as_text())
        try:
            mem = compiled.memory_analysis()
            rec["memory"] = {"temp_size_in_bytes": int(mem.temp_size_in_bytes)}
        except Exception:
            pass
        rec["probe"] = {  # same schema as LM cells for the roofline reader
            "total": {
                "flops": rec["cost"]["flops"],
                "bytes": rec["cost"]["bytes"],
                "coll_bytes": float(
                    sum(v["bytes"] for v in rec["collectives"].values())
                ),
            },
            "coll_by_op": {k: float(v["bytes"]) for k, v in rec["collectives"].items()},
        }
    if verbose:
        print(
            f"[dryrun] lz4-engine {rec['shape']} {rec['mesh']}: "
            f"compile {rec['compile_s']:.1f}s flops/dev {rec['cost']['flops']:.3e} "
            f"bytes/dev {rec['cost']['bytes']:.3e} "
            f"coll/dev {rec['probe']['total']['coll_bytes']/1e6:.1f}MB",
            flush=True,
        )
    return rec


def cell_path(arch: str, shape_name: str, multi_pod: bool, optimized: bool = False) -> str:
    mesh = ("multi" if multi_pod else "single") + ("_opt" if optimized else "")
    os.makedirs(OUT_DIR, exist_ok=True)
    return os.path.join(OUT_DIR, f"{arch}__{shape_name}__{mesh}.json")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--lz4", action="store_true",
                    help="run the lz4-engine cells (paper's own workload)")
    ap.add_argument("--reprobe", action="store_true",
                    help="refresh only the probe costs of existing cell JSONs")
    ap.add_argument("--optimized", action="store_true",
                    help="apply the beyond-paper optimized posture (see steps.optimized_config)")
    args = ap.parse_args(argv)

    if args.reprobe:
        mesh = make_production_mesh()
        for arch, shape_name in cells():
            path = cell_path(arch, shape_name, False)
            if not os.path.exists(path):
                continue
            with open(path) as f:
                rec = json.load(f)
            with use_mesh(mesh):
                t0 = time.time()
                rec["probe"] = probe_costs(get_config(arch), SHAPES[shape_name], mesh)
                rec["probe_s"] = time.time() - t0
            with open(path, "w") as f:
                json.dump(rec, f, indent=1)
            print(f"[reprobe] {arch} {shape_name} {rec['probe_s']:.0f}s", flush=True)
        return

    meshes = [False, True] if args.mesh == "both" else [args.mesh == "multi"]
    if args.lz4:
        # associative selection only: it is while-loop-free, so cost_analysis
        # is exact (the sequential variant hides 8192 scan steps from XLA's
        # counter; its wall-clock comparison lives in benchmarks/jax_throughput)
        for multi in meshes:
            rec = run_lz4_cell(multi, scan_impl="associative")
            path = cell_path("lz4-engine", rec["shape"], multi)
            with open(path, "w") as f:
                json.dump(rec, f, indent=1)
        return
    todo = cells() if args.all else [(args.arch, args.shape)]
    failures = []
    for arch, shape_name in todo:
        for multi in meshes:
            path = cell_path(arch, shape_name, multi, args.optimized)
            if args.skip_existing and os.path.exists(path):
                continue
            try:
                rec = run_cell(arch, shape_name, multi, optimized=args.optimized)
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1)
            except Exception:
                failures.append((arch, shape_name, multi))
                print(f"[dryrun] FAILED {arch} {shape_name} multi={multi}", flush=True)
                traceback.print_exc()
    if failures:
        print(f"[dryrun] {len(failures)} failures: {failures}", flush=True)
        sys.exit(1)
    print("[dryrun] all cells OK", flush=True)


if __name__ == "__main__":
    main()
