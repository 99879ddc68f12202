"""Perf hillclimb driver: lower a cell with config variants and report the
three roofline terms per variant (hypothesis -> change -> measure -> record).

  python -m repro.launch.hillclimb --cell internlm2-train
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse
import dataclasses
import json
import time

import jax

from repro.configs.base import SHAPES, get_config
from repro.distributed.sharding import use_mesh
from repro.launch.dryrun import OUT_DIR, _cost_of, _lower_cell, _with_repeats, probe_costs
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import HBM_BW, LINK_BW, PEAK_FLOPS, model_flops


def measure(cfg, shape, mesh, label: str) -> dict:
    t0 = time.time()
    probe = probe_costs(cfg, shape, mesh)
    tot = probe["total"]
    out = {
        "label": label,
        "compute_s": tot["flops"] / PEAK_FLOPS,
        "memory_s": tot["bytes"] / HBM_BW,
        # fused-TPU memory estimate: dot traffic + collectives (CPU HLO leaves
        # elementwise unfused, inflating raw `bytes accessed`; see DESIGN.md)
        "memory_fused_s": (tot.get("dot_bytes", 0.0) + tot["coll_bytes"]) / HBM_BW,
        "collective_s": tot["coll_bytes"] / LINK_BW,
        "flops": tot["flops"], "bytes": tot["bytes"],
        "dot_bytes": tot.get("dot_bytes", 0.0), "coll_bytes": tot["coll_bytes"],
        "coll_by_op": probe.get("coll_by_op", {}),
        "wall_s": time.time() - t0,
    }
    mf = model_flops(cfg, shape, mesh.size)
    bound = max(out["compute_s"], out["memory_s"], out["collective_s"])
    bound_fused = max(out["compute_s"], out["memory_fused_s"], out["collective_s"])
    out["useful_ratio"] = mf / max(tot["flops"], 1.0)
    out["roofline_fraction"] = (mf / PEAK_FLOPS) / bound if bound else 0.0
    out["roofline_fraction_fused"] = (mf / PEAK_FLOPS) / bound_fused if bound_fused else 0.0
    print(
        f"[{label:>28}] comp {out['compute_s']*1e3:8.1f}ms  "
        f"mem {out['memory_s']*1e3:8.1f}ms (fused {out['memory_fused_s']*1e3:7.1f})  "
        f"coll {out['collective_s']*1e3:8.1f}ms  "
        f"useful {out['useful_ratio']:.2f}  frac {out['roofline_fraction']:.4f}"
        f" (fused {out['roofline_fraction_fused']:.3f})",
        flush=True,
    )
    return out


def cell_internlm2_train(variants=None):
    cfg0 = get_config("internlm2-1.8b")
    shape = SHAPES["train_4k"]
    mesh = make_production_mesh()
    results = []
    with use_mesh(mesh):
        results.append(measure(cfg0, shape, mesh, "baseline (paper-faithful)"))
        results.append(
            measure(dataclasses.replace(cfg0, seq_shard=True), shape, mesh,
                    "H2: megatron-SP residual")
        )
        results.append(
            measure(dataclasses.replace(cfg0, remat_policy="dots"), shape, mesh,
                    "H3: dots-saveable remat")
        )
        results.append(
            measure(dataclasses.replace(cfg0, seq_shard=True, remat_policy="dots"),
                    shape, mesh, "H2+H3 combined")
        )
        results.append(
            measure(dataclasses.replace(cfg0, pure_dp=True, remat_policy="dots"),
                    shape, mesh, "H4: pure-DP (model axis=DP)")
        )
    return results


def cell_gemma2_long_decode():
    """Most collective-bound cell: 500k-token decode, seq-sharded KV cache."""
    cfg0 = get_config("gemma2-9b")
    shape = SHAPES["long_500k"]
    mesh = make_production_mesh()
    from repro.launch.steps import serving_config

    results = []
    with use_mesh(mesh):
        base = serving_config(cfg0, mesh)
        results.append(measure(base, shape, mesh, "baseline (dus cache write)"))
        results.append(
            measure(dataclasses.replace(base, cache_update="masked"), shape, mesh,
                    "H1: masked cache update")
        )
        pinned = dataclasses.replace(base, decode_cache_axes=("data", "model"))
        results.append(
            measure(pinned, shape, mesh, "H2: pin flash-decode sharding")
        )
        results.append(
            measure(dataclasses.replace(pinned, cache_update="masked"), shape, mesh,
                    "H2+H1 pinned + masked")
        )
    return results


def cell_lz4_engine():
    """The paper's own workload: iterate the engine's roofline."""
    from repro.launch.dryrun import run_lz4_cell

    results = []
    for label, kw in [
        ("baseline associative", dict(scan_impl="associative")),
        ("hash_bits=12 (4K entries)", dict(scan_impl="associative", hash_bits=12)),
    ]:
        rec = run_lz4_cell(False, verbose=False, **kw)
        tot = rec["probe"]["total"]
        out = {
            "label": label,
            "compute_s": tot["flops"] / PEAK_FLOPS,
            "memory_s": tot["bytes"] / HBM_BW,
            "collective_s": tot["coll_bytes"] / LINK_BW,
            "bytes_per_step": rec["bytes_per_step"],
        }
        bound = max(out["compute_s"], out["memory_s"], out["collective_s"])
        out["gbps_per_chip"] = rec["bytes_per_step"] / rec["chips"] / bound * 8 / 1e9
        print(f"[{label:>28}] comp {out['compute_s']*1e3:8.1f}ms mem {out['memory_s']*1e3:8.1f}ms "
              f"coll {out['collective_s']*1e3:8.1f}ms -> {out['gbps_per_chip']:.1f} Gb/s/chip",
              flush=True)
        results.append(out)
    return results


def cell_small_arch_posture():
    """Beyond-paper posture fix for the small archs with padded/replicated
    attention (whisper 12 heads, minicpm 36 heads vs TP=16): pure DP."""
    shape = SHAPES["train_4k"]
    mesh = make_production_mesh()
    results = []
    with use_mesh(mesh):
        for arch in ("whisper-small", "minicpm-2b", "xlstm-125m"):
            cfg0 = get_config(arch)
            results.append(measure(cfg0, shape, mesh, f"{arch} baseline"))
            results.append(
                measure(
                    dataclasses.replace(cfg0, pure_dp=True, remat_policy="dots",
                                        fsdp=True),
                    shape, mesh, f"{arch} pure-DP+dots",
                )
            )
    return results


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", default="internlm2-train")
    args = ap.parse_args(argv)
    fn = {
        "internlm2-train": cell_internlm2_train,
        "gemma2-long-decode": cell_gemma2_long_decode,
        "lz4-engine": cell_lz4_engine,
        "small-arch-posture": cell_small_arch_posture,
    }[args.cell]
    results = fn()
    path = os.path.join(OUT_DIR, "..", f"hillclimb_{args.cell}.json")
    with open(path, "w") as f:
        json.dump(results, f, indent=1)


if __name__ == "__main__":
    main()
