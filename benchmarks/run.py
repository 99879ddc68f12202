"""Benchmark harness aggregator — one function per paper table.

Prints ``name,us_per_call,derived`` CSV lines; detailed JSON lands in
experiments/benchmarks/.  `--full` uses the whole corpus (slower).
"""
from __future__ import annotations

import argparse
import sys
import time


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="use the full corpus")
    args = ap.parse_args(argv)
    fast = not args.full

    from . import (
        engine_batched,
        jax_throughput,
        table1_window,
        table2_maxlen,
        table3_combined,
        table4_throughput,
    )

    jobs = [
        ("table1_single_vs_multi", table1_window.run,
         lambda r: f"attenuation {r['rows'][0]['attenuation_pct']}..{r['rows'][-1]['attenuation_pct']}% (paper 0.86..5.39)"),
        ("table2_maxlen_cap", table2_maxlen.run,
         lambda r: f"att@36 {min(r['attenuation_36_pct'])}..{max(r['attenuation_36_pct'])}% (paper 4.46..8.23) monotone={r['monotone_in_cap']}"),
        ("table3_combined", table3_combined.run,
         lambda r: f"attenuation {r['rows'][0]['attenuation_pct']}..{r['rows'][-1]['attenuation_pct']}% (paper 4.93..11.68)"),
        ("table4_throughput", table4_throughput.run,
         lambda r: f"ours {r['ours']['gbps']}Gb/s (paper 16.10) baseline {r['baseline_multi_match']['gbps']}Gb/s speedup {r['speedup_vs_baseline']}x (paper 2.648x)"),
        ("jax_engine_throughput", jax_throughput.run,
         lambda r: f"cpu {r['cpu_mbps_batch']}MB/s; v5e roofline {r['tpu_v5e_roofline_gbps_per_chip']}Gb/s/chip"),
        ("engine_batched", engine_batched.run,
         lambda r: f"serial {r['serial_blocks_per_s']} blk/s; best batched "
                   f"{r['speedup_best_vs_serial']}x"),
    ]
    print("name,us_per_call,derived")
    for name, fn, describe in jobs:
        t0 = time.perf_counter()
        result = fn(fast=fast)
        dt_us = (time.perf_counter() - t0) * 1e6
        print(f"{name},{dt_us:.0f},{describe(result)}")
        sys.stdout.flush()


if __name__ == "__main__":
    from repro import compile_cache

    compile_cache.enable()
    main()
