#!/usr/bin/env python3
"""Read a cell's compared numbers for its control, on the chip.

    python3 bench/control.py --workload <cell> --seeds 11,12,13 --seconds 3 [--program]

The control is the program at the settings the cell's mix names under
``control``, which break one guarantee the configuration states (the
writer at another parallel window; a restore or page read without its
integrity check).  Each seed is one run of the cell through `harness.run`,
in this one process, at the cell's own sizes and load; the benchmark's own
runs never take this path.  ``--program`` runs the program's own path
instead, for the lower readings.  One JSON line per seed on standard output:
``{"seed", "control", "correct", "checks"}``.  The limits in `bench/ops/`
were set from these readings (see PERF.md).
"""
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--program", action="store_true", help="the program's path, not the control")
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from bench import harness

    try:
        for seed in (int(s) for s in args.seeds.split(",")):
            r = harness.run(args.workload, seed, args.seconds, False, time.perf_counter(),
                            control=not args.program)
            print(json.dumps({"seed": seed, "control": not args.program, "correct": r["correct"],
                              "attempted": r["attempted"], "checks": r["checks"]}), flush=True)
    except harness.NoChip as e:
        print(e, file=sys.stderr, flush=True)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
