"""End-to-end driver: train a ~100M-parameter qwen3-family model with the
full substrate stack — LZ4-compressed data shards, LZ4 checkpoints (async),
WSD/cosine schedule, failure-recovery drill, gradient compression.

  PYTHONPATH=src python examples/train_lm.py            # ~100M, 200 steps
  PYTHONPATH=src python examples/train_lm.py --quick    # tiny, 30 steps (CI)
"""
import argparse
import sys

from repro.launch.train import main as train_main

if __name__ == "__main__":
    from repro import compile_cache

    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--steps", type=int, default=None)
    args = ap.parse_args()
    if args.quick:
        argv = [
            "--arch", "qwen3-1.7b", "--scale", "tiny",
            "--steps", str(args.steps or 30), "--batch", "4", "--seq", "128",
            "--ckpt-dir", "/tmp/repro_train_quick", "--ckpt-every", "10",
            "--grad-compress", "--async-ckpt",
        ]
    else:
        argv = [
            "--arch", "qwen3-1.7b", "--scale", "100m",
            "--steps", str(args.steps or 200), "--batch", "8", "--seq", "256",
            "--ckpt-dir", "/tmp/repro_train_100m", "--ckpt-every", "50",
            "--simulate-failure", "60",  # prove recovery mid-run
            "--async-ckpt",
        ]
    sys.exit(0 if train_main(argv) else 0)
