"""Benchmark entry point: ``PYTHONPATH=src python -m benchmarks.run``.

Runs, in order:
  1. paper tables 1–6 (AlexNet/ResNet20 × CIFAR10/100, f32 vs AdaPT,
     accuracy + the paper's analytical perf model),
  2. the beyond-paper LM transfer benchmark,
  3. the roofline table from any dry-run records present.

``--quick`` shrinks step counts (CI); ``--skip-cifar`` etc. select stages.
"""
from __future__ import annotations

import argparse
import sys
import time


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--skip-cifar", action="store_true")
    ap.add_argument("--skip-lm", action="store_true")
    ap.add_argument("--skip-roofline", action="store_true")
    ap.add_argument("--skip-ablations", action="store_true")
    args = ap.parse_args(argv)

    t0 = time.time()
    if not args.skip_cifar:
        from benchmarks import paper_tables
        paper_tables.run_all(quick=args.quick)
    if not args.skip_lm:
        from benchmarks import lm_bench
        lm_bench.run(steps=40 if args.quick else 120)
    if not args.skip_ablations:
        from benchmarks import ablations
        print("\n== Ablations (paper §6) ==")
        ablations.run(steps=60 if args.quick else 150)
    if not args.skip_roofline:
        from benchmarks import roofline_table
        roofline_table.main()
    print(f"\n[benchmarks] total {time.time() - t0:.0f}s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
