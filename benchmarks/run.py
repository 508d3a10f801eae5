"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV per block.  Default is quick mode
(2 SNNs, short profiling window — CI-friendly); ``--full`` reproduces the
paper-scale runs (all 5 SNNs at Table 1 spike counts) used in
EXPERIMENTS.md.
"""
from __future__ import annotations

import argparse
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--full", action="store_true",
                    help="paper-scale runs (all 5 SNNs, Table 1 spike counts)")
    ap.add_argument("--only", choices=["partition", "mapping",
                                       "mapping_engine", "overall",
                                       "exec_time", "kernels", "nocsim",
                                       "faults", "sweep", "scale"])
    args = ap.parse_args()

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    from . import (bench_exec_time, bench_faults, bench_kernels,
                   bench_mapping_algos, bench_nocsim, bench_overall,
                   bench_partition, bench_scale, bench_sweep)

    suites = {
        "partition": bench_partition.run,
        "mapping": bench_mapping_algos.run,
        "mapping_engine": bench_mapping_algos.run_engines,
        "overall": bench_overall.run,
        "exec_time": bench_exec_time.run,
        "kernels": bench_kernels.run,
        "nocsim": bench_nocsim.run,
        "faults": bench_faults.run,
        "sweep": bench_sweep.run,
        "scale": bench_scale.run,
    }
    if args.only:
        suites = {args.only: suites[args.only]}
    t0 = time.perf_counter()
    for name, fn in suites.items():
        print(f"\n=== {name} ===", file=sys.stderr)
        fn(full=args.full)
    print(f"\n# benchmarks done in {time.perf_counter() - t0:.1f}s",
          file=sys.stderr)


if __name__ == "__main__":
    main()
