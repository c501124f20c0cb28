"""Benchmark entry point: one function per paper table/figure.
Prints ``name,us_per_call,derived`` CSV lines.

  PYTHONPATH=src python -m benchmarks.run            # all
  PYTHONPATH=src python -m benchmarks.run table1     # one
"""
from __future__ import annotations

import sys

from benchmarks import (bench_parle, bench_serve, comm_volume, fig1_overlap,
                        kernel_bench, table1_baselines, table2_split_data)

SUITES = {
    "table1": table1_baselines.main,     # Parle vs baselines (Table 1)
    "table2": table2_split_data.main,    # data splitting (Table 2, §5)
    "fig1": fig1_overlap.main,           # overlap / one-shot avg (§1.2)
    # comm_volume grew a CLI (--mesh); pass an empty argv so the suite
    # runner's own argv (the suite names) doesn't leak into its parser
    "comm": lambda: comm_volume.main([]),  # §4.1 communication accounting
    "kernels": kernel_bench.main,        # Pallas kernel oracle micro-bench
    "parle": bench_parle.main,           # BENCH_parle.json perf trajectory
    "serve": bench_serve.main,           # BENCH_serve.json engine vs naive
}


def main() -> None:
    wanted = sys.argv[1:] or list(SUITES)
    for name in wanted:
        print(f"# --- {name} ---", flush=True)
        SUITES[name]()


if __name__ == '__main__':
    main()
