"""Benchmark harness: one module per paper table + system microbenches.

Prints ``name,us_per_call,derived`` CSV rows (one per cell).
"""
from __future__ import annotations

import sys
import time


def main() -> None:
    from . import bench_kernel, bench_roofline, bench_scaling
    from . import bench_serving, bench_table4, bench_table5

    print("name,us_per_call,derived")
    t0 = time.time()
    for mod, tag in (
        (bench_table4, "table4 (PM vs GT, artificial data)"),
        (bench_table5, "table5 (PM vs GT, Enron-like data)"),
        (bench_scaling, "mining scaling"),
        (bench_kernel, "match kernel micro"),
        (bench_serving, "pattern serving vs host oracle"),
        (bench_roofline, "roofline table from dry-run"),
    ):
        print(f"# --- {tag} ---", file=sys.stderr)
        mod.main()  # a failing phase fails the run
    print(f"# total {time.time()-t0:.1f}s", file=sys.stderr)


if __name__ == "__main__":
    main()
