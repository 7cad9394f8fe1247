"""Benchmark harness: one entry per paper table/figure + kernels + roofline.

Prints ``name,us_per_call,derived`` CSV lines (brief: deliverable d).
"""
from __future__ import annotations

import argparse


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="jet|svhn|muon|fig2|kernels|roofline")
    args = ap.parse_args()
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    print("name,us_per_call,derived")
    only = args.only

    from . import kernel_bench, paper_tables, roofline_table
    if only in (None, "kernels"):
        kernel_bench.bench_kernels()
    if only in (None, "roofline"):
        roofline_table.bench_roofline()
    if only in (None, "jet"):
        paper_tables.bench_table1_jet()
    if only in (None, "muon"):
        paper_tables.bench_table3_muon()
    if only in (None, "fig2"):
        paper_tables.bench_fig2_resource_estimation()
    if only in (None, "svhn"):
        paper_tables.bench_table2_svhn()


if __name__ == "__main__":
    main()
