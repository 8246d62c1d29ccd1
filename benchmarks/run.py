"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (benchmarks/common.emit) and
writes a machine-readable ``BENCH_<timestamp>.json`` snapshot of the same rows
so the perf trajectory accumulates one artifact per run.
"""

from __future__ import annotations

import os
import sys


def main() -> None:
    from benchmarks import (
        bench_bayesnet,
        bench_drift,
        bench_fig1_device,
        bench_fig2_logic,
        bench_fig3_inference,
        bench_fig4_fusion,
        bench_latency,
        bench_reliability,
        bench_roofline,
        bench_serve,
        bench_table_s1,
        common,
    )
    from repro.kernels import backend

    backend.enable_compile_cache()
    out_dir = sys.argv[1] if len(sys.argv) > 1 else "."
    print("name,us_per_call,derived")
    for mod in (
        bench_fig1_device,
        bench_fig2_logic,
        bench_table_s1,
        bench_fig3_inference,
        bench_fig4_fusion,
        bench_bayesnet,
        bench_reliability,
        bench_serve,
        bench_drift,
        bench_latency,
        bench_roofline,
    ):
        print(f"# --- {mod.__name__} ---")
        mod.run()
    report = bench_drift.write_drift_report(
        os.path.join(out_dir, "drift_report.csv")
    )
    print(f"# wrote {report}")
    path = common.write_json(out_dir)
    print(f"# wrote {path}")


if __name__ == "__main__":
    main()
