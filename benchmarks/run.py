"""Benchmark orchestrator: one section per paper table.

``PYTHONPATH=src python -m benchmarks.run [--tables table1,table3]``
Quick mode by default; set REPRO_BENCH_FULL=1 for paper-scale sizes.
The ``solver`` table (benchmarks.roofline) covers the fused wave-level CD
solver: wave-vs-per-slot wall clock, warm-start iteration counts, and the
analytic flops/byte roofline; it writes ``BENCH_solver.json``.
"""
from __future__ import annotations

import argparse
import sys
import time

from benchmarks.common import QUICK, Report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tables",
                    default="table1,table2,table3,table4,table10,gram_reuse,"
                            "serve,serve_micro,cells,robustness,embed,solver")
    args = ap.parse_args(argv)
    tables = args.tables.split(",")
    report = Report()
    t0 = time.time()
    print(f"# benchmarks (quick={QUICK})  — csv: table,name,us,derived",
          flush=True)

    if "table1" in tables:
        from benchmarks import table1_small
        table1_small.run(report)
    if "table2" in tables:
        from benchmarks import table2_multiclass
        table2_multiclass.run(report)
    if "table3" in tables:
        from benchmarks import table3_cells
        table3_cells.run(report)
    if "table4" in tables:
        from benchmarks import table4_distributed
        table4_distributed.run(report)
    if "table10" in tables:
        from benchmarks import table10_configs
        table10_configs.run(report)
    if "gram_reuse" in tables:
        from benchmarks import gram_reuse
        gram_reuse.run(report)
    if "serve" in tables:
        from benchmarks import serve_throughput
        serve_throughput.run(report)
    if "serve_micro" in tables:
        from benchmarks import serve_microbench
        serve_microbench.run(report)
    if "cells" in tables:
        from benchmarks import cell_build
        cell_build.run(report)
    if "robustness" in tables:
        from benchmarks import robustness
        robustness.run(report)
    if "embed" in tables:
        from benchmarks import embed_bench
        embed_bench.run(report)
    if "solver" in tables:
        from benchmarks import roofline
        roofline.run(report)

    print(f"\n# done in {time.time() - t0:.0f}s")
    for t in ("table1", "table2", "table3", "table4", "table10", "gram_reuse",
              "serve", "serve_micro", "cells", "robustness", "embed",
              "solver"):
        md = report.table_markdown(t)
        if md:
            print(f"\n## {t}\n{md}")
    return 0


if __name__ == "__main__":
    from repro.kernels.runtime import enable_compile_cache
    enable_compile_cache()
    sys.exit(main())
