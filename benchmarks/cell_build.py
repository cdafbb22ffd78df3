"""Cell construction at scale: streaming builder vs in-memory builder.

The acceptance claim for the streaming pipeline: ``build_cells`` over an
on-disk memmap source completes at n = 1e6 with peak host memory bounded
by the chunk working set O(chunk·C + C·d) — never the (n, C) distance matrix, never a second copy
of x.  Each (n, mode) case runs in its OWN subprocess so ``ru_maxrss`` is
a clean per-case high-watermark (the in-memory case additionally holds x
itself; the streaming case holds only the memmap window + the plan).

``PYTHONPATH=src python -m benchmarks.cell_build`` — quick mode runs
n = 1e5; REPRO_BENCH_FULL=1 adds n = 1e6.  Always writes BENCH_cells.json
at the repo root so the perf trajectory is recorded.
"""
from __future__ import annotations

import argparse
import json
import os
import resource
import subprocess
import sys
import time

import numpy as np

from benchmarks.common import QUICK, Report

OUT_PATH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                        "BENCH_cells.json")

D = 8
CELL_SIZE = 2000
CHUNK = 16384


def _make_memmap(path: str, n: int, d: int, seed: int = 0) -> None:
    """Write an (n, d) .npy in chunks — the dataset never sits in RAM."""
    mm = np.lib.format.open_memmap(path, mode="w+", dtype=np.float32,
                                   shape=(n, d))
    rng = np.random.default_rng(seed)
    for lo in range(0, n, CHUNK):
        hi = min(lo + CHUNK, n)
        mm[lo:hi] = rng.normal(size=(hi - lo, d)).astype(np.float32)
    mm.flush()
    del mm


def _run_case(n: int, mode: str, path: str) -> dict:
    """One subprocess case: build cells, report seconds + peak memory.

    ``peak_rss_mb`` is the OS high-watermark (includes the Python/jax
    runtime floor, hence ``base_rss_mb``); ``peak_alloc_mb`` is the
    tracemalloc peak of Python/numpy allocations DURING the build — the
    number the O(chunk·C + C·d) working-set bound is about.
    """
    import tracemalloc
    base_rss_kb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    tracemalloc.start()
    t0 = time.perf_counter()
    if mode == "stream":
        from repro.pipeline.cell_stream import build_cells_stream
        from repro.pipeline.dataset import MemmapSource
        plan = build_cells_stream(MemmapSource(path), cell_size=CELL_SIZE,
                                  method="voronoi", seed=0, chunk_size=CHUNK)
    else:
        from repro.cells.builder import build_cells
        x = np.load(path)              # fully resident x: the RAM baseline
        plan = build_cells(x, cell_size=CELL_SIZE, method="voronoi", seed=0)
    secs = time.perf_counter() - t0
    _, peak_alloc = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    peak_rss_kb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    return {
        "n": n, "mode": mode, "seconds": secs,
        "n_cells": int(plan.n_cells), "k_max": int(plan.k_max),
        "base_rss_mb": round(base_rss_kb / 1024, 1),
        "peak_rss_mb": round(peak_rss_kb / 1024, 1),
        "peak_alloc_mb": round(peak_alloc / 2**20, 1),
        "chunk": CHUNK,
        # the streaming transient working set — O(chunk·C + chunk·d + C·d),
        # independent of n (the (chunk, C) D² block dominates):
        "working_set_mb": round((CHUNK * plan.n_cells * 4
                                 + CHUNK * D * 4
                                 + plan.n_cells * D * 4) / 2**20, 1),
    }


def run(report: Report) -> None:
    import tempfile
    sizes = [100_000] if QUICK else [100_000, 1_000_000]
    env = dict(os.environ)
    env["PYTHONPATH"] = ("src" + os.pathsep + env["PYTHONPATH"]
                         if env.get("PYTHONPATH") else "src")
    env["JAX_PLATFORMS"] = "cpu"     # the case measures host RSS, not the chip
    rows = []
    with tempfile.TemporaryDirectory() as tmp:
        for n in sizes:
            path = os.path.join(tmp, f"x_{n}.npy")
            _make_memmap(path, n, D)
            for mode in ("stream", "in_memory"):
                out = subprocess.run(
                    [sys.executable, "-m", "benchmarks.cell_build",
                     "--case", mode, "--n", str(n), "--path", path],
                    capture_output=True, text=True, env=env, check=True)
                row = json.loads(out.stdout.strip().splitlines()[-1])
                rows.append(row)
                report.add("cells", f"{mode}_n{n}", row["seconds"],
                           n_cells=row["n_cells"],
                           peak_rss_mb=row["peak_rss_mb"],
                           peak_alloc_mb=row["peak_alloc_mb"])
    payload = {"d": D, "cell_size": CELL_SIZE, "chunk": CHUNK, "cases": rows}
    with open(OUT_PATH, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"wrote {OUT_PATH}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--case", choices=["stream", "in_memory"], default=None)
    ap.add_argument("--n", type=int, default=0)
    ap.add_argument("--path", default="")
    args = ap.parse_args(argv)
    if args.case:                       # subprocess entry: one measured case
        print(json.dumps(_run_case(args.n, args.case, args.path)))
        return 0
    run(Report())
    return 0


if __name__ == "__main__":
    sys.exit(main())
