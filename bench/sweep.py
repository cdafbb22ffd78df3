#!/usr/bin/env python3
"""Find a serving cell's knee: the highest offered rate whose backlog does
not grow over a window.

    python3 bench/sweep.py --workload covtype.serve --rates 1000 2000 4000 --seconds 10

One process sets the cell up once (bank, engine, warm-up), then offers
each rate open loop for ``--seconds`` and prints one JSON line per rate:
p50/p95 latency, the latency of the first and the last fifth of the
requests (a backlog that grows shows as a last fifth far slower than the
first), how late the generator ran, the waves launched and the compiles.  The rate a
cell's traffic file states is 0.8 x the knee found here; it is written
into the file as a number, and the benchmark never searches for it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run as harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    import jax
    if not args.rehearse and jax.devices()[0].platform != "tpu":
        print("error: the sweep runs on the chip", file=sys.stderr)
        return 2
    harness.compile_cache(jax)
    cell, cfg, traffic, _, _ = harness.load_cell(args.workload, args.rehearse)
    ctx = harness.Ctx(cell, cfg, traffic, args.seed, args.seconds)
    drv = harness.load_module(os.path.join(HERE, "drivers",
                                           traffic["driver"] + ".py"))
    st = drv.setup(ctx)
    clock = harness.CompileClock()
    rng = np.random.default_rng(args.seed)
    xte = st["xte"]
    for rate in args.rates:
        n = int(round(rate * args.seconds))
        rows = xte[rng.choice(len(xte), size=n, replace=n > len(xte))]
        due = np.sort(rng.uniform(0.0, args.seconds, n))
        w0 = st["eng"].stats()["per_stage"]["pack"]["count"]
        c0 = clock.count
        r = drv._serve(st["eng"], rows, due)
        lat = r["lat"][np.isfinite(r["lat"])]
        fifth = max(len(lat) // 5, 1)
        print(json.dumps({
            "rate_per_s": rate, "requests": n, "answered": int(len(lat)),
            "p50_ms": float(np.quantile(lat, 0.5)),
            "p95_ms": float(np.quantile(lat, 0.95)),
            "first_fifth_p50_ms": float(np.median(lat[:fifth])),
            "last_fifth_p50_ms": float(np.median(lat[-fifth:])),
            "generator_late_ms_max": float(r["late"].max()),
            "waves": st["eng"].stats()["per_stage"]["pack"]["count"] - w0,
            "compiles": clock.count - c0,
            "wall_s": r["wall"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
