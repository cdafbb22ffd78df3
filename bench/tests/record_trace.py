#!/usr/bin/env python3
"""Record the small device trace that ``test_trace_reduce.py`` checks the
reduction against, on the chip:

    python3 bench/tests/record_trace.py --out bench/tests/data/fixture.xplane.pb

It traces one tiny training wave (``train_cells``: 2 slots of 256 rows,
10 gammas, 10 FISTA iterations) and a few serve waves of ``SVMEngine``'s
fused predict kernel, copies the ``.xplane.pb`` to ``--out`` and prints
what a reader of the trace needs to know: the device planes and lines, the
ops that took most time with their scope stats, and the reduction's
output (JSON, last line).
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))
sys.path.insert(0, BENCH)

KERNELS = {"sq_dists": "sq_dists", "gram_epilogue": "gram_from_d2",
           "svm_predict": "predict_cells"}


def workload():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import cv as cv_mod
    from repro.core.grids import liquid_grid
    from repro.distributed.cell_trainer import train_cells
    from repro.serve import SVMEngine
    from repro.serve.model_bank import ModelBank
    rng = np.random.default_rng(0)
    s, k, d = 2, 256, 54
    x = rng.normal(size=(s, k, d)).astype(np.float32)
    y = np.where(rng.uniform(size=(s, 1, k)) < 0.5, -1.0, 1.0).astype(
        np.float32)
    m = np.ones((s, k), np.float32)
    g = liquid_grid(n=k, dim=d, median_dist=10.0, cell_size=k)
    cfg = cv_mod.CVConfig(max_iters=10, keep_surface=True)
    lam_c, sub_c, task_c, n_lam, n_sub = cv_mod.grid_columns(g, cfg, 1)
    gam = np.tile(np.asarray(g.gammas)[None], (s, 1))
    keys = np.asarray(jax.random.split(jax.random.PRNGKey(0), s))
    args = [jnp.asarray(a) for a in (x, y, np.ones_like(y), m, gam, keys)]
    jax.block_until_ready(train_cells(*args, lam_c, sub_c, task_c, cfg,
                                      n_lam, n_sub))
    coefs = rng.normal(size=(8, k, 1, 1)).astype(np.float32)
    bank = ModelBank.from_cells(rng.normal(size=(8, k, d)).astype(np.float32),
                                np.ones((8, k), np.float32), coefs,
                                np.full((8, 1, 1), 10.0, np.float32),
                                rng.normal(size=(8, d)).astype(np.float32))
    eng = SVMEngine(bank)
    eng.predict(rng.normal(size=(64, d)).astype(np.float32))

    def traced():
        jax.block_until_ready(train_cells(*args, lam_c, sub_c, task_c, cfg,
                                          n_lam, n_sub))
        for _ in range(3):
            eng.predict(rng.normal(size=(64, d)).astype(np.float32))
    return traced


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    import jax
    import trace_reduce
    if jax.devices()[0].platform != "tpu":
        print("error: the fixture is a chip trace", file=sys.stderr)
        return 2
    traced = workload()
    tdir = tempfile.mkdtemp(prefix="fixture_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tdir, profiler_options=opts)
    with jax.profiler.TraceAnnotation("fixture.window"):
        traced()
    jax.profiler.stop_trace()
    path = trace_reduce.find_xplane(tdir)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    shutil.copyfile(path, args.out)
    shutil.rmtree(tdir, ignore_errors=True)
    pd = jax.profiler.ProfileData.from_file(args.out)
    for plane in pd.planes:
        lines = [(ln.name, sum(1 for _ in ln.events)) for ln in plane.lines]
        print("plane", plane.name, lines)
        if plane.name.startswith(trace_reduce.DEVICE_PREFIX):
            for ln in plane.lines:
                if ln.name != trace_reduce.OPS_LINE:
                    continue
                evs = sorted(ln.events, key=lambda e: -e.duration_ns)[:25]
                for e in evs:
                    print("  op", e.name[:120], e.duration_ns,
                          {k: str(v)[:80] for k, v in e.stats})
    red = trace_reduce.reduce(trace_reduce.load(args.out), KERNELS)
    print(json.dumps(red))
    return 0


if __name__ == "__main__":
    sys.exit(main())
