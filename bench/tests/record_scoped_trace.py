#!/usr/bin/env python3
"""Record the device trace that ``test_trace_scopes.py`` checks
``program_trace`` against, on the chip:

    python3 bench/tests/record_scoped_trace.py \
        --out bench/tests/data/fixture_scoped.xplane.pb

It traces the program as a traced run of the benchmark does, with the span
tracer on: two waves of ``train_cells_waves`` (3 slots of 512 rows, the
last wave padded, up to 100 FISTA iterations) and a few serve waves of
``SVMEngine``.  Beside the trace it saves, as JSON (``--out`` with
``.json`` for ``.xplane.pb``), the program's scope tables
(``repro.obs.jaxprof.scope_tables``) and the names of the spans that
fired, and prints the modules, the spans and (last line, JSON) the scope
seconds and span-idle seconds.
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


def workload():
    """Two waves of the wave scheduler and a few serve waves, warmed up;
    the returned callable runs them again with the span tracer on."""
    import jax
    import numpy as np
    from repro import obs
    from repro.core import cv as cv_mod
    from repro.core.grids import liquid_grid
    from repro.distributed.cell_trainer import train_cells_waves
    from repro.serve import SVMEngine
    from repro.serve.model_bank import ModelBank
    rng = np.random.default_rng(0)
    n_slots, wave, k, d = 3, 2, 512, 54
    x = rng.normal(size=(n_slots, k, d)).astype(np.float32)
    y = np.where(x[:, None, :, 0] + rng.normal(size=(n_slots, 1, k)) > 0,
                 1.0, -1.0).astype(np.float32)
    g = liquid_grid(n=k, dim=d, median_dist=10.0, cell_size=k)
    cfg = cv_mod.CVConfig(max_iters=100, keep_surface=True)
    lam_c, sub_c, task_c, n_lam, n_sub = cv_mod.grid_columns(g, cfg, 1)
    gam = np.tile(np.asarray(g.gammas)[None], (n_slots + 1, 1))
    keys = np.asarray(jax.random.split(jax.random.PRNGKey(0), n_slots + 1))

    def stage(lo, hi):
        m = np.zeros((hi - lo, k), np.float32)
        m[:max(min(hi, n_slots) - lo, 0)] = 1.0      # past n_slots: padding
        xs = np.zeros((hi - lo, k, d), np.float32)
        ys = np.zeros((hi - lo, 1, k), np.float32)
        xs[:len(x[lo:hi])], ys[:len(y[lo:hi])] = x[lo:hi], y[lo:hi]
        return xs, ys, (ys != 0).astype(np.float32), m, gam[lo:hi], \
            keys[lo:hi]

    def train():
        return train_cells_waves(stage, n_slots, wave, lam_c, sub_c, task_c,
                                 cfg, n_lam, n_sub)

    coefs = rng.normal(size=(8, k, 1, 1)).astype(np.float32)
    bank = ModelBank.from_cells(rng.normal(size=(8, k, d)).astype(np.float32),
                                np.ones((8, k), np.float32), coefs,
                                np.full((8, 1, 1), 10.0, np.float32),
                                rng.normal(size=(8, d)).astype(np.float32))
    eng = SVMEngine(bank)
    train()
    eng.predict(rng.normal(size=(64, d)).astype(np.float32))

    def traced():
        obs.tracer.enabled = True
        train()
        for _ in range(3):
            eng.predict(rng.normal(size=(64, d)).astype(np.float32))
        obs.tracer.enabled = False
    return traced


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    import jax
    import program_trace
    import trace_reduce
    from repro import obs
    if jax.devices()[0].platform != "tpu":
        print("error: the fixture is a chip trace", file=sys.stderr)
        return 2
    traced = workload()
    tdir = tempfile.mkdtemp(prefix="fixture_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tdir, profiler_options=opts)
    traced()
    jax.profiler.stop_trace()
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    shutil.copyfile(trace_reduce.find_xplane(tdir), args.out)
    shutil.rmtree(tdir, ignore_errors=True)
    tables = obs.jaxprof.scope_tables()
    names = sorted(obs.tracer.summary())
    side = args.out[:-len(".xplane.pb")] + ".json"
    with open(side, "w") as f:
        json.dump({"scope_tables": tables, "span_names": names}, f,
                  indent=1, sort_keys=True)
    tr = program_trace.load(args.out)
    print("modules", sorted(tables), "spans", names)
    print("trace modules", sorted({m for mods in tr["modules"].values()
                                   for m, _, _ in mods}))
    print(json.dumps({"scope_s": program_trace.scope_s(tr, tables),
                      "span_idle_s": program_trace.span_idle_s(tr, names)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
