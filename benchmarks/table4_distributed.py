"""Paper Table 4: distributed coarse/fine cells (Spark -> TPU mesh).

Runs the shard_map cell trainer over an 8-device forced-host mesh in a
subprocess (the benchmark process itself must keep the single real CPU
device).  On one physical CPU the 8 'devices' timeshare cores, so
wall-clock speedup is NOT the metric here — the deliverables are:
  * identical errors distributed vs single-device (exactness of the
    static-shuffle port of the Spark layer);
  * the per-device FLOP share (= the structural speedup at scale, which is
    what Table 4's superlinear column measures on real hardware).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

from benchmarks.common import QUICK, Report

SCRIPT = textwrap.dedent("""
    import os, json, time
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, numpy as np
    from repro.data.synthetic import covtype_like, train_test_split
    from repro.train.svm_trainer import LiquidSVM, SVMTrainerConfig

    n = {n}
    x, yc = covtype_like(n=int(n*1.2), d=8, seed=0, label_noise=0.08)
    y = np.where(yc == 0, -1, 1)
    xtr, ytr, xte, yte = train_test_split(x, y, 0.2, 0)
    cfg = SVMTrainerConfig(n_folds=3, max_iters=150,
                           cell_method="coarse_fine", cell_size={k})

    t0 = time.time(); m1 = LiquidSVM(cfg).fit(xtr, ytr); t1 = time.time() - t0
    e1 = m1.error(xte, yte)

    mesh = jax.make_mesh((8,), ("data",))
    t0 = time.time()
    m8 = LiquidSVM(cfg, mesh=mesh, mesh_axes=("data",)).fit(xtr, ytr)
    t8 = time.time() - t0
    e8 = m8.error(xte, yte)
    n_cells = m8.plan.n_cells
    print(json.dumps(dict(t1=t1, t8=t8, e1=e1, e8=e8, n_cells=n_cells,
                          flop_share_per_dev=1.0/8)))
""")


def run(report: Report) -> None:
    n = 3000 if QUICK else 20000
    k = 250 if QUICK else 1000
    script = SCRIPT.format(n=n, k=k, K=n // 4)
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env["JAX_PLATFORMS"] = "cpu"     # forced host devices; never the chip
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))),
                       capture_output=True, text=True, timeout=1800)
    if r.returncode != 0:
        raise RuntimeError(f"table4 child failed (rc={r.returncode}):\n"
                           f"{r.stderr[-2000:]}")
    d = json.loads(r.stdout.strip().splitlines()[-1])
    report.add("table4", f"n={n}/single-dev", d["t1"],
               err_pct=round(100 * d["e1"], 2), n_cells=d["n_cells"])
    report.add("table4", f"n={n}/mesh-8dev", d["t8"],
               err_pct=round(100 * d["e8"], 2),
               err_match=abs(d["e1"] - d["e8"]) < 0.02,
               flop_share_per_dev=d["flop_share_per_dev"])
