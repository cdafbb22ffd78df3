#!/usr/bin/env python3
"""The control of a least-squares cell's check, whose numbers must fail the
cell's limits: the plain reference (``bench/reference_ls.py``) with every
matmul at ``--precision`` (default ``default``: one bf16 pass on a TPU, the
step below the configured ``highest``) put in the program's place (the
driver's ``stand_in``), judged by the same check against the reference at
``highest``.

    python3 bench/control_ls.py --workload yearmsd-ls.train --seeds 11 12 13

prints one JSON line per seed: every number the check computed, the
limits, and whether the check refused the run (``refused``).  It needs the
chip, as the benchmark does (``--rehearse``: tiny sizes, any backend).

``bench/control.py`` loads ``bench/reference.py`` and offers no precision
below ``high``; the least-squares cells' control is this file.  Their
traffic files have no ``control`` block: the ls path reads no Gram dtype.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run as harness  # noqa: E402


def reference_at(precision: str):
    """A fresh copy of ``bench/reference_ls.py`` whose matmuls run at
    ``precision`` (a ``jax.lax.Precision`` name)."""
    import jax
    ref = harness.load_module(os.path.join(HERE, "reference_ls.py"))
    ref.PRECISION = getattr(jax.lax.Precision, precision.upper())
    return ref


def control(workload: str, seed: int, seconds: float,
            rehearse: bool = False, precision: str = "default") -> dict:
    cell, cfg, traffic, _, _ = harness.load_cell(workload, rehearse)
    ctx = harness.Ctx(cell, cfg, traffic, seed, seconds)
    driver = harness.load_module(os.path.join(HERE, "drivers",
                                              traffic["driver"] + ".py"))
    ctx.window = driver.stand_in(ctx, reference_at(precision))
    compared, correct = harness.judge(driver.check(ctx), traffic["limits"])
    return {"workload": workload, "seed": seed,
            "control": {"precision": precision}, "compared": compared,
            "refused": not correct}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--precision", default="default",
                    choices=("default", "high", "highest"))
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    for s in args.seeds:
        print(json.dumps(control(args.workload, s, args.seconds,
                                 args.rehearse, args.precision)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
