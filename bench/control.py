#!/usr/bin/env python3
"""The controls of a cell's check, whose numbers must fail the cell's limits.

* the program's own lower-precision path (default): the traffic file's
  ``control`` block switched on (the solver's bf16 Gram,
  ``CVConfig.gram_dtype="bf16"``, for training; a bf16 bank, ``ModelBank``
  ``dtype="bf16"``, for serving), through the same set-up, window and
  check as a run;
* ``--precision high``: the plain reference computed with every matmul at
  ``high`` (three bf16 passes, the step below the configured ``highest``)
  put in the program's place (the driver's ``stand_in``), judged by the
  same check against the reference at ``highest``.

    python3 bench/control.py --workload small2k.train --seeds 11 12 13
    python3 bench/control.py --workload small2k.train --precision high --seeds 11 12 13

prints one JSON line per seed: every number the check computed, the
limits, and whether the check refused the run (``refused``).  It needs the
chip, as the benchmark does (``--rehearse``: tiny sizes, any backend).
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
    """A fresh copy of ``bench/reference.py`` whose matmuls run at
    ``precision`` (a ``jax.lax.Precision`` name)."""
    import jax
    ref = harness.load_module(os.path.join(HERE, "reference.py"))
    ref.PRECISION = getattr(jax.lax.Precision, precision.upper())
    return ref


def stand_in(workload: str, seed: int, seconds: float, precision: str,
             rehearse: bool = False) -> dict:
    """The cell's check run on the reference at ``precision`` in the
    program's place; the program itself does not run."""
    cell, cfg, traffic, _, _ = harness.load_cell(workload, rehearse)
    ctx = harness.Ctx(cell, cfg, traffic, seed, seconds)
    driver = harness.load_module(os.path.join(HERE, "drivers",
                                              traffic["driver"] + ".py"))
    ctx.window = driver.stand_in(ctx, reference_at(precision))
    compared, correct = harness.judge(driver.check(ctx), traffic["limits"])
    return {"compared": compared, "correct": correct}


def control(workload: str, seed: int, seconds: float,
            rehearse: bool = False, precision: str | None = None) -> dict:
    if precision:
        res = stand_in(workload, seed, seconds, precision, rehearse)
        what = {"precision": precision}
    else:
        _, _, traffic, _, _ = harness.load_cell(workload, rehearse)
        res = harness.run(workload, seed, seconds, False, rehearse=rehearse,
                          override=traffic["control"])
        what = traffic["control"]
    return {"workload": workload, "seed": seed, "control": what,
            "compared": res["compared"], "refused": not res["correct"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--precision", choices=("high", "highest"),
                    help="the reference at this precision in the program's "
                         "place (default: the program's own lower path)")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    for s in args.seeds:
        print(json.dumps(control(args.workload, s, args.seconds,
                                 args.rehearse, args.precision)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
