#!/usr/bin/env python3
"""Faults planted under a cell's timed path, to show that its check
refuses them (``tests/test_faults.py``, on the CPU at rehearsal sizes) and
to read each fault's numbers on the chip at the cell's own size:

    python3 bench/faults.py --workload covtype.train --kind half --seeds 1 2 3

Kinds (the cells run on one chip, so no exchange between chips exists to
be left out):

* ``unchanged`` — the step returns its starting state: zero models from
  ``train_cells``; zero decisions from the predict kernel;
* ``half`` — half of the batch left out: every other row of each cell
  masked before the solve (the models are fitted on the rest); half of
  each wave's slots and of each slot's rows never computed;
* ``altered`` — an answer altered where it is produced: the sign of the
  largest coefficient of every model flipped; 1.0 added to the first
  decision of every wave.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

KINDS = ("unchanged", "half", "altered")


def _train(kind):
    from repro.distributed import cell_trainer
    real = cell_trainer.train_cells

    def wrapped(x, y, tmask, mask, *rest, **kw):
        if kind == "half":
            keep = np.arange(mask.shape[1]) % 2 == 0
            mask, y, tmask = mask * keep, y * keep, tmask * keep
        out = list(real(x, y, tmask, mask, *rest, **kw))
        c = np.array(out[0])
        flat = c.reshape(c.shape[0], -1)
        if kind == "unchanged":
            flat[:] = 0.0
        elif kind == "altered":
            i = np.argmax(np.abs(flat), axis=1)
            flat[np.arange(len(i)), i] *= -1.0
        out[0] = c
        return tuple(out)
    return cell_trainer, "train_cells", wrapped


def _serve(kind):
    from repro.kernels.svm_predict import ops
    real = ops.svm_predict_cells

    def wrapped(xt, *rest, **kw):
        dec = np.array(real(xt, *rest, **kw))
        if kind == "unchanged":
            dec[:] = 0.0
        elif kind == "half":
            dec[:, dec.shape[1] // 2:] = 0.0
            dec[dec.shape[0] // 2:] = 0.0
        elif kind == "altered":
            dec[0, 0] += 1.0
        return dec
    return ops, "svm_predict_cells", wrapped


SITES = {"train_waves": _train, "fits": _train, "serve_open": _serve}


@contextlib.contextmanager
def planted(driver: str, kind: str):
    """The cell's timed call replaced by its faulty wrapper, then restored."""
    mod, name, wrapped = SITES[driver](kind)
    real = getattr(mod, name)
    setattr(mod, name, wrapped)
    try:
        yield
    finally:
        setattr(mod, name, real)


def main(argv=None) -> int:
    import run as harness
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--kind", choices=KINDS, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    _, _, traffic, _, _ = harness.load_cell(args.workload, args.rehearse)
    for s in args.seeds:
        with planted(traffic["driver"], args.kind):
            res = harness.run(args.workload, s, args.seconds, False,
                              rehearse=args.rehearse)
        print(json.dumps({"workload": args.workload, "kind": args.kind,
                          "seed": s, "compared": res["compared"],
                          "refused": not res["correct"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
