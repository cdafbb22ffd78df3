"""The least work of one iteration of the FISTA box-QP solve, for
``fista_roofline``: operations and bytes as ``work.py`` counts them, and
timed with ``work.least_s``."""
from __future__ import annotations


def fista_iter(k: int, columns: int, k_bytes: int, folds: int) -> tuple:
    """(flops, bytes) of one lane-iteration of the FISTA box-QP
    (``core/solvers/base.box_qp``): one slot and fold, ``columns`` lambda
    columns, the gradient's K @ C: 2 k^2 P flops.  K is shared by a slot's
    folds, so the loop, batched over them, reads it once per iteration:
    each lane is charged a fold's share, k^2 k_bytes / folds bytes.  ``k``
    is the padded size the loop runs at and ``k_bytes`` the width of K as
    the solve reads it (4 for f32).

    At ``covtype-cells`` (k = 4379, 10 lambda columns, f32, 5 folds):
    2 * 4379^2 * 10 = 383,512,820 flops; 4379^2 * 4 / 5 = 15,340,512.8
    bytes.  On a v5e: 11.7 us of f32 MXU time against 18.7 us of HBM time,
    so memory bounds it at 18.73 us; a 2-slot wave's device iteration (10
    lanes) at 187.3 us.
    """
    return 2.0 * k * k * columns, float(k * k * k_bytes) / folds
