"""The comparison that decides ``correct`` for the training drivers."""
from __future__ import annotations

import numpy as np


def cv_numbers(ref: dict, c_prog, g_prog: float, l_prog: float, surf_prog,
            gammas, lambdas, n_real: int) -> dict:
    """The three numbers compared for one working set.

    ``ref`` is :func:`reference.cv_cell`'s output; ``c_prog`` (n,) the
    program's fold-averaged model, ``g_prog``/``l_prog`` its selected
    gamma and lambda (matched to the reference's grid, which the reference
    computed itself), ``surf_prog`` (G, L) its validation surface."""
    gi = int(np.argmin(np.abs(np.log(gammas / g_prog))))
    li = int(np.argmin(np.abs(np.log(lambdas / l_prog))))
    c_ref = ref["coefs"][gi, :, li]
    scale = max(float(np.max(np.abs(c_ref))), 1e-30)
    return {
        "coef_gap": float(np.max(np.abs(c_prog - c_ref)) / scale),
        "surface_gap": float(np.max(np.abs(surf_prog - ref["surface"]))
                             * n_real),
        "select_regret": float((ref["surface"][gi, li]
                                - ref["surface"].min()) * n_real),
    }


def worst(a: dict, b: dict) -> dict:
    """Each number's larger reading of two sets (a may be empty)."""
    return {k: max(a.get(k, 0.0), v) for k, v in b.items()}
