"""The configuration's data set and cell plan, built in set-up with the
program's own functions: ``Scaler`` (train statistics, as ``SVM.train``
scales), ``build_cells_stream`` and ``pack_cells`` over the run's chips.
The data set and the plan are fixed by the configuration (its
``geometry_seed`` and ``plan_seed``), so every seed times the same shapes.
"""
from __future__ import annotations

import data as bdata


def build(ctx):
    from repro.data.scaling import Scaler
    from repro.distributed.planner import pack_cells
    from repro.pipeline.cell_stream import build_cells_stream
    from repro.pipeline.dataset import ArraySource, as_source
    cfg = ctx.cfg
    xtr, ytr, xte, yte = bdata.binary_rows(cfg["data"])
    chunk = cfg["cells"].get("chunk_size", 65536)
    scaler = Scaler.fit_stream(as_source(xtr), chunk)
    xs = scaler.transform(xtr)
    plan = build_cells_stream(ArraySource(xs), cell_size=cfg["cells"]["size"],
                              method=cfg["cells"]["method"],
                              seed=cfg["cells"]["plan_seed"], chunk_size=chunk)
    packed = pack_cells(plan, ctx.chips)
    return {"xtr": xtr, "ytr": ytr, "xte": xte, "yte": yte, "xs": xs,
            "plan": plan, "packed": packed}
