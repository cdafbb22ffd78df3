#!/usr/bin/env python3
"""Chip smoke: the SVM system's main path, once, on a TPU, checked.

    python3 chip_smoke.py                   # one chip (the default run)
    python3 chip_smoke.py --n-train 100000  # more training rows
    python3 chip_smoke.py --chips 4         # slot-sharded training on 4 chips

One chip: rows shaped like the UCI Covertype binary task (54 features,
581,012 rows, ``covtype_like`` from a seed, split 80/20, the training split
cut to ``N_TRAIN`` rows, which is printed) go through the
user entry points: ``api.SVM(...).train()`` over Voronoi cells of 2000 with
the default 10x10 grid and 5 folds, ``select()``, ``test()``, ``to_bank()``,
then ``serve.SVMEngine(bank)`` answers held-out rows through
``submit``/``run``.  The run fails unless

* JAX's first device is a TPU (nothing falls back to the CPU, the Pallas
  interpreter or a jnp reference);
* the lowered train-wave and serve-wave programs hold ``tpu_custom_call``
  (the Mosaic kernels, not their references);
* engine decisions equal ``SelectResult.decision_function`` on the same rows,
  and one cell's D² and ``K @ c`` equal a float64 host computation, within
  the f32 error model below;
* the held-out test error is under ``TEST_ERROR_BOUND``.

``--chips 4`` runs only the distributed layer: the same training wave on a
``("data",)`` mesh of 4 devices and on one device, which must select the
same (gamma, lambda) per cell, give decisions within tolerance, and leave
the sharded outputs on all 4 devices.

Every phase prints one line; the last line is the JSON verdict.  The
process starts no other process.  The compile cache follows
``repro.kernels.runtime.enable_compile_cache``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

N_ROWS = 581_012           # UCI Covertype
N_FEATURES = 54
CELL_SIZE = 2000
FOLDS = 5
SEED = 0
# Training rows of the default run.  The full 80% split (464,804 rows) makes
# 233 Voronoi cells padded to k_max = 4379; a 20-cell wave at k_max = 3428
# took 71 s of solve on one v5e, so the full split would take ~20 min.  The
# first 40,000 rows (20 cells, one wave) finish in a few minutes cold; the
# cut is printed on its own line.  --n-train raises it.
N_TRAIN = 40_000
# One wave must fit in 16 GB of HBM.  At the full split's k_max (4379, padded
# to 4480) the v5e compiler asked for 23.93 GB of temporaries for 80 slots,
# 0.30 GB per slot, so 40 slots need ~12 GB of the 15.75 GB it can use.
WAVE_SLOTS = 40
SERVE_ROWS = 4096          # held-out rows the engine answers
SERVE_BATCH = 256          # arrival burst fed to SVMEngine.run
FOUR_CHIP_N_TRAIN = 32_000  # --chips 4: 16 cells, one wave of 4 per device

# Test-error bound.  Source: a CPU rehearsal of this script (same data,
# config and phases, jnp kernel references) at n_train = 8,000 gave 0.0401
# on the 116,200 held-out rows, at the data's noise floor (8% of labels
# redrawn uniformly, so 4% land on the other class).  More training rows
# do not raise it; 0.01 is ~17 standard errors of a 116,200-row estimate.
TEST_ERROR_BOUND = 0.05

# f32 error model for the parity checks, in units of f32 eps:
#  * D² by the GEMM form |x|² + |z|² − 2x·z errs by a few eps of
#    |x|² + |z|² (one bf16 pass would err by ~2^-8 of it: 32768 eps);
#  * a decision sum_i c_i K_i errs by at most the D² error amplified
#    through exp (|dK| <= K dD²/gamma²) plus the rounding of the k-term sum,
#    both bounded by ulps of sum_i |c_i| K_i.
EPS32 = float(np.finfo(np.float32).eps)
D2_ULPS = 64
DEC_ULPS = 1024


class CheckFailed(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise CheckFailed(what)


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (all threads)."""

    def __init__(self):
        self.secs = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_):
        if event.startswith("/jax/core/compile/"):
            self.secs += duration


def timed(name: str, clock: CompileClock, fn, *args, **kwargs):
    """Run one phase; print its wall, compile and run seconds."""
    c0, t0 = clock.secs, time.perf_counter()
    out = fn(*args, **kwargs)
    wall = time.perf_counter() - t0
    comp = clock.secs - c0
    print(f"phase {name}: wall_s={wall:.3f} compile_s={comp:.3f} "
          f"run_s={max(wall - comp, 0.0):.3f}", flush=True)
    return out


# ------------------------------------------------------------------ phases
def device_line() -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def load_data(n_rows: int = N_ROWS, n_train: int | None = None,
              seed: int = SEED):
    """Covertype-shaped binary rows split 80/20; labels in {-1, +1}."""
    from repro.data.synthetic import covtype_like, train_test_split
    x, yc = covtype_like(n=n_rows, d=N_FEATURES, n_classes=2, seed=seed)
    y = np.where(yc == 0, -1.0, 1.0).astype(np.float32)
    xtr, ytr, xte, yte = train_test_split(x, y, 0.2, seed)
    if n_train is not None and n_train < len(xtr):
        print(f"reduced: n_train {len(xtr)} -> {n_train} (first rows of the "
              f"80% split)", flush=True)
        xtr, ytr = xtr[:n_train], ytr[:n_train]
    print(f"data: n_train={len(xtr)} n_test={len(xte)} d={x.shape[1]}",
          flush=True)
    return xtr, ytr, xte, yte


def train_select(xtr, ytr, mesh=None, **keys):
    """``api.SVM`` train + select through its string-key front door."""
    from repro.api import SVM
    cfg = dict(SCENARIO="binary", VORONOI="voronoi", CELL_SIZE=CELL_SIZE,
               FOLDS=FOLDS, WAVE_SLOTS=WAVE_SLOTS, RANDOM_SEED=SEED)
    cfg.update(keys)
    sess = SVM(xtr, ytr, mesh=mesh,
               mesh_axes=("data",) if mesh is not None else None, **cfg)
    tr = sess.train()
    sel = sess.select()
    print(f"cells: n_cells={tr.plan.n_cells} k_max={tr.plan.k_max} "
          f"slots={tr.packed.n_slots} wave_slots={tr.config.n_slots_per_wave}",
          flush=True)
    return sess, tr, sel


def wave_args(tr, lo: int, hi: int):
    """The positional arguments ``train_cells`` gets for slots [lo, hi)."""
    from repro.core import cv as cv_mod
    from repro.core.grids import GridSpec
    lam_c, sub_c, task_c, n_lam, n_sub = cv_mod.grid_columns(
        GridSpec(gammas=None, lambdas=jnp.asarray(tr.lambdas)), tr.cv_cfg,
        tr.tasks.n_tasks)
    arrays = [jnp.asarray(a[lo:hi]) for a in (
        tr.x_cells, tr.y_cells, tr.tmask_cells, tr.mask_cells,
        tr.gammas_cells, tr.fold_keys)]
    return (*arrays, lam_c, sub_c, task_c, tr.cv_cfg, n_lam, n_sub)


def wave_size(tr) -> int:
    return min(tr.config.n_slots_per_wave or tr.packed.n_slots,
               tr.packed.n_slots)


def train_wave_program(tr):
    """The first training wave's program as ``train()`` ran it: lowered
    text and compiled memory analysis (a cache hit after the run)."""
    from repro.distributed.cell_trainer import train_cells
    low = train_cells.lower(*wave_args(tr, 0, wave_size(tr)),
                            mesh=None, axis_names=None)
    return low.as_text(), low.compile().memory_analysis()


def serve_wave_text(bank, rows: int = 8, cells: int = 4) -> str:
    """Lowered text of the launch ``SVMEngine`` makes for one serve wave."""
    from repro.kernels.svm_predict import ops as sp_ops
    sv, co = bank.cell_arrays_f32()
    ga = jnp.asarray(bank.gammas, jnp.float32)
    xt = jnp.zeros((cells, rows, sv.shape[2]), jnp.float32)
    return sp_ops.svm_predict_cells.lower(
        xt, sv[:cells], co[:cells], ga[:cells], kind=bank.kernel).as_text()


def serve(sel, rows: np.ndarray, batch: int = SERVE_BATCH):
    """Bank the selection and answer ``rows`` through the async engine."""
    from repro.serve import SVMEngine
    bank = sel.to_bank()
    eng = SVMEngine(bank)
    res = eng.run([rows[i:i + batch] for i in range(0, len(rows), batch)])
    check(len(res) == len(rows), f"engine answered {len(res)} of {len(rows)}")
    dec = np.stack([res[r] for r in sorted(res)])          # (m, T, S)
    st = eng.stats()
    print(f"serve: rows={len(rows)} waves={st.get('steps', 0)} "
          f"fused={eng.fused} cells={bank.n_cells} k_max={bank.k_max}",
          flush=True)
    return bank, dec


def host_decisions(sel, rows: np.ndarray):
    """float64 host decisions and their f32 error scale sum_i |c_i| K_i."""
    xs = sel.scaler.transform(np.asarray(rows, np.float32)).astype(np.float64)
    slot = sel.packed.slot_of_cell[sel.plan.route(xs.astype(np.float32))]
    f64 = np.zeros(len(rows))
    scale = np.zeros(len(rows))
    for s in np.unique(slot):
        idx = np.flatnonzero(slot == s)
        m = sel.mask_cells[s] > 0
        sv = sel.x_cells[s][m].astype(np.float64)
        c = sel.coefs[s][m, 0, 0].astype(np.float64)
        g = float(sel.gamma[s, 0, 0])
        d2 = ((xs[idx, None, :] - sv[None]) ** 2).sum(-1)
        k = np.exp(-d2 / (g * g))
        f64[idx] = k @ c
        scale[idx] = k @ np.abs(c)
    return f64, scale


def check_decisions(name: str, got: np.ndarray, want: np.ndarray,
                    scale: np.ndarray) -> float:
    """|got - want| <= DEC_ULPS eps sum|c K| per row; returns the worst
    error in those units."""
    err = np.abs(got.reshape(len(scale), -1)[:, 0]
                 - want.reshape(len(scale), -1)[:, 0])
    ulps = float(np.max(err / (EPS32 * np.maximum(scale, 1e-30))))
    print(f"parity {name}: max_abs={float(err.max()):.3e} "
          f"max_ulps_of_sum|cK|={ulps:.1f} bound={DEC_ULPS}", flush=True)
    check(ulps <= DEC_ULPS, f"{name}: {ulps:.1f} ulps > {DEC_ULPS}")
    return ulps


def check_cell_kernels(sel) -> None:
    """One cell's device D² and ``K @ c`` against float64 on the host."""
    from repro.core import kernel_fns
    from repro.core.solvers import base as qp
    sizes = sel.mask_cells.sum(1)
    s = int(np.argmax(sizes))
    k = int(sizes[s])
    x = sel.x_cells[s, :k]
    c = sel.coefs[s, :k, 0, 0]
    g = float(sel.gamma[s, 0, 0])
    cg = kernel_fns.CachedGram.build(jnp.asarray(x), name=sel.config.kernel)
    d2 = np.asarray(cg.d2)
    kc = np.asarray(qp._kdot(cg.gram(jnp.float32(g)),
                             jnp.asarray(c)[:, None]))[:, 0]

    x64 = x.astype(np.float64)
    xx = (x64 * x64).sum(1)
    d2_64 = np.maximum(xx[:, None] + xx[None, :] - 2.0 * x64 @ x64.T, 0.0)
    d2_ulps = float(np.max(np.abs(d2 - d2_64)
                           / (EPS32 * (xx[:, None] + xx[None, :] + 1e-30))))
    k64 = np.exp(-d2_64 / (g * g))
    kc64 = k64 @ c.astype(np.float64)
    kc_scale = k64 @ np.abs(c.astype(np.float64))
    kc_ulps = float(np.max(np.abs(kc - kc64)
                           / (EPS32 * np.maximum(kc_scale, 1e-30))))
    print(f"parity cell_d2: k={k} max_ulps_of_|x|2+|z|2={d2_ulps:.1f} "
          f"bound={D2_ULPS}", flush=True)
    print(f"parity cell_Kc: gamma={g:.4g} max_ulps_of_|K||c|={kc_ulps:.1f} "
          f"bound={DEC_ULPS}", flush=True)
    check(d2_ulps <= D2_ULPS, f"cell D² {d2_ulps:.1f} ulps > {D2_ULPS}")
    check(kc_ulps <= DEC_ULPS, f"cell K@c {kc_ulps:.1f} ulps > {DEC_ULPS}")


def peak_hbm(tag: str):
    """Print the allocator's peak; returns its ``bytes_limit`` (or None).
    The peak counts live buffers, not a program's compiled temporaries."""
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    limit = stats.get("bytes_limit")
    print(f"hbm {tag}: peak_bytes_in_use="
          f"{'not reported' if peak is None else peak} bytes_limit="
          f"{'not reported' if limit is None else limit}", flush=True)
    return limit


def wave_hbm(mem, limit) -> None:
    """One training wave's compiled HBM need, checked against the limit."""
    need = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
            + mem.output_size_in_bytes)
    print(f"hbm train_wave program: temp_bytes={mem.temp_size_in_bytes} "
          f"argument_bytes={mem.argument_size_in_bytes} "
          f"output_bytes={mem.output_size_in_bytes} total={need}", flush=True)
    if limit is not None:
        check(need <= limit, f"one wave needs {need} > {limit} bytes")


# ---------------------------------------------------------------- the runs
def one_chip(n_train: int | None, clock: CompileClock,
             require_kernels: bool = True, n_rows: int = N_ROWS,
             serve_rows: int = SERVE_ROWS, **keys) -> float:
    """train -> select -> test -> bank -> serve, with every check.
    Returns the test error."""
    xtr, ytr, xte, yte = timed("data", clock, load_data, n_rows, n_train)
    sess, tr, sel = timed("train+select", clock, train_select, xtr, ytr,
                          **keys)
    limit = peak_hbm("after train")
    text, mem = train_wave_program(tr)
    wave_hbm(mem, limit)
    has = "tpu_custom_call" in text
    print(f"kernels train_wave: tpu_custom_call={has}", flush=True)
    if require_kernels:
        check(has, "train-wave program has no tpu_custom_call")

    res = timed("test", clock, sel.test, xte, yte)
    print(f"test: error={res.error:.4f} n={res.n} bound={TEST_ERROR_BOUND}",
          flush=True)
    check(res.error < TEST_ERROR_BOUND,
          f"test error {res.error:.4f} >= {TEST_ERROR_BOUND}")

    rows = xte[:serve_rows]
    bank, dec = timed("bank+serve", clock, serve, sel, rows)
    peak_hbm("after serve")
    text = serve_wave_text(bank)
    has = "tpu_custom_call" in text
    print(f"kernels serve_wave: tpu_custom_call={has}", flush=True)
    if require_kernels:
        check(has, "serve-wave program has no tpu_custom_call")

    df = timed("decision_function", clock, sel.decision_function, rows)
    f64, scale = host_decisions(sel, rows)
    check_decisions("engine_vs_decision_function", dec, df, scale)
    check_decisions("decision_function_vs_float64", df, f64, scale)
    check_cell_kernels(sel)
    return res.error


def run_waves(tr, mesh=None):
    """Every training wave of ``tr`` through ``train_cells`` again, on
    ``mesh`` or on the default device.  Returns the outputs as host arrays
    and the devices holding the last wave's outputs."""
    from repro.distributed.cell_trainer import train_cells
    wave, outs, placed = wave_size(tr), [], set()
    for lo in range(0, tr.packed.n_slots, wave):
        out = jax.block_until_ready(train_cells(
            *wave_args(tr, lo, lo + wave), mesh=mesh,
            axis_names=("data",) if mesh is not None else None))
        placed = {sh.device for o in out for sh in o.addressable_shards}
        outs.append([np.asarray(o) for o in out])
    return [np.concatenate(parts) for parts in zip(*outs)], placed


def four_chips(clock: CompileClock, n_train: int = FOUR_CHIP_N_TRAIN,
               n_rows: int = N_ROWS, serve_rows: int = SERVE_ROWS,
               **keys) -> None:
    """Slot-sharded training over 4 devices against the same waves on one.

    The session trains through ``SVM(..., mesh=, mesh_axes=)``; its waves
    (same slots, same fold keys) then run once more on the mesh and once
    on one device.  Fold keys follow the slot packing, which depends on the
    device count, so the comparison reuses the mesh session's packing.
    """
    import dataclasses
    from jax.sharding import Mesh
    devs = jax.devices()
    check(len(devs) == 4, f"--chips 4 needs 4 devices, found {len(devs)}")
    mesh = Mesh(np.asarray(devs), ("data",))
    xtr, ytr, xte, yte = timed("data", clock, load_data, n_rows, n_train)
    keys = {"WAVE_SLOTS": 16, **keys}
    _, tr, sel = timed("train+select mesh4", clock, train_select, xtr, ytr,
                       mesh=mesh, **keys)
    mesh_out, placed = timed("waves mesh4", clock, run_waves, tr, mesh)
    one_out, _ = timed("waves one_device", clock, run_waves, tr)

    print(f"mesh4: wave_slots={wave_size(tr)} output_devices={len(placed)}",
          flush=True)
    check(placed == set(devs),
          f"sharded outputs on {len(placed)} of 4 devices")
    # outputs: coefs, gamma, lam, ... per slot (= per cell)
    check(np.array_equal(mesh_out[1], tr.gamma)
          and np.array_equal(mesh_out[2], tr.lam),
          "re-run mesh waves selected other (gamma, lambda) than train()")
    differ = int(np.sum(np.any(mesh_out[1] != one_out[1], axis=(1, 2))
                        | np.any(mesh_out[2] != one_out[2], axis=(1, 2))))
    print(f"mesh4: slots={tr.packed.n_slots} cells={tr.plan.n_cells} "
          f"selected_gamma_lambda_differ={differ}", flush=True)
    check(differ == 0, f"{differ} cells selected other (gamma, lambda) on "
                       f"one device")

    one = dataclasses.replace(sel, coefs=one_out[0], gamma=one_out[1],
                              lam=one_out[2], mesh=None, mesh_axes=None)
    rows = xte[:serve_rows]
    d4 = sel.decision_function(rows)
    d1 = one.decision_function(rows)
    _, scale = host_decisions(one, rows)
    check_decisions("mesh4_vs_one_device", d4, d1, scale)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--n-train", type=int, default=None,
                    help=f"training rows of the 80%% split (default "
                         f"{N_TRAIN}; --chips 4: {FOUR_CHIP_N_TRAIN})")
    args = ap.parse_args(argv)

    dev = device_line()
    print(f"device: platform={dev['platform']} kind={dev['kind']} "
          f"count={dev['count']} jax={jax.__version__}", flush=True)
    if dev["platform"] != "tpu":
        print(f"error: no TPU: JAX's first device is {dev['platform']!r}; "
              f"this smoke runs only on the chip", file=sys.stderr)
        return 2
    if dev["count"] != args.chips:
        print(f"error: --chips {args.chips} but JAX sees {dev['count']} "
              f"devices", file=sys.stderr)
        return 2

    from repro.kernels.runtime import enable_compile_cache
    print(f"compile_cache: {enable_compile_cache()}", flush=True)
    clock = CompileClock()
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            four_chips(clock, n_train=args.n_train or FOUR_CHIP_N_TRAIN)
        else:
            one_chip(args.n_train or N_TRAIN, clock)
    except CheckFailed as e:
        print(f"FAILED: {e}", file=sys.stderr)
        return 1
    print(f"total: wall_s={time.perf_counter() - t0:.3f} "
          f"compile_s={clock.secs:.3f}", flush=True)
    print(json.dumps({"ok": True, "device": device_line()}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
