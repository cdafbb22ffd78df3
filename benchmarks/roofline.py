"""Roofline + warm-start benchmark for the fused wave-level CD solver.

The training inner loop solves a WAVE of packed cell slots at once
(``distributed.cell_trainer.train_cells_waves`` -> ``kernels/cd_solver``);
this harness measures exactly that path and records the numbers the
regression gate holds the solver to (``BENCH_solver.json``, read by
``benchmarks.check_regression``):

  * ``wave``       — fused ``cd_epochs_wave`` (ONE launch for S slots)
                     vs the per-slot ``cd_epochs`` baseline (S launches),
                     same data, same epochs.  The committed bar is a
                     same-machine ratio (>= 1.5x), so it is meaningful on
                     any host; parity between the two paths is recorded
                     alongside (``max_abs_diff`` must sit within ``tol``).
  * ``warm_start`` — CD epochs-to-tolerance at a neighboring gamma, cold
                     (``c0 = 0``) vs warm-started from the previous
                     gamma's solution box-clipped in — the gamma-scan
                     carry of ``core/cv.cv_cell`` feeding the fused CD
                     path, in isolation.  This is the paper's warm-start
                     claim on the solver it was made for: an active-set
                     sweep inherits the neighbor's support set, so warm
                     runs converge in measurably fewer epochs (the
                     batched FISTA box-QP, by contrast, is start-
                     insensitive — its count is gated by the worst-
                     conditioned grid column; measured and documented in
                     ``core/cv.solve_columns_at``).  Both runs must end
                     with KKT residual <= tol.
  * ``roofline``   — analytic flops/byte of one fused CD epoch against
                     the ridge of the device it runs on (``DEVICE_PEAKS``;
                     v5e: 197 TFLOP/s bf16 / 819 GB/s HBM):
                     per epoch the Gram (4 n^2 bytes/slot, f32) streams
                     once while the resident state does 2 n^2 P flops of
                     rank-1 maintenance, so intensity ~= P/2 flops/byte —
                     the report says how far from the ridge the sweep
                     runs and which side of it (memory vs compute) the
                     kernel sits on.

``PYTHONPATH=src python -m benchmarks.roofline`` writes the JSON;
``benchmarks.run --tables solver`` folds it into the report tables.
"""
from __future__ import annotations

import functools
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import QUICK, Report, timeit
from repro.core.solvers import base as qp
from repro.kernels.cd_solver import ops as cd_ops
from repro.kernels.cd_solver import ref as cd_ref

# Published per-chip peaks keyed by jax's ``device_kind`` (Google Cloud
# documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM).  A device not
# listed here has no roofline: ``device_peaks`` raises instead of guessing.
DEVICE_PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
}


def device_peaks(device_kind: str) -> dict:
    if device_kind not in DEVICE_PEAKS:
        raise KeyError(f"no published peaks for device_kind {device_kind!r} "
                       f"(known: {sorted(DEVICE_PEAKS)})")
    return DEVICE_PEAKS[device_kind]

OUT_PATH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                        "BENCH_solver.json")

SPEEDUP_BAR = 1.5          # fused wave vs per-slot launches (same machine)
WARM_BAR = 1.2             # cold iters / warm iters


def merge_bench(updates: dict) -> None:
    """Read-merge-write ``BENCH_solver.json`` (one level of dict-merge,
    same pattern as ``serve_throughput.merge_bench``)."""
    data: dict = {}
    if os.path.exists(OUT_PATH):
        try:
            with open(OUT_PATH) as f:
                data = json.load(f)
        except ValueError:
            data = {}
    for k, v in updates.items():
        if isinstance(v, dict) and isinstance(data.get(k), dict):
            data[k].update(v)
        else:
            data[k] = v
    with open(OUT_PATH, "w") as f:
        json.dump(data, f, indent=2)


def model_params(arch_id: str) -> dict:
    """Total and active parameter counts from the launch-vertical configs
    (kept for the dry-run FLOP accounting and its tests)."""
    from repro.configs import get_arch
    from repro.models import model as model_mod
    from repro.models.layers import param_count
    cfg = get_arch(arch_id).config
    total = param_count(model_mod.build_template(cfg))
    active = total
    if cfg.n_experts:
        # active = total - (routed expert params not selected)
        expert_p = 3 * cfg.d_model * cfg.moe_d_ff
        n_moe_layers = sum(1 for _, m in cfg.period_pattern if m == "moe")
        n_moe_layers = cfg.n_periods * n_moe_layers + sum(
            1 for j in range(cfg.tail) if cfg.period_pattern[j][1] == "moe")
        inactive = n_moe_layers * expert_p * (cfg.n_experts - cfg.top_k)
        active = total - inactive
    return {"total": float(total), "active": float(active)}


def model_flops(arch_id: str, shape_kind: str, seq: int, batch: int) -> float:
    """Analytic MODEL_FLOPS: 6*N_active*D train, 2*N_active*D forward,
    2*N_active per decoded token."""
    p = model_params(arch_id)["active"]
    tokens = batch * seq
    if shape_kind == "train":
        return 6.0 * p * tokens
    if shape_kind in ("prefill", "encode"):
        return 2.0 * p * tokens
    return 2.0 * p * batch  # decode: one token per row


def _wave_problem(s, n, p, seed=0):
    """S synthetic hinge-like cell duals: PSD Grams + box grids."""
    key = jax.random.PRNGKey(seed)
    kg, ky, kh, kc = jax.random.split(key, 4)
    a = jax.random.normal(kg, (s, n, n), jnp.float32)
    k_mats = jnp.einsum("sij,skj->sik", a, a) / n + jnp.eye(n)[None]
    y = jax.random.normal(ky, (s, n, p), jnp.float32)
    lo = jnp.zeros((s, n, p), jnp.float32)
    hi = jnp.abs(jax.random.normal(kh, (s, n, p), jnp.float32)) + 0.1
    c0 = jnp.clip(jax.random.normal(kc, (s, n, p)) * 0.05, lo, hi)
    return k_mats, y, lo, hi, c0


def bench_wave(report: Report, s, n, p, epochs, repeats) -> dict:
    """Fused one-launch wave solve vs S per-slot launches."""
    k_mats, y, lo, hi, c0 = _wave_problem(s, n, p)

    def fused():
        return jax.block_until_ready(
            cd_ops.cd_epochs_wave(k_mats, y, lo, hi, c0, epochs=epochs))

    def per_slot():
        outs = [cd_ops.cd_epochs(k_mats[i], y[i], lo[i], hi[i], c0[i],
                                 epochs=epochs) for i in range(s)]
        return jax.block_until_ready(outs)

    t_wave = timeit(fused, repeats=repeats, warmup=1)
    t_slot = timeit(per_slot, repeats=repeats, warmup=1)
    c_wave = fused()
    c_slot = jnp.stack(per_slot())
    diff = float(jnp.max(jnp.abs(c_wave - c_slot)))
    speedup = t_slot / max(t_wave, 1e-12)
    report.add("solver", "wave_fused", t_wave, s=s, n=n, p=p, epochs=epochs,
               speedup=round(speedup, 2), max_abs_diff=diff)
    report.add("solver", "wave_per_slot", t_slot, s=s, n=n, p=p,
               epochs=epochs)
    return {"s": s, "n": n, "p": p, "epochs": epochs,
            "t_wave_s": t_wave, "t_per_slot_s": t_slot,
            "speedup": speedup, "bar": SPEEDUP_BAR,
            "max_abs_diff": diff, "tol": 1e-3}


@functools.partial(jax.jit, static_argnames=("tol", "max_epochs"))
def _cd_to_tol(k_mat, y, lo, hi, c0, tol, max_epochs):
    """Blocked CD epochs until KKT residual <= tol; returns (c, epochs, kkt)."""
    g0 = k_mat @ c0 - y

    def cond(state):
        c, g, e = state
        return jnp.logical_and(
            e < max_epochs, jnp.max(qp.kkt_residual(c, g, lo, hi)) > tol)

    def body(state):
        c, g, e = state
        c, g = cd_ref.cd_epoch_blocked_ref(k_mat, c, g, lo, hi)
        return c, g, e + 1

    c, g, e = jax.lax.while_loop(cond, body, (c0, g0, jnp.int32(0)))
    return c, e, jnp.max(qp.kkt_residual(c, g, lo, hi))


def bench_warm_start(report: Report, n, p, repeats) -> dict:
    """Neighbor-gamma warm start vs cold c0=0: CD epochs to KKT tol.

    Walks a short gamma grid the way ``cv_cell``'s scan does — the warm run
    carries each step's solution into the next step's solve (box-clipped),
    the cold run restarts every step from ``c0 = 0`` — and compares total
    epochs to tolerance.  The step counts are summed over the grid walk so
    the reduction is the scan-level number, not one lucky step.
    """
    key = jax.random.PRNGKey(0)
    kx, ky = jax.random.split(key)
    x = jax.random.normal(kx, (n, 8), jnp.float32)
    y = jnp.sign(jax.random.normal(ky, (n,)))
    d2 = jnp.sum((x[:, None] - x[None, :]) ** 2, -1)
    lam = jnp.logspace(-3, 0, p)
    cost = 1.0 / (2.0 * lam[None, :] * n)
    edge = y[:, None] * cost
    lo, hi = jnp.minimum(0.0, edge), jnp.maximum(0.0, edge)
    y_cols = jnp.broadcast_to(y[:, None], (n, p))
    tol, max_epochs = 1e-3, 4000
    gammas = (6.0, 5.0, 4.2, 3.5)    # geometric-ish scan, coarse -> fine

    def gram(gamma):
        return jnp.exp(-d2 / (gamma * gamma))

    zeros = jnp.zeros_like(y_cols)
    # seed both runs with the first gamma solved cold (the scan's first step
    # has no neighbor); then walk the remaining steps cold vs warm.
    c_first, e_first, _ = _cd_to_tol(gram(gammas[0]), y_cols, lo, hi, zeros,
                                     tol, max_epochs)
    iters_cold = iters_warm = 0
    kkt_cold = kkt_warm = 0.0
    diff = 0.0
    carry = c_first
    for g in gammas[1:]:
        k_g = gram(g)
        cc, ec, rc = _cd_to_tol(k_g, y_cols, lo, hi, zeros, tol, max_epochs)
        cw, ew, rw = _cd_to_tol(k_g, y_cols, lo, hi,
                                qp.clip_warm_start(carry, lo, hi),
                                tol, max_epochs)
        iters_cold += int(ec)
        iters_warm += int(ew)
        kkt_cold = max(kkt_cold, float(rc))
        kkt_warm = max(kkt_warm, float(rw))
        width = float(jnp.max(hi - lo))
        diff = max(diff, float(jnp.max(jnp.abs(cc - cw))) / width)
        carry = cw

    def cold_walk():
        outs = [_cd_to_tol(gram(g), y_cols, lo, hi, zeros, tol, max_epochs)[0]
                for g in gammas[1:]]
        return jax.block_until_ready(outs)

    def warm_walk():
        c = c_first
        for g in gammas[1:]:
            c, _, _ = _cd_to_tol(gram(g), y_cols, lo, hi,
                                 qp.clip_warm_start(c, lo, hi),
                                 tol, max_epochs)
        return jax.block_until_ready(c)

    t_cold = timeit(cold_walk, repeats=repeats, warmup=1)
    t_warm = timeit(warm_walk, repeats=repeats, warmup=1)
    reduction = iters_cold / max(iters_warm, 1)
    report.add("solver", "warm_start", t_warm, n=n, p=p,
               iters_cold=iters_cold, iters_warm=iters_warm,
               reduction=round(reduction, 2), kkt_warm=round(kkt_warm, 5))
    return {"n": n, "p": p, "tol": tol, "gamma_steps": len(gammas) - 1,
            "iters_cold": iters_cold, "iters_warm": iters_warm,
            "reduction": reduction, "bar": WARM_BAR,
            "kkt_cold": kkt_cold, "kkt_warm": kkt_warm,
            "t_cold_s": t_cold, "t_warm_s": t_warm,
            "max_rel_diff": diff}


def roofline(s, n, p, epochs, t_wave_s, device_kind: str) -> dict:
    """Analytic flops/byte of the fused CD epoch vs the device's ridge.

    Per slot-epoch: every coordinate does a rank-1 gradient update
    (n multiplies + n adds per grid column) plus the 1-D step — the
    2 n^2 p term dominates.  Bytes: the Gram streams through VMEM once
    (4 n^2, f32) while c/g/lo/hi stay resident (amortized across the
    sweep; charged once per epoch: 4 arrays x 4 n p bytes).
    """
    flops = 2.0 * n * n * p * s * epochs
    bytes_moved = (4.0 * n * n + 4 * 4.0 * n * p) * s * epochs
    intensity = flops / bytes_moved
    peaks = device_peaks(device_kind)
    ridge = peaks["flops_per_s"] / peaks["hbm_bytes_per_s"]
    t_mem = bytes_moved / peaks["hbm_bytes_per_s"]
    t_comp = flops / peaks["flops_per_s"]
    bound = "memory" if t_mem >= t_comp else "compute"
    measured = flops / max(t_wave_s, 1e-12)
    return {"device_kind": device_kind, "flops": flops, "bytes": bytes_moved,
            "intensity_flops_per_byte": intensity,
            "ridge_flops_per_byte": ridge,
            "frac_of_ridge": intensity / ridge,
            "bound": bound,
            "tpu_t_memory_s": t_mem, "tpu_t_compute_s": t_comp,
            "measured_flops_per_s": measured}


def run(report: Report) -> None:
    device_kind = jax.devices()[0].device_kind
    device_peaks(device_kind)          # no roofline for an unknown device
    s, n, p = (8, 256, 16) if QUICK else (16, 1024, 48)
    epochs = 4
    repeats = 5 if QUICK else 3
    wave = bench_wave(report, s, n, p, epochs, repeats)
    warm = bench_warm_start(report, 256 if QUICK else 512,
                            24 if QUICK else 48, repeats)
    roof = roofline(s, n, p, epochs, wave["t_wave_s"], device_kind)
    report.add("solver", "roofline", wave["t_wave_s"],
               intensity=round(roof["intensity_flops_per_byte"], 2),
               ridge=round(roof["ridge_flops_per_byte"], 1),
               bound=roof["bound"])
    merge_bench({"wave": wave, "warm_start": warm, "roofline": roof,
                 "quick": QUICK})
    print(f"# wrote {OUT_PATH}")


def main() -> int:
    report = Report()
    run(report)
    print(report.table_markdown("solver"))
    return 0


if __name__ == "__main__":
    sys.exit(main())
