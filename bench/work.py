"""Operations and bytes each measured call needs, from its shapes, and the
chip peaks they are held against.

These count the work the call needs, not what a given kernel does: a later
kernel that skips work is judged against the same numbers.  Floats are f32
(4 bytes), as the configurations state.

Peaks: copied from ``benchmarks/roofline.DEVICE_PEAKS`` (Google Cloud
documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM), keyed by
``device_kind``; a device that is not in the table is an error.  The
configurations compute in f32 at ``precision=HIGHEST``, which the v5e MXU
runs as six bf16 passes, so the f32 peak is the bf16 peak over six.
"""
from __future__ import annotations

F32 = 4

PEAKS = {
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
}
HIGHEST_PASSES = 6


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device_kind {device_kind!r} "
                       f"(known: {sorted(PEAKS)})")
    p = PEAKS[device_kind]
    return {"f32_flops_per_s": p["bf16_flops_per_s"] / HIGHEST_PASSES,
            "hbm_bytes_per_s": p["hbm_bytes_per_s"]}


def least_s(flops: float, nbytes: float, pk: dict) -> float:
    """The roofline's least time: the larger of the two bounds."""
    return max(flops / pk["f32_flops_per_s"], nbytes / pk["hbm_bytes_per_s"])


def sq_dists(k: int, d: int) -> tuple:
    """One working set's D² (k, k) from its rows (k, d): the GEMM cross
    term 2 k^2 d flops; x read once, D² written once.

    At ``covtype-cells`` (k = 4379, d = 54): 2 * 4379^2 * 54 =
    2,070,969,228 flops; 4379 * 54 * 4 + 4379^2 * 4 = 945,864 + 76,702,564
    = 77,648,428 bytes.  On a v5e: 63.1 us of f32 MXU time against
    94.8 us of HBM time, so memory bounds it at 94.8 us per slot.
    """
    return 2.0 * k * k * d, float(F32 * (k * d + k * k))


def gram_epilogue(k: int) -> tuple:
    """One gamma's kernel pass over a cached D²: read D², write K (both
    f32, k x k); the exp is VPU work and not counted against the MXU.

    At k = 4379: 8 * 4379^2 = 153,405,128 bytes, 187.3 us on a v5e.
    """
    return 0.0, float(2 * F32 * k * k)


def svm_predict(m: int, k: int, d: int, p: int) -> tuple:
    """One serve slot: m query rows against a cell's k SV rows, p columns:
    2 m k d flops of cross term plus m k p of contraction; the SV table,
    its coefficients and the queries read once, the decisions written.

    At ``covtype.serve``'s usual slot (m = 8, k = 1536, d = 54, p = 1):
    2*8*1536*54 + 8*1536 = 1,339,392 flops; 4 * (1536*54 + 1536 + 8*54
    + 8) = 339,680 bytes.  Memory bounds it: 0.415 us per slot.
    """
    flops = 2.0 * m * k * d + 1.0 * m * k * p
    return flops, float(F32 * (k * d + k * p + m * d + m * p))


def cv_wave_minimum(n: int, d: int, n_gamma: int, n_folds: int,
                    p: int) -> tuple:
    """The least work of one working set's CV, as ``mfu.train`` counts it:
    D² once, one kernel pass per gamma, one validation K @ C per gamma and
    fold (the solver's iterations are left out: their number depends on
    the solver).

    At a 2,000-row ``covtype-cells`` cell (d = 54, 10 gammas, 5 folds,
    10 lambda columns): flops 2*2000^2*54 + 10*5*2*2000^2*10 =
    432,000,000 + 4,000,000,000 = 4,432,000,000; bytes 4*(2000*54 +
    2000^2) + 10*8*2000^2 + 10*5*4*2000^2 = 16,432,000 + 320,000,000 +
    800,000,000 = 1,136,432,000.  On a v5e: 135 us of MXU against
    1.388 ms of HBM time.
    """
    f0, b0 = sq_dists(n, d)
    _, b1 = gram_epilogue(n)
    flops = f0 + n_gamma * n_folds * 2.0 * n * n * p
    nbytes = b0 + n_gamma * b1 + n_gamma * n_folds * F32 * n * n
    return flops, nbytes
