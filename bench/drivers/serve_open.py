"""Driver ``serve_open``: open-loop 1-row requests into ``SVMEngine.run``.

Set-up builds the configuration's cell plan (as ``train_waves`` does) and a
bank on it that the benchmark draws itself: each cell's rows scaled by the
benchmark's float64 statistics, its centre the mean of its rows, gamma one
of its liquidSVM grid values (median heuristic; the grid index a real fit
chose), and hinge coefficients ``y_i * C * u`` on a ``nnz_share`` of its
rows (``C = 1 / (2 lambda n_train)``, ``u`` from the quantiles a real fit
gave), drawn from the seed and compacted by ``ModelBank.from_cells``.  It
then warms up every launch shape (every slot count up to all cells, by
``slot_bucket``, at each row bucket of ``warm_m_pad``) and runs
``warmup_s`` of the cell's own traffic.

The window: ``rate_per_s * seconds`` requests of one held-out row each,
arrival times uniform over the window (a Poisson process of that count),
rows and times drawn from the seed.  A generator yields, each time the
engine asks, the requests that are due (or ``None``), and ``SVMEngine.run``
serves them under its own launch policy.  A request's latency runs from
its due time to the completion the engine records for it (its
``breakdown`` total plus the wait from due time to submission).

The check compares ``reference_rows`` requests, drawn from the seed,
against the plain reference's routing and decision: ``dec_gap`` is the
widest |decision - reference| in f32 ulps of sum_i |c_i| K_i (a tie between
two centres accepts either cell).
"""
from __future__ import annotations

import time

import numpy as np

import cellplan
import data as bdata
import reference

EPS32 = float(np.finfo(np.float32).eps)


def _draw_bank(ctx, st, rng):
    """The bank's arrays, drawn by the benchmark (numpy, float64 stats)."""
    plan, xtr, ytr = st["plan"], st["xtr"], st["ytr"]
    b = ctx.traffic["bank"]
    x64 = xtr.astype(np.float64)
    mean, std = x64.mean(0), x64.std(0)
    std = np.where(std > 0, std, 1.0)
    n_cells, k = plan.indices.shape
    d = xtr.shape[1]
    sv = np.zeros((n_cells, k, d), np.float32)
    coef = np.zeros((n_cells, k), np.float32)
    gamma = np.zeros(n_cells, np.float32)
    centers = np.zeros((n_cells, d), np.float32)
    q_at = np.linspace(0.0, 1.0, len(b["u_quantiles"]))
    for c in range(n_cells):
        msk = plan.mask[c] > 0
        ids = plan.indices[c][msk]
        xc = (x64[ids] - mean) / std
        n = len(ids)
        sv[c, :n] = xc.astype(np.float32)
        centers[c] = xc.mean(0).astype(np.float32)
        gam, lam = reference.liquid_grid(
            n, d, reference.median_dist(sv[c], plan.mask[c]),
            ctx.cfg["cells"]["size"])
        gamma[c] = gam[b["gamma_index"]]
        big_c = 1.0 / (2.0 * lam[b["lambda_index"]] * 0.8 * n)
        on = rng.uniform(size=n) < b["nnz_share"]
        u = np.interp(rng.uniform(size=n), q_at, b["u_quantiles"])
        coef[c, :n] = np.where(on, ytr[ids] * big_c * u, 0.0)
    return {"sv": sv, "coef": coef, "gamma": gamma, "centers": centers,
            "mean": mean.astype(np.float32), "std": std.astype(np.float32),
            "mask": plan.mask.astype(np.float32)}


def _compact(bank):
    """Nonzero rows only, per cell (the reference's own compaction)."""
    nz = np.abs(bank["coef"]) > 0
    kmax = max(int(nz.sum(1).max()), 1)
    sv = np.zeros((len(nz), kmax, bank["sv"].shape[2]), np.float32)
    co = np.zeros((len(nz), kmax), np.float32)
    for c in range(len(nz)):
        i = np.flatnonzero(nz[c])
        sv[c, :len(i)], co[c, :len(i)] = bank["sv"][c, i], bank["coef"][c, i]
    return sv, co


def _prepare(ctx):
    """Plan, bank arrays, the window's rows and due times (no engine)."""
    tr = ctx.traffic
    st = cellplan.build(ctx)
    s_bank, s_req = bdata.seeds(ctx.seed, 2)
    bank = _draw_bank(ctx, st, np.random.default_rng(s_bank))
    rs = np.random.default_rng(s_req)
    xte = st["xte"]
    n_req = int(round(tr["rate_per_s"] * ctx.seconds))
    n_warm = int(round(tr["rate_per_s"] * tr["warmup_s"]))
    pick = rs.choice(len(xte), size=n_req + n_warm,
                     replace=n_req + n_warm > len(xte))
    st.update(bank=bank, s_req=s_req, rs=rs,
              warm_rows=xte[pick[:n_warm]], rows=xte[pick[n_warm:]],
              warm_due=np.sort(rs.uniform(0, tr["warmup_s"], n_warm)),
              due=np.sort(rs.uniform(0.0, ctx.seconds, n_req)))
    return st


def setup(ctx):
    from repro.serve import SVMEngine
    from repro.serve.model_bank import ModelBank
    tr = ctx.traffic
    st = _prepare(ctx)
    bank, rs = st["bank"], st["rs"]
    n_cells = len(bank["gamma"])
    mb = ModelBank.from_cells(
        bank["sv"], bank["mask"], bank["coef"][:, :, None, None],
        bank["gamma"][:, None, None], bank["centers"], kernel="gauss_rbf",
        drop_tol=0.0, dtype=tr["bank"]["dtype"], feat_mean=bank["mean"],
        feat_std=bank["std"], scenario="binary", routing="nearest",
        pad_multiple=tr["bank"]["pad_multiple"])
    eng = SVMEngine(mb, **tr["engine"])

    # every launch shape: S slots of M rows (S cells' centres, M times each)
    raw_c = bank["centers"] * bank["std"] + bank["mean"]
    for m_pad in tr["warm_m_pad"]:
        for s in range(eng.slot_bucket, n_cells + eng.slot_bucket,
                       eng.slot_bucket):
            cells = rs.choice(n_cells, size=min(s, n_cells), replace=False)
            eng.submit(np.repeat(raw_c[cells], m_pad, axis=0))
            eng.step()
    _serve(eng, st["warm_rows"], st["warm_due"])
    st["eng"] = eng
    return st


def _serve(eng, rows, due):
    """Drive ``eng.run`` open loop; returns per-request latency (ms),
    decisions, generator lateness (ms) and the window's wave records."""
    n = len(rows)
    base = int(eng.stats().get("submitted", 0))
    lat = np.full(n, np.nan)
    yield_t = np.zeros(n)
    waves, seen_w = [], eng.wave_stats.total
    state = {"next": 0}
    late = []

    def harvest():
        nonlocal seen_w
        new = eng.wave_stats.total - seen_w
        for i in range(new, 0, -1):
            waves.append(eng.wave_stats[-i] if i <= len(eng.wave_stats)
                         else None)
        seen_w = eng.wave_stats.total
        i = state["next"]
        while i < n:
            b = eng.breakdown(base + i)
            if b is None:
                break
            lat[i] = b["total_ms"] + (yield_t[i] - due[i]) * 1e3
            i += 1
        state["next"] = i

    def traffic():
        t0 = time.perf_counter()
        i = 0
        while i < n:
            harvest()
            now = time.perf_counter() - t0
            j = int(np.searchsorted(due, now, side="right"))
            if j > i:
                yield_t[i:j] = now
                late.append((now - due[i]) * 1e3)
                batch, i = rows[i:j], j
                yield batch
            else:
                yield None
        harvest()

    t0 = time.perf_counter()
    res = eng.run(traffic())
    wall = time.perf_counter() - t0
    harvest()
    dec = np.full(n, np.nan, np.float32)
    for i in range(n):
        r = res.get(base + i)
        if r is not None:
            dec[i] = float(np.asarray(r).reshape(-1)[0])
    return {"lat": lat, "dec": dec, "late": np.asarray(late), "wall": wall,
            "waves": [w for w in waves if w is not None]}


def window(ctx, st):
    eng = st["eng"]
    s0 = eng.stats()
    r = _serve(eng, st["rows"], st["due"] * 1.0)
    s1 = eng.stats()
    lat = r["lat"]
    done = np.isfinite(lat)
    q = np.quantile(lat[done], [0.5, 0.95]) if done.any() else [np.nan] * 2
    end = float(np.nanmax(st["due"] * 1e3 + lat)) / 1e3 if done.any() else r["wall"]
    ps0, ps1 = s0["per_stage"], s1["per_stage"]
    host_ms = sum(ps1[s]["total_ms"] - ps0[s]["total_ms"]
                  for s in ("pack", "dispatch", "collect"))
    n_waves = ps1["pack"]["count"] - ps0["pack"]["count"]
    launched = s1.get("launched_rows", 0) - s0.get("launched_rows", 0)
    served = s1.get("served_rows", 0) - s0.get("served_rows", 0)
    late = r["late"]
    return {
        "wall_s": r["wall"],
        "attempted": len(lat), "failed": int((~done).sum()),
        "end_to_end": {"serve_p50_ms": float(q[0]),
                       "serve_p95_ms": float(q[1]),
                       "serve_rows_per_s": float(done.sum() / end)},
        "serve": {"host_ms_per_wave": host_ms / max(n_waves, 1),
                  "occupancy": served / max(launched, 1),
                  "waves": r["waves"], "n_waves": n_waves,
                  "k": int(eng.bank.k_max), "d": int(st["xte"].shape[1]),
                  "p": 1},
        "dec": r["dec"], "done": done,
        "notes": {"requests": len(lat), "waves": n_waves,
                  "generator_late_ms_p50": round(float(np.median(late)), 6)
                  if len(late) else 0.0,
                  "generator_late_ms_max": round(float(late.max()), 6)
                  if len(late) else 0.0,
                  "wave_records": len(r["waves"]),
                  "bank_k_max": int(eng.bank.k_max)},
        "state": st,
    }


def _sample(ctx, st, done):
    """The answered requests the check compares, drawn from the seed."""
    rng = np.random.default_rng(st["s_req"] + 1)
    idx = np.flatnonzero(done)
    return rng.choice(idx, size=min(ctx.traffic["reference_rows"], len(idx)),
                      replace=False)


def _ulps(dec, ref):
    """|dec - ref| in f32 ulps of sum_i |c_i| K_i (ties: either cell)."""
    gap = np.abs(dec - ref["dec"])
    alt = np.abs(dec - ref["alt_dec"])
    gap = np.where(np.isfinite(alt), np.minimum(gap, alt), gap)
    return gap / (EPS32 * np.maximum(ref["scale"], 1e-30))


def check(ctx):
    """``dec_gap`` over the sampled requests, and the unanswered count."""
    w = ctx.window
    st = w.pop("state")
    dec, done = w.pop("dec"), w.pop("done")
    idx = _sample(ctx, st, done)
    bank = st["bank"]
    sv, co = _compact(bank)
    ref = reference.decisions(st["rows"][idx], bank["mean"], bank["std"],
                              bank["centers"], sv, co, bank["gamma"])
    ulps = _ulps(dec[idx], ref)
    return {"dec_gap": float(ulps.max()) if len(ulps) else float("inf"),
            "unanswered": int((~done).sum())}


def stand_in(ctx, ref):
    """The record the check reads, with ``ref`` (a copy of the reference at
    a lower precision) in the program's place for the requests it compares."""
    st = _prepare(ctx)
    done = np.ones(len(st["rows"]), bool)
    idx = _sample(ctx, st, done)
    b = st["bank"]
    sv, co = _compact(b)
    dec = np.full(len(done), np.nan, np.float32)
    dec[idx] = ref.decisions(st["rows"][idx], b["mean"], b["std"],
                             b["centers"], sv, co, b["gamma"])["dec"]
    return {"dec": dec, "done": done, "state": st}
