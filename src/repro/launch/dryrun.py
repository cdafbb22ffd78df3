import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# Multi-pod dry-run: prove every (arch x shape x mesh) cell lowers,
# partitions, and compiles on the production mesh — and extract the
# roofline terms (FLOPs / bytes / collective bytes) from the compiled
# artifact.
#
# MUST run as its own process: the XLA_FLAGS lines above execute before
# ANY jax import (jax locks the device count on first init).  Do NOT set
# this flag globally — smoke tests and benches must see 1 device.
#
# Usage:
#   PYTHONPATH=src python -m repro.launch.dryrun --arch stablelm-12b --shape train_4k
#   PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both --out results.json

import argparse
import functools
import json
import re
import sys
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, all_cells, get_arch
from repro.launch import shapes as shapes_mod
from repro.launch.mesh import make_production_mesh
from repro.models import model as model_mod
from repro.train.lm_trainer import make_train_step


# --------------------------------------------------------------------------
# collective-bytes extraction from the partitioned HLO
# --------------------------------------------------------------------------

_COLL_RE = re.compile(
    r"=\s*([^=]*?)\s*"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\(")

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}


def _line_coll_bytes(line: str):
    m = _COLL_RE.search(line)
    if not m:
        return None
    shapes_str, op = m.group(1), m.group(2)
    total = 0
    for sm in _SHAPE_RE.finditer(shapes_str):
        dt, dims = sm.group(1), sm.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return op, float(total)


_COMP_HDR = re.compile(r"^(?:ENTRY\s+)?%?([\w\.\-]+)\s*\([^)]*\)\s*->")
_WHILE_RE = re.compile(r"\bwhile\(")
_COND_RE = re.compile(r"condition=%?([\w\.\-]+)")
_BODY_RE = re.compile(r"body=%?([\w\.\-]+)")
_S32_CONST = re.compile(r"s32\[\]\s+constant\((\d+)\)")
# non-while computation edges (executed once per call site)
_CALL_EDGES = re.compile(
    r"(?:to_apply|calls|true_computation|false_computation)=%?([\w\.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")

_OPS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
        "collective-permute")


def collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Trip-count-aware collective bytes from the partitioned HLO.

    XLA text places a scan's body in a separate while-body computation —
    summing naively counts it ONCE.  We parse computations, recover each
    while's trip count from the s32 bound in its condition computation,
    and multiply nested collective bytes accordingly.
    """
    # ---- split into computations (header: unindented "name (...) -> ... {")
    comps: Dict[str, list] = {}
    cur: Optional[str] = None
    entry: Optional[str] = None
    for line in hlo_text.splitlines():
        s = line.strip()
        if not line.startswith(" ") and s.endswith("{") and "->" in s:
            toks = s.split()
            name = toks[1] if toks[0] == "ENTRY" else toks[0]
            cur = name.lstrip("%")
            comps[cur] = []
            if toks[0] == "ENTRY":
                entry = cur
            continue
        if cur is not None:
            if s == "}":
                cur = None
            else:
                comps[cur].append(line)

    def trip_count(cond_name: str) -> float:
        consts = [int(v) for l in comps.get(cond_name, ())
                  for v in _S32_CONST.findall(l)]
        return float(max(consts)) if consts else 1.0

    # ---- per-computation direct bytes + nested whiles
    direct: Dict[str, Dict[str, float]] = {}
    children: Dict[str, list] = {}
    for name, lines in comps.items():
        d = {op: 0.0 for op in _OPS}
        counts = {op: 0 for op in _OPS}
        kids = []
        for line in lines:
            got = _line_coll_bytes(line)
            if got:
                d[got[0]] += got[1]
                counts[got[0]] += 1
            if _WHILE_RE.search(line):
                mc, mb = _COND_RE.search(line), _BODY_RE.search(line)
                if mb:
                    kids.append((mb.group(1),
                                 trip_count(mc.group(1)) if mc else 1.0))
            else:
                for callee in _CALL_EDGES.findall(line):
                    kids.append((callee, 1.0))
                mb = _BRANCHES.search(line)
                if mb:
                    for callee in mb.group(1).split(","):
                        kids.append((callee.strip().lstrip("%"), 1.0))
        direct[name] = d
        direct[name + "/counts"] = counts  # type: ignore
        children[name] = kids

    def total(name: str, seen=()) -> Dict[str, float]:
        if name in seen or name not in direct:
            return {op: 0.0 for op in _OPS}
        out = dict(direct[name])
        for kid, trips in children.get(name, ()):  # nested scans multiply
            sub = total(kid, seen + (name,))
            for op in _OPS:
                out[op] += sub[op] * trips
        return out

    if entry is None:
        entry = next(iter(comps), None)
    result = total(entry) if entry else {op: 0.0 for op in _OPS}
    result["counts"] = (direct.get(entry + "/counts")
                        if entry else None) or {op: 0 for op in _OPS}
    return result


# --------------------------------------------------------------------------
# per-cell lowering
# --------------------------------------------------------------------------

def build_step_fn(spec: Dict[str, Any]):
    cfg = spec["cfg"]
    kind = spec["kind"]
    if kind == "train":
        step = make_train_step(cfg, spec["opt_cfg"], spec["grad_accum"])
        return jax.jit(step)
    if kind == "prefill":
        return jax.jit(functools.partial(model_mod.prefill, cfg))
    if kind == "encode":
        return jax.jit(functools.partial(model_mod.encode, cfg))
    if kind == "decode":
        return jax.jit(functools.partial(model_mod.decode_step, cfg))
    raise ValueError(kind)


VARIANTS: Dict[str, Dict[str, Any]] = {
    "baseline": {},
    "kv8": {"kv_cache_dtype": "int8"},
    "moe_gather": {"moe_impl": "gather"},
    "moe_gather_cap1": {"moe_impl": "gather", "moe_capacity_factor": 1.0},
    "moe_pregather": {"moe_impl": "gather", "moe_capacity_factor": 1.0,
                      "moe_pregather": True},
    "moe_bigchunk": {"moe_impl": "gather", "moe_capacity_factor": 1.0,
                     "moe_chunk": 8192},
    "noactshard": {"shard_activations": False},
    "noactshard_accum4": {"shard_activations": False, "grad_accum": 4},
}


def dryrun_cell(arch_id: str, shape_name: str, mesh, mesh_name: str,
                verbose: bool = True, variant: str = "baseline"
                ) -> Dict[str, Any]:
    from repro.launch.jaxpr_cost import cost_of

    t0 = time.time()
    spec = shapes_mod.input_specs(arch_id, shape_name, mesh,
                                  overrides=VARIANTS[variant])
    fn = build_step_fn(spec)
    with mesh:
        lowered = fn.lower(*spec["args"])
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        # trip-aware structural FLOPs/bytes from the jaxpr (global -> /dev)
        structural = cost_of(fn, *spec["args"])

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()   # per-device, but scan bodies once
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    coll = collective_bytes(compiled.as_text())   # trip-aware, per device

    n_dev = int(np.prod(list(mesh.shape.values())))

    result = {
        "arch": arch_id,
        "shape": shape_name,
        "mesh": mesh_name,
        "kind": spec["kind"],
        "variant": variant,
        "n_devices": n_dev,
        "flops": structural.flops / n_dev,
        "bytes_accessed": structural.bytes / n_dev,
        "xla_flops_body_once": float(cost.get("flops", -1.0)),
        "xla_bytes_body_once": float(cost.get("bytes accessed", -1.0)),
        "collective_bytes": {k: v for k, v in coll.items() if k != "counts"},
        "collective_counts": coll["counts"],
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
            "output_bytes": getattr(mem, "output_size_in_bytes", 0),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
            "generated_code_bytes": getattr(mem, "generated_code_size_in_bytes", 0),
        },
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
    }
    if verbose:
        print(f"[dryrun] {arch_id} x {shape_name} x {mesh_name}: "
              f"flops/dev={result['flops']:.3e} bytes/dev={result['bytes_accessed']:.3e} "
              f"coll/dev={sum(result['collective_bytes'].values()):.3e} "
              f"(lower {t_lower:.0f}s compile {t_compile:.0f}s)", flush=True)
        print(f"  memory_analysis: {result['memory']}", flush=True)
    return result


def dryrun_svm(mesh, mesh_name: str, slots_per_dev: int = 2, k: int = 2000,
               d: int = 128, verbose: bool = True,
               gram_dtype: str = "f32") -> Dict[str, Any]:
    """Roofline the paper's own technique: the sharded cell-CV trainer.

    One slot = one padded cell of k samples; the full 10x10 grid x 5 folds
    CV runs per slot, slots sharded over every mesh axis, at the given
    Gram dtype."""
    from repro.core import cv as cv_mod
    from repro.core.grids import liquid_grid
    from repro.distributed.cell_trainer import train_cells

    t0 = time.time()
    n_dev = int(np.prod(list(mesh.shape.values())))
    n_slots = n_dev * slots_per_dev
    cfg = cv_mod.CVConfig(n_folds=5, max_iters=500, gram_dtype=gram_dtype)
    grid = liquid_grid(n=k, dim=d)
    lam_c, sub_c, task_c, n_lam, n_sub = cv_mod.grid_columns(grid, cfg, 1)
    axes = tuple(mesh.axis_names)

    from jax.sharding import NamedSharding, PartitionSpec as P
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, P(axes)))

    keys = jax.random.split(jax.random.PRNGKey(0), n_slots)  # concrete, tiny
    args = (sds((n_slots, k, d), jnp.float32),        # x_cells
            sds((n_slots, 1, k), jnp.float32),        # y_cells
            sds((n_slots, 1, k), jnp.float32),        # tmask
            sds((n_slots, k), jnp.float32),           # mask
            sds((n_slots, len(grid.gammas)), jnp.float32),
            keys)                                      # fold keys
    with mesh:
        lowered = train_cells.lower(*args, lam_c, sub_c, task_c, cfg,
                                    n_lam, n_sub, mesh=mesh, axis_names=axes)
        compiled = lowered.compile()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    coll = collective_bytes(compiled.as_text())
    mem = compiled.memory_analysis()

    from repro.launch.jaxpr_cost import cost_of
    structural = cost_of(
        lambda *a: train_cells(*a, lam_c, sub_c, task_c, cfg, n_lam, n_sub,
                               mesh=mesh, axis_names=axes),
        *args, while_trips=float(cfg.max_iters))

    result = {
        "arch": "svm-cell-trainer",
        "shape": f"cells_k{k}_d{d}_{gram_dtype}gram",
        "mesh": mesh_name, "kind": "svm_train", "n_devices": n_dev,
        "flops": structural.flops / n_dev,
        "bytes_accessed": structural.bytes / n_dev,
        "while_trips_assumed": cfg.max_iters,
        "xla_flops_body_once": float(cost.get("flops", -1.0)),
        "collective_bytes": {kk: v for kk, v in coll.items() if kk != "counts"},
        "collective_counts": coll["counts"],
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
            "output_bytes": getattr(mem, "output_size_in_bytes", 0),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
        },
        "compile_s": round(time.time() - t0, 1),
    }
    if verbose:
        print(f"[dryrun] svm-cell-trainer x {mesh_name}: "
              f"flops={result['flops']:.3e} bytes={result['bytes_accessed']:.3e} "
              f"coll={sum(result['collective_bytes'].values()):.3e}", flush=True)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=list(ARCH_IDS))
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--svm", action="store_true",
                    help="also dry-run the SVM cell trainer workload")
    ap.add_argument("--variant", default="baseline", choices=list(VARIANTS),
                    help="ModelConfig perf-variant overrides")
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--out", default=None, help="append JSON-lines results here")
    args = ap.parse_args(argv)

    assert len(jax.devices()) == 512, \
        f"dryrun needs 512 forced host devices, got {len(jax.devices())}"

    meshes = []
    if args.mesh in ("single", "both"):
        meshes.append(("single_pod_16x16", make_production_mesh(multi_pod=False)))
    if args.mesh in ("multi", "both"):
        meshes.append(("multi_pod_2x16x16", make_production_mesh(multi_pod=True)))

    cells = all_cells() if args.all else (
        [(args.arch, args.shape)] if args.arch else [])
    failures = []
    results = []
    if args.svm:
        for mesh_name, mesh in meshes:
            for gdt in ("f32", "bf16"):
                try:
                    r = dryrun_svm(mesh, mesh_name, gram_dtype=gdt)
                    results.append(r)
                    if args.out:
                        with open(args.out, "a") as f:
                            f.write(json.dumps(r) + "\n")
                except Exception as e:  # noqa: BLE001
                    traceback.print_exc()
                    failures.append(("svm-cell-trainer", "cells", mesh_name,
                                     repr(e)))
    for arch_id, shape_name in cells:
        for mesh_name, mesh in meshes:
            try:
                r = dryrun_cell(arch_id, shape_name, mesh, mesh_name,
                                variant=args.variant)
                results.append(r)
                if args.out:
                    with open(args.out, "a") as f:
                        f.write(json.dumps(r) + "\n")
            except Exception as e:  # noqa: BLE001 — report all cell failures
                traceback.print_exc()
                failures.append((arch_id, shape_name, mesh_name, repr(e)))

    print(f"\n[dryrun] {len(results)} cells OK, {len(failures)} failed")
    for f in failures:
        print("  FAIL:", f)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
