"""Reduce a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read: device busy time (the union of op intervals), device time
per kernel, the ops that took most time, and the longest idle gaps named by
what the host was doing.

Device planes are those whose name starts with ``/device:TPU:``; their op
events sit on the ``XLA Ops`` line, where a loop's event encloses the
events of the ops it runs, so per-op times are self times (an event's
duration less its children's).  An op's name is its HLO text, which starts
with the instruction's name (``%sq_dists_pallas.1 = ...``); a kernel is
found by a substring of it.  The op events carry no scope metadata, so no
time per ``jax.named_scope`` can be read.  Host events (the ``/host:CPU``
plane, with the profiler's Python tracer on) name the idle gaps.
"""
from __future__ import annotations

import glob
import os

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str) -> dict:
    """{"device": {plane: [(name, t0_ns, t1_ns)]},
    "host": [(name, t0_ns, t1_ns)]} from one xplane file."""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    dev, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    ops.append((ev.name, float(ev.start_ns),
                                float(ev.end_ns)))
            dev[plane.name] = ops
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.duration_ns > 0:
                        host.append((ev.name, float(ev.start_ns),
                                     float(ev.end_ns)))
    return {"device": dev, "host": host}


def union(intervals) -> list:
    """Merged, sorted, disjoint [t0, t1) intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return out


def length(intervals) -> float:
    return sum(b - a for a, b in intervals)


def self_times(ops) -> list:
    """(name, self_ns) per op: its duration less the durations of the ops
    directly inside it (events on one line nest properly)."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    self_ns = [ops[i][2] - ops[i][1] for i in range(len(ops))]
    stack = []
    for i in order:
        a, b = ops[i][1], ops[i][2]
        while stack and ops[stack[-1]][2] <= a:
            stack.pop()
        if stack and b <= ops[stack[-1]][2]:
            self_ns[stack[-1]] -= b - a
        stack.append(i)
    return [(ops[i][0], self_ns[i]) for i in range(len(ops))]


def short_name(hlo: str) -> str:
    """``%while.119 = (...) while(...)`` -> ``while.119``."""
    head = hlo.split(" = ", 1)[0]
    return head[1:] if head.startswith("%") else head


def reduce(tr: dict, kernels: dict, n_ops: int = 10,
           n_gaps: int = 10) -> dict:
    """Seconds, averaged over the device planes that ran anything.

    ``kernels`` maps a kernel's metric name to the substring its events'
    names carry.  Returns ``busy_s``, ``kernel_s`` {name: self seconds},
    ``device_ops`` [[op, self seconds]] and ``idle_gaps`` [[host activity,
    seconds]], largest first, and ``n_devices``.
    """
    planes = [ops for ops in tr["device"].values() if ops]
    n = max(len(planes), 1)
    busy, kern = 0.0, {k: 0.0 for k in kernels}
    per_op, gaps = {}, []
    for ops in planes:
        merged = union((a, b) for _, a, b in ops)
        busy += length(merged)
        for name, t in self_times(ops):
            key = short_name(name)
            per_op[key] = per_op.get(key, 0.0) + t
            for k, pat in kernels.items():
                if pat in key:
                    kern[k] += t
        gaps += [(merged[i][1], merged[i + 1][0])
                 for i in range(len(merged) - 1)]
    gaps.sort(key=lambda g: g[0] - g[1])
    top_ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:n_ops]
    return {
        "busy_s": busy / n / 1e9,
        "kernel_s": {k: v / n / 1e9 for k, v in kern.items()},
        "device_ops": [[name, t / n / 1e9] for name, t in top_ops],
        "idle_gaps": [[host_activity(tr["host"], a, b), (b - a) / 1e9]
                      for a, b in gaps[:n_gaps]],
        "n_devices": len(planes),
    }


def host_activity(host, a: float, b: float) -> str:
    """What the host was doing in the gap [a, b): the shortest host event
    that covers at least half of it, else the one overlapping it most, else
    "no host event"."""
    cover, best_ov, name = None, 0.0, "no host event"
    for ev, h0, h1 in host:
        ov = min(b, h1) - max(a, h0)
        if ov >= 0.5 * (b - a) and (cover is None or h1 - h0 < cover[0]):
            cover = (h1 - h0, ev)
        if ov > best_ov:
            best_ov, name = ov, ev
    return cover[1] if cover else name
