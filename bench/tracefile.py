"""From a JAX profiler trace to the numbers the per-layer metrics read.

``load_xplane`` turns the ``.xplane.pb`` of a traced window into plain
lists of events, ``[name, start_ns, duration_ns]``:

  ops      device operations (the device plane's "XLA Ops" line)
  modules  executions of whole compiled programs ("XLA Modules")
  host     the host's own trace annotations (threads of "/host:CPU")

``reduce`` works on those lists only, so it is tested on a small trace
recorded on the chip (``tests/data``).
"""
from __future__ import annotations

import glob
import os

import numpy as np

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_xplane(log_dir: str) -> str:
    hits = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                            recursive=True))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return hits[-1]


def load_xplane(path: str) -> dict:
    """The events of the first device plane and of the host threads."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    out = {"ops": [], "modules": [], "host": [], "lines": {}}
    device_done = False
    for plane in pd.planes:
        is_device = plane.name.startswith("/device:")
        if is_device and device_done:
            continue
        for line in plane.lines:
            out["lines"].setdefault(plane.name, []).append(line.name)
            if is_device and line.name in (OPS_LINE, MODULES_LINE):
                key = "ops" if line.name == OPS_LINE else "modules"
                out[key] += [[e.name, float(e.start_ns), float(e.duration_ns)]
                             for e in line.events]
            elif plane.name.startswith("/host:CPU"):
                out["host"] += [[e.name, float(e.start_ns),
                                 float(e.duration_ns)] for e in line.events]
        device_done = device_done or (is_device and bool(out["ops"]))
    return out


def union(intervals: np.ndarray) -> np.ndarray:
    """Merge ``[[start, end], ...]`` into disjoint sorted intervals."""
    if not len(intervals):
        return np.zeros((0, 2))
    iv = intervals[np.argsort(intervals[:, 0])]
    merged = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return np.asarray(merged)


def _intervals(events) -> np.ndarray:
    if not events:
        return np.zeros((0, 2))
    a = np.asarray([[s, s + d] for _, s, d in events], dtype=np.float64)
    return a


def op_name(text: str, width: int = 72) -> str:
    """``%name = type{layout} op(...)`` -> ``name type``, cut to
    ``width``: the trace names an operation by its whole HLO line."""
    name, _, rest = text.partition(" = ")
    kind = rest.split("{", 1)[0].split(" ", 1)[0] if rest else ""
    return f"{name.lstrip('%')} {kind}".strip()[:width]


def host_label(host, t0: float, t1: float) -> str:
    """The innermost host annotation covering the middle of a gap."""
    mid = 0.5 * (t0 + t1)
    best, best_d = "no host annotation", np.inf
    for name, s, d in host:
        if s <= mid <= s + d and d < best_d:
            best, best_d = name, d
    return best


def reduce(tr: dict, step_module: str, top: int = 10,
           chunk_module: str | None = None) -> dict:
    """Device busy time (union of operation intervals), the device time
    of each execution of the program whose name contains
    ``step_module`` (and of ``chunk_module``'s, if given), the
    operations that took most time, and the longest idle gaps labelled
    by what the host was doing in them."""
    busy_iv = union(_intervals(tr["ops"]))
    busy_s = float((busy_iv[:, 1] - busy_iv[:, 0]).sum()) / 1e9

    def module_s(part):
        return [d / 1e9 for name, _, d in tr["modules"] if part in name]
    steps = module_s(step_module)
    chunks = module_s(chunk_module) if chunk_module else []
    by_op: dict = {}
    for name, _, d in tr["ops"]:
        key = op_name(name)
        by_op[key] = by_op.get(key, 0.0) + d / 1e9
    ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    gaps = []
    for (_, e0), (s1, _) in zip(busy_iv[:-1], busy_iv[1:]):
        gaps.append((s1 - e0, e0, s1))
    gaps.sort(reverse=True)
    idle = [[host_label(tr["host"], a, b), g / 1e9]
            for g, a, b in gaps[:top]]
    return {"busy_s": busy_s, "step_device_s": steps,
            "chunk_device_s": chunks,
            "device_ops": [[n, s] for n, s in ops], "idle_gaps": idle}
