"""Reduce a profiler trace (`.xplane.pb`) to what the per-layer metrics read.

The traced window is the span from the first to the last `bench_round`
host annotation, the marks the harness puts around each round it drives.
Within it, per device plane (`/device:TPU:<n>`):

  * busy time: the union of the intervals of the device's operations
    (the "XLA Ops" line), clipped to the window;
  * module time: each jitted program's device time from the
    "XLA Modules" line, keyed by the program's name without its id
    (`jit_run(12)` -> `jit_run`);
  * collective time: operations named as all-reduce / all-gather /
    collective-permute / reduce-scatter / all-to-all, and the part of it
    during which no other operation runs on that device.

Each is averaged over the device planes that ran anything. Idle gaps are
labelled by the innermost host event that covers the gap's middle.
"""
from __future__ import annotations

import glob
import re
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

ROUND_MARK = "bench_round"
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
_COLLECTIVE = re.compile(r"all-reduce|all-gather|collective-permute|"
                         r"reduce-scatter|all-to-all|psum", re.I)
_ID_SUFFIX = re.compile(r"\(\d+\)$")


@dataclass
class TraceSummary:
    window_s: float
    busy_s: float                    # mean over devices that ran anything
    n_devices: int
    module_s: Dict[str, float]       # mean device seconds per program
    op_s: Dict[str, float]           # mean device seconds per operation
    collective_s: float
    collective_exposed_s: float
    idle_gaps: List[Tuple[str, float]] = field(default_factory=list)


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


def _length(iv) -> float:
    return sum(e - s for s, e in iv)


def _intersect(a, b) -> float:
    """Total length of the intersection of two sorted disjoint lists."""
    i = j = 0
    tot = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            tot += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def events_of(path: str):
    """[(plane, line, name, start_ns, end_ns)] of every event in the file."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                out.append((plane.name, line.name, ev.name,
                            float(ev.start_ns), float(ev.end_ns)))
    return out


def summarize(events, n_gaps: int = 10) -> TraceSummary:
    marks = [(s, e) for pl, _, name, s, e in events
             if not _DEVICE_PLANE.match(pl) and name == ROUND_MARK]
    if not marks:
        raise ValueError(f"the trace holds no '{ROUND_MARK}' annotation")
    lo, hi = min(s for s, _ in marks), max(e for _, e in marks)
    window_ns = hi - lo

    per_dev: Dict[str, dict] = {}
    for pl, line, name, s, e in events:
        if not _DEVICE_PLANE.match(pl) or e <= lo or s >= hi:
            continue
        s, e = _clip(s, e, lo, hi)
        d = per_dev.setdefault(pl, {"ops": [], "coll": [], "other": [],
                                    "mod": {}, "op": {}})
        if line == "XLA Modules":
            key = _ID_SUFFIX.sub("", name)
            d["mod"][key] = d["mod"].get(key, 0.0) + (e - s)
            d.setdefault("mod_iv", []).append((s, e))
        elif line == "XLA Ops":
            d["ops"].append((s, e))
            d["op"][name] = d["op"].get(name, 0.0) + (e - s)
            (d["coll"] if _COLLECTIVE.search(name) else d["other"]).append(
                (s, e))
    for d in per_dev.values():
        if not d["ops"]:             # a plane without an ops line: the
            d["ops"] = d.get("mod_iv", [])   # programs' spans are the busy time
            d["other"] = list(d["ops"])
    devs = {k: v for k, v in per_dev.items() if v["ops"]}
    if not devs:
        raise ValueError("no device operation ran inside the traced window")
    n = len(devs)
    busy = coll = coll_exposed = 0.0
    modules: Dict[str, float] = {}
    ops: Dict[str, float] = {}
    first = sorted(devs)[0]
    for name, d in devs.items():
        u = _union(d["ops"])
        busy += _length(u)
        cu, ou = _union(d["coll"]), _union(d["other"])
        coll += _length(cu)
        coll_exposed += _length(cu) - _intersect(cu, ou)
        for k, v in d["mod"].items():
            modules[k] = modules.get(k, 0.0) + v
        for k, v in d["op"].items():
            ops[k] = ops.get(k, 0.0) + v
        if name == first:
            gaps = _gaps(u, lo, hi)
    host = [(s, e, name) for pl, _, name, s, e in events
            if not _DEVICE_PLANE.match(pl) and e > s]
    labelled = [(_label(host, (a + b) / 2), (b - a) * 1e-9)
                for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:n_gaps]]
    return TraceSummary(
        window_s=window_ns * 1e-9, busy_s=busy / n * 1e-9, n_devices=n,
        module_s={k: v / n * 1e-9 for k, v in modules.items()},
        op_s={k: v / n * 1e-9 for k, v in ops.items()},
        collective_s=coll / n * 1e-9,
        collective_exposed_s=coll_exposed / n * 1e-9, idle_gaps=labelled)


def _gaps(busy, lo, hi):
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def _label(host, t) -> str:
    """Innermost host event covering time t (the round mark if nothing
    narrower does)."""
    best = None
    for s, e, name in host:
        if s <= t <= e and (best is None or e - s < best[1] - best[0]):
            best = (s, e, name)
    return best[2] if best else "no host event"


def breakdown(summary: TraceSummary, top: int = 10) -> dict:
    ops = sorted(summary.op_s.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in summary.idle_gaps[:top]]}
