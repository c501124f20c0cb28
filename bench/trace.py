"""Reduce a profiler trace (``.xplane.pb``) to what the metrics read: the
window, the device's busy and idle time, per-op and per-program device
time, collective time and the longest idle gaps with what the host was doing in each.

The harness opens a host annotation named ``WINDOW`` around the traced
window, so the window and the device's ops are read on one clock.
Busy time is the union of the intervals of the ops on a chip's
``XLA Ops`` line, clipped to the window, averaged over the chips used.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict

WINDOW = "bench_window"
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")


def find_xplane(root) -> str:
    files = sorted(glob.glob(os.path.join(str(root), "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {root}")
    return files[-1]


def _events(line):
    for e in line.events:
        yield e.name, e.start_ns, e.start_ns + e.duration_ns


def _union(intervals):
    """Sorted, merged [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def device_planes(pd, chips: int):
    planes = [p for p in pd.planes if p.name.startswith("/device:TPU:")
              and p.name[len("/device:TPU:"):].isdigit()]
    planes.sort(key=lambda p: int(p.name[len("/device:TPU:"):]))
    return planes[:chips]


def _line(plane, name):
    for ln in plane.lines:
        if ln.name == name:
            return ln
    return None


def window_of(pd):
    """(start_ns, end_ns) of the harness's ``WINDOW`` host annotation."""
    for p in pd.planes:
        if not p.name.startswith("/host:"):
            continue
        for ln in p.lines:
            for name, s, e in _events(ln):
                if name == WINDOW:
                    return s, e
    raise ValueError(f"no {WINDOW!r} annotation in the trace")


def host_events(pd, lo, hi):
    """Host events overlapping [lo, hi): (name, start, end, depth-ish)."""
    out = []
    for p in pd.planes:
        if not p.name.startswith("/host:"):
            continue
        for ln in p.lines:
            for name, s, e in _events(ln):
                if name != WINDOW and s < hi and e > lo:
                    out.append((name, s, e))
    return out


def reduce(path, chips: int, top: int = 10) -> dict:
    """The reduction of one traced run; times in seconds."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    lo, hi = window_of(pd)
    busy, op_time, coll = [], defaultdict(float), []
    module_time, gaps0 = defaultdict(float), []
    for i, plane in enumerate(device_planes(pd, chips)):
        mods = _line(plane, "XLA Modules")
        if mods is not None:
            for name, s, e in _events(mods):
                s, e = max(s, lo), min(e, hi)
                if e > s:
                    module_time[name] += (e - s) / 1e9 / chips
        ops = _line(plane, "XLA Ops")
        iv = []
        if ops is not None:
            for name, s, e in _events(ops):
                s, e = max(s, lo), min(e, hi)
                if e <= s:
                    continue
                iv.append((s, e))
                op_time[name] += (e - s) / 1e9 / chips
                if i == 0 and any(c in name for c in COLLECTIVES):
                    coll.append((name, (e - s) / 1e9))
        merged = _union(iv)
        busy.append(sum(e - s for s, e in merged) / 1e9)
        if i == 0:
            edges = [lo] + [x for se in merged for x in se] + [hi]
            gaps0 = [(edges[k], edges[k + 1])
                     for k in range(0, len(edges), 2)
                     if edges[k + 1] > edges[k]]
    if not busy:
        raise ValueError("no TPU device plane in the trace")
    gaps0.sort(key=lambda g: g[0] - g[1])
    hosts = host_events(pd, lo, hi)
    gaps = []
    for s, e in gaps0[:top]:
        # the innermost host event that covers most of the gap names it
        best, share, blen = "host idle", 0, 0
        for name, hs, he in hosts:
            ov = min(e, he) - max(s, hs)
            if ov > share or (ov > 0 and ov == share and he - hs < blen):
                best, share, blen = name, ov, he - hs
        gaps.append([best, (e - s) / 1e9])
    ops_sorted = sorted(op_time.items(), key=lambda kv: -kv[1])
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy) / len(busy),
        "op_time": dict(op_time),
        "module_time": dict(module_time),
        "device_ops": [[k, v] for k, v in ops_sorted[:top]],
        "idle_gaps": gaps,
        "collective_s": sum(d for _, d in coll),
        "collectives": len(coll),
    }


def describe(path, limit: int = 40) -> dict:
    """The trace's planes, lines and sample event names, to look at by
    hand before writing a reader against a new kind of trace."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = []
    for p in pd.planes:
        lines = []
        for ln in p.lines:
            names = defaultdict(int)
            n = 0
            for e in ln.events:
                n += 1
                names[e.name] += 1
            top = sorted(names.items(), key=lambda kv: -kv[1])[:limit]
            lines.append({"line": ln.name, "events": n, "names": top})
        out.append({"plane": p.name, "lines": lines})
    return {"planes": out}
