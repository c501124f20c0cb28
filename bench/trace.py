"""Reduce a profiler trace (``.xplane.pb``) to what the metrics read: the
window, the device's busy and idle time, per-op and per-program device
time, collective time and the part of it exposed, and the longest idle
gaps with what the host was doing in each.

The harness opens a host annotation named ``WINDOW`` around the traced
window, so the window and the device's ops are read on one clock.
Busy time is the union of the intervals of the ops on a chip's
``XLA Ops`` line, clipped to the window, averaged over the chips used.

A collective is an op whose opcode is one of ``COLLECTIVES`` (on a TPU
an ``XLA Ops`` event is named by its instruction's text, ``%psum.109 =
f32[...] all-reduce(...)``, whose name need not say it); an
asynchronous one (``<collective>-start`` ... ``<collective>-done``) is in
flight from its start op's beginning to its done op's end.  Collective
time is the union of the collectives' intervals; the exposed part is
what of it no other op covers on the same chip, where the other ops are
the line's innermost ops (a ``while`` or ``call`` that holds others is
not itself work).  Both are averaged over the chips used.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

WINDOW = "bench_window"
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")


_OPCODE = re.compile(r"\s(%s)(?:-(start|done))?\(" % "|".join(COLLECTIVES))
_INSTRUCTION = re.compile(r"^%%?(%s)(?:-(start|done))?(?:\.\d+)?$"
                          % "|".join(COLLECTIVES))


def collective_of(name: str):
    """(collective, phase) of an op event's name, phase ``start``,
    ``done`` or ``""`` (one synchronous op); None for any other op.  The
    name is the instruction's text (``%psum.109 = f32[8] all-reduce(...)``)
    or its bare name (``all-reduce-done.3``).  In the text the opcode is
    the word before the first ``(`` of a collective's name; an operand
    that names one (``%all-reduce-done.3``) is no call."""
    m = _OPCODE.search(name) or _INSTRUCTION.match(name)
    return (m.group(1), m.group(2) or "") if m else None


def collective_split(events):
    """(collective seconds, exposed seconds, collectives) of one chip's
    ``XLA Ops`` events [(name, start_ns, end_ns)], clipped to the window;
    see the module's docstring."""
    coll, other, open_starts = [], [], defaultdict(list)
    order = sorted(events, key=lambda ev: (ev[1], -ev[2]))
    for i, (name, s, e) in enumerate(order):
        kind = collective_of(name)
        if kind is None:
            holds = i + 1 < len(order) and order[i + 1][1] < e
            if not holds:
                other.append((s, e))
        elif kind[1] == "start":
            open_starts[kind[0]].append(s)
        elif kind[1] == "done" and open_starts[kind[0]]:
            coll.append((open_starts[kind[0]].pop(0), e))
        else:                  # synchronous, or a done whose start was
            coll.append((s, e))    # before the window
    for c, starts in open_starts.items():   # still in flight at its end
        coll += [(s, max(e for _, _, e in events)) for s in starts]
    coll_u = _union(coll)
    total = sum(e - s for s, e in coll_u)
    covered = _overlap(coll_u, _union(other))
    return total / 1e9, (total - covered) / 1e9, len(coll)


def _overlap(a, b):
    """Nanoseconds in both of two sorted, merged interval lists."""
    i = j = out = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        out += max(0, hi - lo)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


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
        iv, events = [], []
        if ops is not None:
            for name, s, e in _events(ops):
                s, e = max(s, lo), min(e, hi)
                if e <= s:
                    continue
                iv.append((s, e))
                events.append((name, s, e))
                op_time[name] += (e - s) / 1e9 / chips
        coll.append(collective_split(events))
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
        "collective_s": sum(c[0] for c in coll) / len(coll),
        "collective_exposed_s": sum(c[1] for c in coll) / len(coll),
        "collectives": sum(c[2] for c in coll),
        "collective_s_by_chip": [[c[0], c[1]] for c in coll],
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
