"""Device self time per program scope, from a traced run's ``.xplane.pb``
and the program's own spans (``obs/trace.py``'s Chrome JSON).

The program names its layers with ``jax.named_scope`` (``core/parle.py``,
``models/``, ``data/synthetic.py``); XLA keeps each scope in the
``op_name`` metadata of the instructions it lowers to, under the
transformations that produced them (``vmap(transpose(jvp(model)))``), so
a layer's forward and backward carry the same scope.  An op's scope is
the innermost known scope on its path: the trainer's and Parle's
(``SCOPES``) and those the configuration's architecture module lists
inside ``model`` (its ``SCOPES``, bench/harness.py::architecture); where
no configuration is given, those of every configuration in
``BENCHMARK.json``.

Where the scope comes from: on a TPU v5e an ``XLA Ops`` event carries no
framework op name, only its instruction's text (``%fusion.7 = ...``) and
device offsets (looked at by hand in ``record_scopes.py --describe``), so
the program exports the map itself: the tracer writes a metadata event
(``"ph": "M"``, name ``hlo_ops``) into its Chrome JSON with, for each
compiled program it was handed, ``{hlo_module, ops: {instruction:
op_name}}`` (``Tracer.add_hlo_ops``).  An op's module is the ``XLA
Modules`` event it runs in.  Ops of a module the spans do not map take
the module's name as their scope (the round stager's program,
``jit_stage``, and the progress diagnostics'); instructions XLA made
itself (layout copies, async copies, casts) carry no ``op_name`` and so
no scope.

Self time: an event's duration, clipped to the harness's window, minus
what the events nested in it on the same ``XLA Ops`` line cover, so a
``while``/``call``/``conditional`` container counts only its own time and
the self times of a line add up to its busy time.  A fused op is one
event, named by its fusion instruction, whose metadata XLA takes from
the fusion's root.
"""
from __future__ import annotations

import bisect
import functools
import json
import re
from collections import defaultdict

from bench import harness
from bench import trace as bench_trace

# the trainer's and Parle's named scopes (core/parle.py, models/model.py)
SCOPES = ("model", "parle_inner", "parle_sync")
UNSCOPED = ""

_WRAPPED = re.compile(r"^[\w\-.]+\((.*)\)$")


def in_model(conf: dict) -> tuple:
    """The scopes of the model's loss for configuration ``conf``: ``model``
    and its architecture module's scopes inside it."""
    return ("model",) + tuple(harness.architecture(conf).SCOPES)


def of_config(conf: dict) -> tuple:
    """Every scope a run of configuration ``conf`` names."""
    return SCOPES + in_model(conf)[1:]


def of_benchmark() -> tuple:
    """Every scope a run of any configuration in ``BENCHMARK.json`` names."""
    spec = harness.load_json(harness.ROOT / "BENCHMARK.json")
    known = list(SCOPES)
    for c in spec["configs"]:
        conf = harness.load_json(harness.ROOT / c["file"])
        known += [s for s in in_model(conf) if s not in known]
    return tuple(known)


def scope_of(op_name: str, known=None) -> str:
    """The innermost of ``known`` (default ``of_benchmark()``) on an
    ``op_name`` path, each component unwrapped from its transformations;
    ``UNSCOPED`` where none is.  XLA joins the names of merged
    instructions with ``;``: the first is the whole path."""
    known = known or of_benchmark()
    found = UNSCOPED
    for part in op_name.split(";", 1)[0].split("/"):
        m = _WRAPPED.match(part)
        while m:
            part = m.group(1)
            m = _WRAPPED.match(part)
        if part in known:
            found = part
    return found


def hlo_op_map(spans_path) -> dict:
    """{hlo_module: {instruction: op_name}} from the spans' ``hlo_ops``
    metadata events; empty where there are none."""
    with open(spans_path) as f:
        events = json.load(f)["traceEvents"]
    out = {}
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "hlo_ops":
            out.setdefault(e["args"]["hlo_module"], {}).update(
                e["args"]["ops"])
    return out


def self_times(events):
    """[(name, start, end)] of one line, clipped -> each event's duration
    less what the events nested in it cover."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    cover = [0] * len(events)
    stack = []
    for i in order:
        _, s, e = events[i]
        while stack and events[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            cover[stack[-1]] += min(e, events[stack[-1]][2]) - s
        stack.append(i)
    return [(e - s) - c for (_, s, e), c in zip(events, cover)]


_LEADING = re.compile(r"^%?([^\s=(]+)")
_MODULE_ID = re.compile(r"\(\d+\)$")


def _op_scope(name, module, ops_by_module, known=None) -> str:
    """An op event's scope, from the program's map of its module where
    there is one, else its module's name."""
    ops = ops_by_module.get(module)
    if ops is None:
        return module or UNSCOPED
    op = _LEADING.match(name)
    return scope_of(ops.get(op.group(1), "") if op else "", known)


def _modules(plane):
    """The plane's ``XLA Modules`` events as sorted (starts, [(start, end,
    name)])."""
    line = bench_trace._line(plane, "XLA Modules")
    mods = sorted((e.start_ns, e.start_ns + e.duration_ns,
                   _MODULE_ID.sub("", e.name))
                  for e in (line.events if line is not None else ()))
    return [m[0] for m in mods], mods


def _module_of(start, modules) -> str:
    """The module whose event holds an op that starts at ``start``."""
    starts, mods = modules
    i = bisect.bisect_right(starts, start) - 1
    return mods[i][2] if i >= 0 and start < mods[i][1] else ""


@functools.lru_cache(maxsize=8)
def reduce(xplane_path: str, spans_path: str, chips: int,
           known: tuple | None = None) -> dict:
    """Seconds of device self time per scope of ``known`` (default
    ``of_benchmark()``) over the window, averaged over ``chips``:
    ``{"scope_s": {scope: s}, "busy_s": s, "window_s": s, "unscoped_ops":
    [[op, s], ...]}`` (the longest ten ops in no scope).  Cached per path
    and scopes: every reader of one run parses the trace once."""
    from jax.profiler import ProfileData
    known = known or of_benchmark()
    pd = ProfileData.from_file(xplane_path)
    lo, hi = bench_trace.window_of(pd)
    ops_by_module = hlo_op_map(spans_path)
    scope_s, unscoped, busy = defaultdict(float), defaultdict(float), 0.0
    for plane in bench_trace.device_planes(pd, chips):
        line = bench_trace._line(plane, "XLA Ops")
        if line is None:
            continue
        modules = _modules(plane)
        events = []
        for e in line.events:
            s, t = max(e.start_ns, lo), min(e.start_ns + e.duration_ns, hi)
            if t > s:
                events.append((e.name, s, t))
        iv = bench_trace._union([(s, t) for _, s, t in events])
        busy += sum(t - s for s, t in iv) / 1e9 / chips
        for (name, start, _), self_ns in zip(events, self_times(events)):
            sc = _op_scope(name, _module_of(start, modules), ops_by_module,
                           known)
            scope_s[sc] += self_ns / 1e9 / chips
            if sc == UNSCOPED:
                unscoped[name[:120]] += self_ns / 1e9 / chips
    top = sorted(unscoped.items(), key=lambda kv: -kv[1])[:10]
    return {"scope_s": dict(scope_s), "busy_s": busy,
            "window_s": (hi - lo) / 1e9,
            "unscoped_ops": [[k, v] for k, v in top]}


def of_run(art) -> dict | None:
    """The reduction of a training cell's traced run; None for another
    kind of cell."""
    if art.get("kind") != "train":
        return None
    path = bench_trace.find_xplane(art["xprof"])
    return reduce(path, str(art["spans"]), art["chips"],
                  of_config(art["config"]))


def per_step_ms(art, scopes) -> float | None:
    """Self time of ``scopes`` per inner step (rounds x L), in ms."""
    red = of_run(art)
    if red is None:
        return None
    t = sum(v for k, v in red["scope_s"].items() if k in scopes)
    return 1e3 * t / (art["rounds"] * art["args"].L)


def per_round_ms(art, scopes) -> float | None:
    """Self time of ``scopes`` per round, in ms."""
    red = of_run(art)
    if red is None:
        return None
    t = sum(v for k, v in red["scope_s"].items() if k in scopes)
    return 1e3 * t / art["rounds"]
