"""Span tracing with a JAX-aware timing discipline, exported as
Chrome-trace JSON (open in Perfetto / chrome://tracing).

JAX dispatch is asynchronous: the wall clock at the end of a ``with``
block measures enqueue time, not execution.  A :class:`Span` therefore
carries an optional *block target* — ``sp.block(x)`` arms the span so
its ``__exit__`` runs ``jax.block_until_ready(x)`` BEFORE taking the
end timestamp.  The span's duration then covers dispatch + device
execution, the same discipline the benchmarks use (PR 4).  Compile
time is its own span: wrap the AOT ``jit().lower().compile()`` call in
``tracer.span(name, cat="compile")`` so steady-state spans stay clean.

Profiler clock: an enabled span also opens a
``jax.profiler.TraceAnnotation`` of its name and attributes, closed
after the ``block_until_ready``, so under ``jax.profiler.trace`` every
span lies on the trace's host plane on the same clock as the device's
ops.  ``Tracer.add_hlo_ops(compiled)`` writes the compiled program's
map from HLO instruction to ``op_name`` (the ``jax.named_scope`` path)
into the Chrome JSON as one ``hlo_ops`` metadata event, so that a
device op in that trace can be put down to the scope that produced it.

A disabled tracer hands out a shared no-op span — zero allocations,
no timestamps, no ``block_until_ready``, no annotation — so
un-instrumented runs are byte-for-byte the old code path.

Chrome-trace mapping: every span is one complete event (``"ph": "X"``)
with microsecond ``ts``/``dur`` relative to tracer construction;
nesting is by containment on the same ``(pid, tid)`` track, and the
span's nesting depth is also recorded in ``args.depth``.
"""
from __future__ import annotations

import json
import os
import re
import threading
import time
from typing import Any, List, Optional


class _NullSpan:
    """The disabled-tracer span: every method is a no-op."""
    __slots__ = ()
    dur_s = 0.0

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def block(self, x) -> None:
        pass

    def set(self, **attrs) -> None:
        pass


NULL_SPAN = _NullSpan()


class Span:
    __slots__ = ("tracer", "name", "cat", "attrs", "t_start", "t_end",
                 "depth", "tid", "_block", "_annotation")

    def __init__(self, tracer: "Tracer", name: str, cat: str, attrs: dict):
        self.tracer = tracer
        self.name = name
        self.cat = cat
        self.attrs = attrs
        self.t_start = self.t_end = 0.0
        self.depth = 0
        self.tid = 0
        self._block: Any = None
        self._annotation: Any = None

    def block(self, x) -> None:
        """Arm the span: ``__exit__`` blocks until ``x`` (any jax
        array/pytree) is ready before recording the end timestamp."""
        self._block = x

    def set(self, **attrs) -> None:
        self.attrs.update(attrs)

    def __enter__(self):
        from jax.profiler import TraceAnnotation
        self.depth, self.tid = self.tracer._push()
        self._annotation = TraceAnnotation(self.name, **self.attrs)
        self._annotation.__enter__()
        self.t_start = time.perf_counter()
        return self

    def __exit__(self, *exc):
        if self._block is not None:
            import jax
            jax.block_until_ready(self._block)
            self._block = None
        self.t_end = time.perf_counter()
        self._annotation.__exit__(*exc)
        self._annotation = None
        self.tracer._pop()
        self.tracer._record(self)
        return False

    @property
    def dur_s(self) -> float:
        return self.t_end - self.t_start


class Tracer:
    def __init__(self, enabled: bool = False, collect: bool = True,
                 pid: int = 0, process_name: Optional[str] = None):
        """``enabled=False``: span() returns the shared no-op span.
        ``collect=False``: spans time themselves (``dur_s`` usable for
        histograms) but no events are retained — for metrics-only runs
        that should not grow a trace buffer."""
        self.enabled = enabled
        self.collect = collect
        self.pid = pid
        self.process_name = process_name
        self.events: List[dict] = []
        self.t0 = time.perf_counter()
        self._tls = threading.local()
        self._tids: dict = {}
        self._hlo_mapped: dict = {}     # id -> program already mapped

    # -- span lifecycle ------------------------------------------------
    def span(self, name: str, cat: str = "", **attrs):
        if not self.enabled:
            return NULL_SPAN
        return Span(self, name, cat, attrs)

    def _push(self):
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        ident = threading.get_ident()
        tid = self._tids.setdefault(ident, len(self._tids))
        depth = len(stack)
        stack.append(depth)
        return depth, tid

    def _pop(self):
        self._tls.stack.pop()

    def _record(self, span: Span) -> None:
        if not self.collect:
            return
        self.events.append({
            "name": span.name,
            "cat": span.cat or "span",
            "ph": "X",
            "ts": round((span.t_start - self.t0) * 1e6, 3),
            "dur": round((span.t_end - span.t_start) * 1e6, 3),
            "pid": self.pid,
            "tid": span.tid,
            "args": dict(span.attrs, depth=span.depth),
        })

    def add_hlo_ops(self, program) -> None:
        """Record one ``hlo_ops`` metadata event for a compiled program
        (anything with ``as_text``): ``{hlo_module, ops: {instruction:
        op_name}}``, parsed from its optimized HLO.  Once per program,
        and only where the tracer collects events."""
        if (not (self.enabled and self.collect)
                or not hasattr(program, "as_text")
                or id(program) in self._hlo_mapped):
            return
        self._hlo_mapped[id(program)] = program
        module, ops = hlo_op_names(program.as_text())
        self.events.append({"name": "hlo_ops", "ph": "M", "pid": self.pid,
                            "tid": 0,
                            "args": {"hlo_module": module, "ops": ops}})

    # -- export --------------------------------------------------------
    def to_chrome(self) -> dict:
        meta = []
        if self.process_name is not None:
            meta.append({"name": "process_name", "ph": "M",
                         "pid": self.pid, "tid": 0,
                         "args": {"name": self.process_name}})
        return {"traceEvents": meta + list(self.events),
                "displayTimeUnit": "ms"}

    def save(self, path: str) -> None:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(path, "w") as f:
            json.dump(self.to_chrome(), f)
            f.write("\n")


_MODULE = re.compile(r"^HloModule ([^\s,]+)")
_INSTRUCTION = re.compile(
    r'^\s+(?:ROOT )?%?([^\s=]+) = .*metadata=\{[^}]*?op_name="([^"]*)"')


def hlo_op_names(text: str):
    """(module name, {instruction: op_name}) of an optimized HLO module's
    text.  Instruction names are unique in a module, so the map holds the
    instructions of every computation: a device op event names one."""
    m = _MODULE.search(text)
    ops = {}
    for line in text.splitlines():
        op = _INSTRUCTION.match(line)
        if op:
            ops[op.group(1)] = op.group(2)
    return (m.group(1) if m else ""), ops
