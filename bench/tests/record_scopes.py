#!/usr/bin/env python3
"""Record the small scoped program that ``test_scopes.py`` reduces: a
jitted ``lax.scan`` of 4 steps whose body takes the gradient of a
``model`` scope under ``vmap`` and applies it with one Pallas call
(``scaled_add``) inside a ``parle_inner`` scope, called 3 times inside
the harness's window annotation, each call under a ``round`` span of the
program's own tracer.  Run on a TPU; writes ``data/scopes.xplane.pb``
and the tracer's Chrome JSON ``data/scopes.spans.json`` beside this
file, and prints what the reduction reads from them.

    python3 bench/tests/record_scopes.py [--describe]

``--describe`` also prints the device plane's lines and, for each
distinct op of the ``XLA Ops`` line, its stats: what a reader of a new
kind of trace looks at first.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import harness  # noqa: E402
from bench import scopes as bench_scopes  # noqa: E402
from bench import trace as bench_trace  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
OUT = DATA / "scopes.xplane.pb"
SPANS = DATA / "scopes.spans.json"
CALLS, STEPS, N, ROWS, COLS = 3, 4, 2, 512, 1024


def program(interpret: bool):
    """The jitted scan: (w (N, ROWS, COLS), xs (STEPS, N, 64, ROWS))."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def kernel(w_ref, g_ref, o_ref):
        o_ref[...] = w_ref[...] - 0.01 * g_ref[...]

    def scaled_add(w, g):
        spec = pl.BlockSpec((1, 256, COLS), lambda a, i: (a, i, 0))
        return pl.pallas_call(
            kernel, grid=(N, ROWS // 256), in_specs=[spec, spec],
            out_specs=spec, out_shape=jax.ShapeDtypeStruct(w.shape, w.dtype),
            name="scaled_add", interpret=interpret)(w, g)

    def loss(w, x):
        with jax.named_scope("model"):
            return jnp.mean(jnp.tanh(x @ w) ** 2)

    def body(w, x):
        g = jax.vmap(jax.grad(loss))(w, x)
        with jax.named_scope("parle_inner"):
            w = scaled_add(w, g)
        return w, jnp.sum(g)

    return jax.jit(lambda w, xs: jax.lax.scan(body, w, xs))


def describe(path):
    """The device plane's lines, and each distinct XLA op with its
    stats, as JSON lines."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    for p in pd.planes:
        if not p.name.startswith("/device:"):
            continue
        print(json.dumps({"plane": p.name,
                          "stats": [[k, str(v)] for k, v in p.stats]}))
        for ln in p.lines:
            seen = {}
            for e in ln.events:
                if e.name not in seen:
                    seen[e.name] = [[k, str(v)[:300]] for k, v in e.stats]
            print(json.dumps({"line": ln.name, "distinct": len(seen)}))
            if ln.name == "XLA Ops":
                for name, stats in seen.items():
                    print(json.dumps({"op": name[:300], "stats": stats}))


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--describe", action="store_true")
    a = ap.parse_args()
    import jax
    import jax.numpy as jnp
    harness.add_src()
    from repro.obs import Obs
    harness.require_chips(1)
    f = program(interpret=False)
    w = jnp.ones((N, ROWS, COLS), jnp.float32) / ROWS
    xs = jnp.ones((STEPS, N, 64, ROWS), jnp.float32)
    compiled = f.lower(w, xs).compile()
    jax.block_until_ready(compiled(w, xs))
    tmp = tempfile.mkdtemp()
    try:
        obs = Obs(trace_out=str(SPANS), process_name="record_scopes")
        with jax.profiler.trace(tmp), \
                jax.profiler.TraceAnnotation(bench_trace.WINDOW):
            for r in range(CALLS):
                with obs.span("round", round=r + 1) as sp:
                    w, _ = compiled(w, xs)
                    sp.block(w)
        obs.tracer.add_hlo_ops(compiled)
        obs.finalize()
        DATA.mkdir(parents=True, exist_ok=True)
        shutil.copy(bench_trace.find_xplane(tmp), OUT)
    finally:
        shutil.rmtree(tmp)
    if a.describe:
        describe(str(OUT))
    red = bench_scopes.reduce(str(OUT), str(SPANS), 1)
    print(json.dumps(dict(red, bytes=OUT.stat().st_size)))


if __name__ == "__main__":
    main()
