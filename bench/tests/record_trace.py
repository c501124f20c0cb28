#!/usr/bin/env python3
"""Record the small profiler trace that ``test_trace.py`` reduces: five
calls of a jitted matmul chain inside the harness's window annotation,
with a 20 ms sleep on the host after each, so that the trace holds known
idle gaps.  Run on a TPU; writes ``data/small.xplane.pb`` beside this
file and prints what the reduction reads from it.

    python3 bench/tests/record_trace.py
"""
from __future__ import annotations

import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import harness  # noqa: E402
from bench import trace as bench_trace  # noqa: E402

OUT = Path(__file__).resolve().parent / "data" / "small.xplane.pb"
CALLS, SLEEP_S = 5, 0.02


def main():
    import jax
    import jax.numpy as jnp
    harness.require_chips(1)

    @jax.jit
    def chain(a):
        for _ in range(8):
            a = jnp.tanh(a @ a)
        return a

    a = jnp.ones((2048, 2048), jnp.float32) / 2048
    chain(a).block_until_ready()
    tmp = tempfile.mkdtemp()
    try:
        with jax.profiler.trace(tmp), \
                jax.profiler.TraceAnnotation(bench_trace.WINDOW):
            for _ in range(CALLS):
                a = chain(a)
                a.block_until_ready()
                with jax.profiler.TraceAnnotation("host_sleep"):
                    time.sleep(SLEEP_S)
        OUT.parent.mkdir(parents=True, exist_ok=True)
        shutil.copy(bench_trace.find_xplane(tmp), OUT)
    finally:
        shutil.rmtree(tmp)
    red = bench_trace.reduce(str(OUT), 1)
    red.pop("op_time")
    print(json.dumps(dict(red, bytes=OUT.stat().st_size)))


if __name__ == "__main__":
    main()
