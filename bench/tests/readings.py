#!/usr/bin/env python3
"""The readings a cell's limits are set from, at the cell's own size, in one
process: the program's numbers on many seeds, the lower-precision control
on a few, and each planted fault on a few.  Prints one JSON line per run.

    python3 bench/tests/readings.py --workload train-mamba2-s12 \\
        --seeds 11,12,13 --control-seeds 21,22,23 --fault-seeds 31,32,33

The control is the program's own ``--precision bf16`` path (the inner
iterate, activations and gradients in bfloat16), run on the control
seeds.  The faults are planted in this process only (see ``faults.py``).
No measured window is run: the readings are of the cell's first rounds.

``--layout replicas:N,mesh:SPEC`` reads the cell's traffic with
``--replicas N --mesh SPEC`` in its place, a layout that is no cell of
its own (on a four-chip cell: ``replicas:2,mesh:replica:2,data:2``).
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import harness  # noqa: E402
from bench.tests import faults  # noqa: E402


def seeds(s):
    return [int(x) for x in s.split(",") if x]


def relayout(spec, layout: str):
    """The cell's traffic with ``--replicas`` and ``--mesh`` from
    ``replicas:N,mesh:SPEC``."""
    n, _, mesh = layout.removeprefix("replicas:").partition(",mesh:")
    flags = spec["traffic"]["flags"]
    for k, v in (("--replicas", n), ("--mesh", mesh)):
        flags[flags.index(k) + 1] = v
    spec["traffic"]["replicas"] = int(n)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--faults", default="")
    ap.add_argument("--layout", default="")
    a = ap.parse_args(argv)
    from bench.run import run_cell
    spec = harness.cell_spec(a.workload)
    if a.layout:
        relayout(spec, a.layout)
    harness.setup_jax()
    devices = harness.require_chips(spec["workload"]["chips"])

    def one(tag, seed, **kw):
        try:
            out, checks, info = run_cell(a.workload, seed, 0.0, False,
                                         devices=devices, spec=spec, **kw)
            rec = {"run": tag, "seed": seed, "correct": out["correct"],
                   "readings": info["readings"],
                   "worst": [info.get("worst_grad_leaf"),
                             info.get("worst_change_leaf")],
                   "left_out": info.get("leaves_left_out"),
                   "reference_s": info.get("reference_s"),
                   "peak": out["device"]["memory_peak_bytes"]}
        except Exception as e:            # a control that crashes has failed
            rec = {"run": tag, "seed": seed, "error": repr(e)[:500]}
        print(json.dumps(rec), flush=True)

    for s in seeds(a.seeds):
        one("program", s)
    for s in seeds(a.control_seeds):
        one("control_bf16", s, extra_flags=("--precision", "bf16"))
    for name in [f for f in a.faults.split(",") if f]:
        for s in seeds(a.fault_seeds):
            with faults.planted(name):
                one(f"fault_{name}", s)


if __name__ == "__main__":
    main()
