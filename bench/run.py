#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

The cell is a ``workloads`` entry of ``BENCHMARK.json``; its
configuration (with the architecture module it names), traffic mix,
limits and per-layer metrics are files under ``bench/`` found by name.
``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1``
records a profiler trace of the window and reports its per-layer
metrics.  Needs a TPU with as many chips as the cell asks
for; on anything else it exits non-zero without a result.  Longer output
(set-up split, readings, trace) goes to ``results/bench/``.
"""
from __future__ import annotations

import argparse
import importlib
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import harness  # noqa: E402


class Ctx:
    """What a kind's ``run`` gets: the cell and the run's arguments."""

    def __init__(self, spec, seed, seconds, trace, devices, counter, t_jax,
                 extra_flags=()):
        self.spec = spec
        self.seed = seed
        self.seconds = seconds
        self.trace = trace
        self.devices = devices
        self.chips = len(devices)
        self.counter = counter
        self.t_jax = t_jax
        self.extra_flags = extra_flags
        self.out = harness.out_dir(spec["workload"]["name"], seed, trace)


def run_cell(name, seed, seconds, trace, devices=None, spec=None,
             extra_flags=()):
    """One run of a cell: returns (result line, checks, info).  ``devices``
    given skips the look for a chip (the tests' CPU runs); ``extra_flags``
    go to the cell's trainer after its own (the control's
    ``--precision bf16``)."""
    spec = spec or harness.cell_spec(name)
    harness.setup_jax()
    counter = harness.CompileCounter()
    if devices is None:
        devices = harness.require_chips(spec["workload"]["chips"])
    ctx = Ctx(spec, seed, seconds, trace, devices, counter, harness.age(),
              extra_flags)
    kind = importlib.import_module(f"bench.kinds.{spec['traffic']['kind']}")
    res = kind.run(ctx)
    ok, checks = harness.judge(res["readings"],
                               {k: v["limit"] for k, v in
                                spec["limits"].items()})
    device = harness.device_info(devices, res["peak_bytes"])
    out = {"correct": ok, "attempted": res["attempted"],
           "failed": res["failed"], "device": device}
    info = dict(res["info"], readings=res["readings"])
    if trace:
        from bench import trace as bench_trace
        art = dict(res["art"])
        art["trace"] = bench_trace.reduce(
            bench_trace.find_xplane(art["xprof"]), len(devices))
        out["metrics"] = harness.read_per_layer(spec["per_layer"], art)
        device["busy_s"] = art["trace"]["busy_s"]
        device["window_s"] = art["trace"]["window_s"]
        out["breakdown"] = {"device_ops": art["trace"]["device_ops"],
                            "idle_gaps": art["trace"]["idle_gaps"]}
        info["trace"] = {k: v for k, v in art["trace"].items()
                         if k != "op_time"}
    else:
        out["metrics"] = {m["name"]: {"value": res["e2e"][m["name"]],
                                      "unit": m["unit"]}
                          for m in spec["end_to_end"]}
    with open(ctx.out / "info.json", "w") as f:
        json.dump(info, f, indent=1, default=str)
    return out, checks, info


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    out, checks, info = run_cell(a.workload, a.seed, a.seconds, bool(a.trace))
    print(json.dumps({"setup": info["setup"],
                      "compiles_in_window": info["compiles_in_window"]}),
          flush=True)
    harness.emit_result(out, checks)


if __name__ == "__main__":
    main()
