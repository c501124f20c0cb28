"""The comparison that decides ``correct``, at a size a CPU test run holds:
a sound run passes, and the lower-precision control and every fault a
training cell can have fail it.  Each run skips the look for a chip and
drives the rest of a run of the cell as ``BENCHMARK.json`` lists it
(set-up, the first rounds, the reference), shrunk to a CPU size, with the
timed path intact, switched to lower precision, or broken underneath
(bench/tests/faults.py).

The limits here are for this size, read on the CPU: above every sound
reading, below every control and fault reading.  A cell's own limits, in
``bench/limits/``, are read on the chip at the cell's size (PERF.md).
"""
import copy

import jax
import pytest

from bench import harness
from bench.run import run_cell
from bench.tests import faults
from bench.tests.readings import relayout

TRAIN_LIMITS = {"loss_gap": 1e-4, "grad_gap": 1e-3, "change_gap": 1e-3}
CELLS = {w["name"]: w for w in harness.load_json(
    harness.ROOT / "BENCHMARK.json")["workloads"]}
# layouts that are no cell of their own: a cell's traffic with other flags.
# replica:2,data:2 is the in-replica (FSDP) mesh that a configuration too
# large for one chip's replicas takes
LAYOUTS = {"train-mamba2-s12-replica4.r2d2": (
    "train-mamba2-s12-replica4", "replicas:2,mesh:replica:2,data:2")}


def tiny_train(cell):
    """The cell's (or layout's) spec, its model shrunk to its architecture
    module's ``CPU_SIZE`` and its traffic to a few short rows."""
    if cell in LAYOUTS:
        base, layout = LAYOUTS[cell]
        spec = tiny_train(base)
        spec["workload"] = dict(spec["workload"], name=cell)
        relayout(spec, layout)
        return spec
    spec = copy.deepcopy(harness.cell_spec(cell))
    conf = spec["config"]
    small = harness.architecture(conf).CPU_SIZE
    conf["model"].update(small)
    conf["reduced"] = sorted(set(conf["reduced"]) | set(small))
    t = spec["traffic"]
    t.update(L=3, batch=2, seq=32)
    fl = t["flags"]
    for k, v in (("--L", "3"), ("--batch", "2"), ("--seq", "32")):
        fl[fl.index(k) + 1] = v
    spec["limits"] = {k: {"limit": v} for k, v in TRAIN_LIMITS.items()}
    return spec


def run(spec, seed=2**31 + 17, **kw):
    chips = spec["workload"]["chips"]
    out, checks, info = run_cell(spec["workload"]["name"], seed, 0.0,
                                 False, devices=jax.devices()[:chips],
                                 spec=spec, **kw)
    return out["correct"], info["readings"]


@pytest.fixture(scope="module", params=[*CELLS, *LAYOUTS])
def train_spec(request):
    return tiny_train(request.param)


def chips_of(cell):
    return CELLS[LAYOUTS.get(cell, (cell,))[0]]["chips"]


def test_sound_run_is_correct(train_spec):
    ok, readings = run(train_spec)
    assert ok, readings


def test_control_bf16_is_not_correct(train_spec):
    ok, readings = run(train_spec, extra_flags=("--precision", "bf16"))
    assert not ok, readings


@pytest.mark.parametrize("cell,fault", [
    (c, f) for c in [*CELLS, *LAYOUTS]
    for f in faults.for_chips(chips_of(c))],
    ids=lambda v: v)
def test_fault_is_not_correct(cell, fault):
    with faults.planted(fault):
        ok, readings = run(tiny_train(cell))
    assert not ok, readings


def test_late_reader_reads_each_round_lag_rounds_late():
    import jax.numpy as jnp
    from bench.kinds.train import _LateReader
    late = _LateReader(lag=2)
    for r in range(5):
        late(r, 3 * (r + 1), {"losses": jnp.full((3,), float(r))})
        assert len(late.losses) == max(0, r + 1 - 2)
    late.drain()
    assert late.losses == [[float(r)] * 3 for r in range(5)]
    assert len(late.read_at) == 5


def test_window_counts_every_round_it_ran(train_spec):
    chips = train_spec["workload"]["chips"]
    out, _, info = run_cell(train_spec["workload"]["name"], 2**31 + 29, 0.5,
                            False, devices=jax.devices()[:chips],
                            spec=train_spec)
    assert out["correct"] and out["failed"] == 0
    assert out["attempted"] == info["rounds"] >= 1
    assert len(info["window_read_s"]) == info["rounds"]
    assert info["compiles_in_window"] == 0
