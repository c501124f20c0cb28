"""bench/scopes.py: self time of nested events, the scope of an op under
JAX's transformations, and the reduction of a small scoped program
recorded on a TPU v5e by ``record_scopes.py``."""
import json
from pathlib import Path

import pytest

from bench import scopes

DATA = Path(__file__).resolve().parent / "data"


def _self(events):
    return dict(zip((n for n, _, _ in events), scopes.self_times(events)))


def test_self_time_of_a_container_with_two_children():
    events = [("while.1", 0, 100), ("fusion.2", 10, 40),
              ("custom-call.3", 50, 90), ("copy.4", 100, 120)]
    got = _self(events)
    assert got == {"while.1": 30, "fusion.2": 30, "custom-call.3": 40,
                   "copy.4": 20}
    # the self times of a line add up to its busy time
    assert sum(got.values()) == 120


def test_self_time_of_deeper_nesting_and_order():
    events = [("inner", 20, 30), ("outer", 0, 100), ("mid", 10, 60),
              ("late", 70, 80)]
    assert _self(events) == {"outer": 40, "mid": 40, "inner": 10,
                             "late": 10}


@pytest.mark.parametrize("op_name,scope", [
    ("jit(round_fn)/while/body/closed_call/parle_inner/mul", "parle_inner"),
    ("jit(round_fn)/while/body/vmap(transpose(jvp(model)))/in_proj/"
     "dot_general", "in_proj"),
    ("jit(round_fn)/while/body/vmap(jvp(model))/while/body/ssd/exp", "ssd"),
    ("jit(round_fn)/while/body/vmap(transpose(jvp(model)))/add_any",
     "model"),
    ("jit(round_fn)/parle_sync/parle_sync/sub", "parle_sync"),
    ("jit(f)/while/body/vmap(transpose(jvp(model)))/mul;"
     "vmap(transpose(jvp(parle_inner)))/broadcast_in_dim", "model"),
    ("jit(round_fn)/while/body/dynamic_slice", scopes.UNSCOPED),
    ("", scopes.UNSCOPED),
])
def test_scope_is_the_innermost_under_transformations(op_name, scope):
    assert scopes.scope_of(op_name) == scope


def test_op_scope_from_the_spans_map_or_the_module(tmp_path):
    spans = tmp_path / "spans.json"
    spans.write_text(json.dumps({"traceEvents": [
        {"name": "round", "ph": "X", "ts": 0, "dur": 1},
        {"name": "hlo_ops", "ph": "M", "pid": 0, "tid": 0,
         "args": {"hlo_module": "jit_round_fn",
                  "ops": {"fusion.7": "jit(round_fn)/while/body/"
                          "vmap(jvp(model))/ssd/mul"}}}]}))
    ops = scopes.hlo_op_map(str(spans))
    # a TPU op event is named by its instruction's text
    assert scopes._op_scope("%fusion.7 = f32[2]{0} fusion(f32[2]{0} %p)",
                            "jit_round_fn", ops) == "ssd"
    # an instruction XLA made itself has no op_name, so no scope
    assert scopes._op_scope("%copy.3 = f32[2]{0} copy(f32[2]{0} %p)",
                            "jit_round_fn", ops) == scopes.UNSCOPED
    # a module the spans do not map takes the module's name
    assert scopes._op_scope("%fusion = s32[4]{0} fusion()", "jit_stage",
                            ops) == "jit_stage"


def test_module_of_an_op_is_the_module_event_that_holds_it():
    modules = ([0, 100], [(0, 50, "jit_stage"), (100, 900, "jit_round_fn")])
    assert scopes._module_of(10, modules) == "jit_stage"
    assert scopes._module_of(120, modules) == "jit_round_fn"
    assert scopes._module_of(60, modules) == ""


@pytest.fixture(scope="module")
def red():
    xplane, spans = DATA / "scopes.xplane.pb", DATA / "scopes.spans.json"
    if not xplane.exists():
        pytest.skip("no trace recorded yet: run record_scopes.py on a TPU")
    return scopes.reduce(str(xplane), str(spans), 1)


def test_recorded_scopes_add_up_to_busy_time(red):
    assert red["busy_s"] > 0
    total = sum(red["scope_s"].values())
    assert abs(total - red["busy_s"]) <= 1e-6 * red["busy_s"] + 1e-9


def test_recorded_program_time_lies_in_its_scopes(red):
    from jax.profiler import ProfileData

    from bench import trace
    s = red["scope_s"]
    assert s.get("model", 0) > 0 and s.get("parle_inner", 0) > 0
    # the Pallas call is its own op, named by its ``name=``, in its scope
    xplane, spans = DATA / "scopes.xplane.pb", DATA / "scopes.spans.json"
    ops = scopes.hlo_op_map(str(spans))
    plane = trace.device_planes(ProfileData.from_file(str(xplane)), 1)[0]
    modules = scopes._modules(plane)
    kernel = [scopes._op_scope(e.name, scopes._module_of(e.start_ns,
                                                         modules), ops)
              for e in trace._line(plane, "XLA Ops").events
              if e.name.startswith("%scaled_add")]
    assert kernel and set(kernel) == {"parle_inner"}
