"""bench/trace.py on a small trace recorded on a TPU v5e by
``record_trace.py``: five calls of a jitted matmul chain inside the
harness's window annotation, each followed by a 20 ms host sleep."""
from pathlib import Path

import pytest

from bench import trace

DATA = Path(__file__).resolve().parent / "data" / "small.xplane.pb"


@pytest.fixture(scope="module")
def red():
    if not DATA.exists():
        pytest.skip("no trace recorded yet: run record_trace.py on a TPU")
    return trace.reduce(str(DATA), 1)


def test_window_and_busy_time(red):
    assert 0.1 < red["window_s"] < 5.0
    assert 0 < red["busy_s"] < red["window_s"]
    # five sleeps of 20 ms leave at least 0.1 s idle
    assert red["window_s"] - red["busy_s"] >= 0.1


def test_longest_gaps_are_the_host_sleeps(red):
    gaps = red["idle_gaps"]
    assert len(gaps) >= 5
    assert [g[0] for g in gaps[:5]] == ["host_sleep"] * 5
    assert all(0.02 <= g[1] < 0.1 for g in gaps[:5])
    assert gaps == sorted(gaps, key=lambda g: -g[1])


def test_op_times_add_up_to_no_more_than_busy(red):
    assert red["device_ops"] and red["op_time"]
    assert sum(red["op_time"].values()) >= red["busy_s"] * (1 - 1e-9)
    assert red["device_ops"][0][1] == max(red["op_time"].values())
    assert sum(red["module_time"].values()) <= red["window_s"]
    assert red["collectives"] == 0


def test_describe_lists_the_device_plane(red):
    planes = [p["plane"] for p in trace.describe(str(DATA))["planes"]]
    assert "/device:TPU:0" in planes


def test_collective_of_reads_the_opcode_not_the_name_or_operands():
    # as a TPU v5e names them (a traced train-mamba2-s12-replica4 run)
    assert trace.collective_of(
        "%psum.109 = f32[4,2048,8512]{1,2,0:T(8,128)} all-reduce(f32[4,2048"
        ",8512]{1,2,0:T(8,128)} %broadcast_multiply_fusion.6), channel_id=1"
    ) == ("all-reduce", "")
    assert trace.collective_of(
        "%all-reduce.15 = (f32[4190,2048]{1,0:T(8,128)}, f32[4,64]{1,0:T(4,"
        "128)}) all-reduce(f32[4190,2048]{1,0:T(8,128)} %fusion.3)") == (
        "all-reduce", "")
    assert trace.collective_of("%all-reduce-start.3 = f32[8] "
                               "all-reduce-start(%x)") == ("all-reduce",
                                                           "start")
    assert trace.collective_of("all-reduce-done.3") == ("all-reduce", "done")
    assert trace.collective_of("%fusion.12 = f32[8] fusion(f32[8] "
                               "%all-reduce-done.3)") is None
    assert trace.collective_of("%reduce_sum.444 = f32[2048] reduce("
                               "f32[2,2048] %copy-done.115)") is None


def test_collective_split_hides_what_other_ops_cover():
    ms = 1_000_000
    events = [
        ("while.1", 0, 100 * ms),                 # holds the ops below
        ("fusion.1", 0, 40 * ms),
        ("all-reduce-start.1", 40 * ms, 41 * ms),
        ("fusion.2", 41 * ms, 60 * ms),           # hides 19 ms of it
        ("all-reduce-done.1", 60 * ms, 70 * ms),  # 10 ms waited on
        ("%all-reduce.2 = f32[] all-reduce(%l)", 80 * ms, 85 * ms),
    ]
    total, exposed, n = trace.collective_split(events)
    assert n == 2
    assert abs(total - 0.035) < 1e-12       # 40..70 ms and 80..85 ms
    assert abs(exposed - 0.016) < 1e-12     # 40..41, 60..70, 80..85 ms
