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
