"""The reduction from trace events to busy time, program time and gaps."""

import json
from pathlib import Path

import pytest

import devtrace

DATA = Path(__file__).resolve().parent / "data"
DEV, HOST = "/device:TPU:0", "/host:CPU"


def ev(plane, line, name, start, dur):
    return [plane, line, name, float(start), float(dur)]


def test_union_busy_gaps_by_hand():
    trace = {"events": [
        ev(HOST, "python", "chipbench.window", 0, 1000),
        ev(HOST, "python", "chipbench.step", 0, 100),
        ev(HOST, "python", "chipbench.query_user", 500, 300),
        # one program wholly inside, one overlapping it, one cut by the end
        ev(DEV, "XLA Modules", "jit_update_block(1)", 100, 300),
        ev(DEV, "XLA Modules", "jit_update_block(1)", 350, 50),
        ev(DEV, "XLA Modules", "jit_query(2)", 900, 200),
    ], "ops_ns": {DEV: {"%while.1": 250.0, "%fusion.2": 50.0}}}
    s = devtrace.reduce(trace)
    assert s["window_s"] == pytest.approx(1000e-9)
    # busy: [100, 400) and [900, 1000)
    assert s["busy_s"] == pytest.approx(400e-9)
    assert s["programs_s"] == {"jit_update_block(1)":
                               [pytest.approx(300e-9), pytest.approx(50e-9)]}
    # gaps: [0, 100) inside step, [400, 900) centred in query_user
    assert s["gaps"] == [["chipbench.query_user", pytest.approx(500e-9)],
                         ["chipbench.step", pytest.approx(100e-9)]]
    b = devtrace.breakdown(s)
    assert b["device_ops"] == [["%while.1", pytest.approx(250e-9)],
                               ["%fusion.2", pytest.approx(50e-9)]]


def test_recorded_v5e_trace():
    """Three traced ticks of telemetry-uniform-sat on one v5e: the first
    execution of the update program began before the traced window (the
    window's last tick, still in flight) and is not a whole one."""
    s = devtrace.reduce(json.loads((DATA / "trace_uniform_sat_v5e.json")
                                   .read_text()))
    assert s["devices"] == 1
    assert s["window_s"] == pytest.approx(1.744851854)
    ticks = s["programs_s"]["jit_update_block(4773819811050903051)"]
    assert ticks == [pytest.approx(0.449013677), pytest.approx(0.450453308),
                     pytest.approx(0.449074189)]
    idle = 1 - s["busy_s"] / s["window_s"]
    assert 0 < idle < 0.01
    assert s["gaps"][0][0] == "chipbench.wait"
    assert devtrace.breakdown(s)["device_ops"][0][0] == "%while.565"


def test_no_window_span_is_an_error():
    with pytest.raises(ValueError):
        devtrace.reduce({"events": [ev(DEV, "XLA Modules", "x", 0, 1)],
                         "ops_ns": {}})
