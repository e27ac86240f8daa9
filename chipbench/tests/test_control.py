"""The control comes out as not correct; the program, on the same run,
as correct.

The control is the configuration's plain reference with one stated
guarantee broken, put in the program's place (``configs/<config>.py``):
for telemetry-d64 the window (no row ever expires), for synthetic-d300
epsilon (the reference at ell / 2).  On the chip it was read at each
cell's own size (``chipbench/control.py``); here the same comparison
runs on the CPU at a small stream count with the windows filled."""

import time

import pytest

import harness
from test_faults import SIZES


# the control's stale rows show after some tens of ticks past the fill,
# as in a chip run's window
SECONDS = 3.0


@pytest.mark.parametrize("cell", sorted(SIZES))
def test_control_fails_and_program_passes(cell):
    out = harness.run_cell(cell, 2**40 + 3, SECONDS, False,
                           t_process=time.perf_counter(), allow_cpu=True,
                           overrides={"config": SIZES[cell]}, control=True,
                           log=lambda s: None)
    assert out["correct"], out["checks"]
    over = [k for k, v in out["control"].items()
            if v > out["checks"][k]["limit"]]
    assert over, (out["control"], out["checks"])
