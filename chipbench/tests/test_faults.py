"""A run with the timed path broken underneath must come out incorrect.

Each cell runs through the whole harness on the CPU at a small stream
count (the look for a chip skipped), once sound and once with each fault
the cell can have: a tick that returns the fleet state unchanged; half
of each user's rows in every slab left out; an answer altered where it
is produced (each query answered with the next user's sketch).  A
one-chip cell has no exchange between chips to leave out."""

import time

import numpy as np
import pytest

import harness

# a stream count the CPU holds; the telemetry windows are filled as on
# the chip, and the window is kept short of a whole window of ticks
CHECK = {"users": 8, "cohorts": 2, "cohort_size": 8}
SIZES = {
    "telemetry-uniform-sat": {"system": {"streams": 16}, "fill_ticks": 128,
                              "check": CHECK},
    "synthetic-d300-sat": {"system": {"streams": 8},
                           "check": {"users": 8}},
}


def unchanged_state(eng):
    eng.fleet = eng.fleet._replace(update_block=lambda state, rows, ts: state)


def half_the_rows(eng):
    update = eng.fleet.update_block

    def half(state, rows, ts):
        keep = np.ones((1, rows.shape[1], 1), np.float32)
        keep[:, rows.shape[1] // 2:] = 0.0
        return update(state, rows * keep, ts)

    eng.fleet = eng.fleet._replace(update_block=half)


def altered_answer(eng):
    S, user, cohort = eng.S, eng.query_user, eng.query_cohort
    eng.query_user = lambda u: user((u + 1) % S)
    eng.query_cohort = lambda us: cohort([(u + 1) % S for u in us])


def run(cell, fault=None, seconds=1.0):
    return harness.run_cell(cell, 2**32 + 17, seconds, False,
                            t_process=time.perf_counter(), allow_cpu=True,
                            overrides={"config": SIZES[cell]}, fault=fault,
                            log=lambda s: None)


@pytest.mark.parametrize("cell", sorted(SIZES))
def test_sound_run_is_correct(cell):
    out = run(cell)
    assert out["correct"], out["checks"]


@pytest.mark.parametrize("fault", [unchanged_state, half_the_rows,
                                   altered_answer])
@pytest.mark.parametrize("cell", sorted(SIZES))
def test_fault_is_caught(cell, fault):
    out = run(cell, fault)
    assert not out["correct"], out["checks"]
