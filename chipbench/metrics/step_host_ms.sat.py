"""The host's own time in a tick: ``engine.step`` less ``engine.wait``
(the wait for the previous tick's state), mean over the window's ticks,
from the engine's tick record."""

import numpy as np

import _engine


def read(run):
    rec = _engine.window_records(run)
    if rec is None:
        return None
    own = rec["end"] - rec["start"] - _engine.phase_s(rec, "wait")
    return float(np.mean(own) * 1e3)
