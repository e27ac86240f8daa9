"""Useful rows in the slab: rows taken ÷ (streams × block), mean over
the window's ticks, from the engine's per-tick row count."""

import numpy as np

import _engine


def read(run):
    rec = _engine.window_records(run)
    if rec is None:
        return None
    return float(np.mean(rec["occupancy"]) * 100.0)
