"""The ingest pipeline's host time in a tick: ``engine.next_slab`` (take
the tick's slab) plus ``engine.prefetch`` (pack and place the next one),
mean over the window's ticks, from the engine's tick record."""

import numpy as np

import _engine


def read(run):
    rec = _engine.window_records(run)
    if rec is None:
        return None
    s = _engine.phase_s(rec, "next_slab") + _engine.phase_s(rec, "prefetch")
    return float(np.mean(s) * 1e3)
