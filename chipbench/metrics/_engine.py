"""Helpers for the readers of what the engine records about itself: its
tick log (``repro.serve.trace``) and the named scopes of its update
program.  The log outlives the engine, so a reader finds the run's log as
the newest in the process.  A program without the log (before it was
added) gives ``None``, and so does every reader built on it."""

from __future__ import annotations

import numpy as np

import _lib
import scopes


def engine_log():
    try:
        from repro.serve import trace
    except ImportError:
        return None
    logs = trace.recent()
    return logs[-1] if logs else None


def window_records(run):
    """The engine's records of the window's ticks: for each harness tick
    of the window, the record whose ``engine.step`` began inside the
    harness's ``step()`` call."""
    log = engine_log()
    if log is None:
        return None
    rec = log.records()
    ticks = [t for t in run.ticks if t["phase"] == "window"]
    if not ticks or not rec.size:
        return None
    i = np.searchsorted(rec["start"], [t["dispatch"] for t in ticks])
    keep = [k for k, t in zip(i, ticks)
            if k < rec.size and rec["start"][k] <= t["ret"]]
    return rec[keep] if keep else None


def phase_s(rec, phase):
    """Each record's time in ``phase``; 0 where the tick did not run it."""
    d = rec[f"{phase}_end"] - rec[f"{phase}_start"]
    return np.where(np.isnan(d), 0.0, d)


def update_scopes_ms(run):
    """Device self time per named scope of the update program, in ms per
    traced tick (``scopes.per_tick_ms``), or ``None``."""
    log = engine_log()
    tick_s = _lib.update_device_s(run)
    if log is None or tick_s is None or log.update_hlo is None:
        return None
    out = scopes.per_tick_ms(run.trace, log.update_hlo(), tick_s)
    # an executable loaded from a compile cache written by a build
    # without the scopes keeps that build's metadata: nothing to read
    if set(out) <= {scopes.UNSCOPED}:
        return None
    return out
