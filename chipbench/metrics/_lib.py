"""Helpers the per-layer readers share.  Each reader is
``metrics/<metric>.py`` with ``read(run) -> float | None``; ``run`` holds
the window's host spans ``(name, start, end, n)``, its ticks (dispatch,
return and ready times, rows), the cell's configuration and the reduced
device trace (``devtrace.reduce``) of the ticks traced after the window.
A reader that finds nothing to read returns ``None``."""

from __future__ import annotations

import re

import numpy as np

# the fleet update is the jitted shard_map of the vmapped block update;
# its program is named after that function
UPDATE_PROGRAM = re.compile(r"update_block")


def window_ticks(run):
    return [t for t in run.ticks if t["phase"] == "window"
            and np.isfinite(t["ready"])]


def tick_ms(run):
    ticks = window_ticks(run)
    if not ticks:
        return None
    return float(np.mean([t["ready"] - t["dispatch"] for t in ticks]) * 1e3)


def trace_ticks(run):
    return [t for t in run.ticks if t["phase"] == "trace"]


def update_device_s(run):
    """Mean device time of the update program's executions that lie
    wholly inside the traced window: one per tick."""
    if run.trace is None:
        return None
    d = [x for name, xs in run.trace["programs_s"].items()
         if UPDATE_PROGRAM.search(name) for x in xs]
    return float(np.mean(d)) if d else None


def idle_pct(run):
    if (run.trace is None or run.trace["devices"] == 0
            or run.trace["window_s"] <= 0):
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])
