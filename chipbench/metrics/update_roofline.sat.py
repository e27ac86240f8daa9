"""The fleet update's share of its roofline: the least time the chip
could take for a traced tick's rows and streams (``cost.least_seconds``)
over the update program's device time per tick."""

import _lib
import cost


def read(run):
    s = _lib.update_device_s(run)
    ticks = _lib.trace_ticks(run)
    if s is None or not ticks or run.peaks is None:
        return None
    rows = sum(t["rows"] for t in ticks) / len(ticks)
    least, _ = cost.least_seconds(run.system, rows, run.S, run.peaks)
    return 100.0 * least / s
