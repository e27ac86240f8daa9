"""Share of the traced window in which no operation ran on the device:
1 - (union of device op intervals / traced window)."""

import _lib


def read(run):
    return _lib.idle_pct(run)
