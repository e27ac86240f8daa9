"""Device time of the fleet update program per tick, from the executions
that lie wholly inside the traced window."""

import _lib


def read(run):
    s = _lib.update_device_s(run)
    return None if s is None else s * 1e3
