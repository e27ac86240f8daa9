"""Engine tick on the host clock: from ``step()`` entry until the
tick's fleet state is ready on the device, mean over the window's ticks."""

import _lib


def read(run):
    return _lib.tick_ms(run)
