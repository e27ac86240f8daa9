"""Device self time per traced tick of the layered update's heavy-row
bypass: the ops under the ``dsfd.bypass`` named scope (Algorithm 6's
append of a row with ``||a||^2 >= theta_j`` into both snapshot rings).
A program without that scope gives nothing."""

import _engine
import _lib
import scopes

SCOPE = "dsfd.bypass"


def read(run):
    log = _engine.engine_log()
    tick_s = _lib.update_device_s(run)
    if log is None or tick_s is None or log.update_hlo is None:
        return None
    hlo = log.update_hlo()
    if SCOPE not in hlo:
        return None
    return scopes.per_tick_ms(run.trace, hlo, tick_s).get(SCOPE, 0.0)
