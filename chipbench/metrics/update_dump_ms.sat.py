"""Device self time per traced tick of the update program's snapshot
dumps: the ops under the ``dsfd.dump`` named scope (the ring-append loop
over the buffer's rows and the compaction after it)."""

import _engine


def read(run):
    ms = _engine.update_scopes_ms(run)
    return None if ms is None else ms.get("dsfd.dump", 0.0)
