"""Device self time per traced tick of the update program's SVDs: the
ops under the ``dsfd.shrink`` and ``dsfd.rotate`` named scopes (under
``vmap`` both branches run for every row; the compiler may merge their
SVDs of the same buffer into one, under either name)."""

import _engine


def read(run):
    ms = _engine.update_scopes_ms(run)
    if ms is None:
        return None
    return ms.get("dsfd.shrink", 0.0) + ms.get("dsfd.rotate", 0.0)
