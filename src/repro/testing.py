"""Reference oracles for the fleet's query planes.

Each rebuilds an answer from scratch along the schedule its plane
documents, with scalar calls and explicit recursion, and shares none of
the plane's caches:

``cohort_fold``
    ``query_cohort`` (the ``AggTree``): the canonical segment-tree cover
    of each stream range, each segment folded by midpoint recursion,
    segments folded left in stream order.
``IntervalOracle``
    ``query_interval`` (the history plane): raw rows re-compressed
    through the canonical dyadic schedule with scalar ``fd_compress``.

The test suites and ``chip_smoke.py`` hold the engine to them bit for
bit.  They check the planes' schedules; an answer's accuracy is checked
separately, against the exact Gram of the rows.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.fd import fd_compress
from repro.sketch.history import dyadic_cover
from repro.sketch.query import canonical_cover


def cohort_fold(base, state, S: int, ranges, t: int):
    """``base.merge`` fold of the fleet ``state``'s streams in ``ranges``
    at query time ``t``.  Leaves are host copies, so the fold can take
    streams from every device of a sharded fleet; merges run on the
    default device."""
    jm = jax.jit(lambda a, b, tt: base.merge(a, b, tt))
    tt = jnp.asarray(t, jnp.int32)

    def fold(lo, hi):
        if hi - lo == 1:
            return jax.tree.map(lambda x: np.asarray(x[lo]), state)
        mid = (lo + hi) // 2
        return jm(fold(lo, mid), fold(mid, hi), tt)

    segs = []

    def cover(lo, hi, qlo, qhi):
        if qlo <= lo and hi <= qhi:
            segs.append((lo, hi))
            return
        mid = (lo + hi) // 2
        if qlo < mid:
            cover(lo, mid, qlo, min(qhi, mid))
        if qhi > mid:
            cover(mid, hi, max(qlo, mid), qhi)

    for lo, hi in ranges:
        cover(0, S, lo, hi)
    acc = None
    for lo, hi in segs:
        node = fold(lo, hi)
        acc = node if acc is None else jm(acc, node, tt)
    return acc


class IntervalOracle:
    """From-scratch ``query_interval`` over raw rows.

    ``rows`` is an ``(S, T, d)`` array or a mapping from stream id to that
    stream's ``(T, d)`` rows; a mapping need hold only the streams a query
    touches.  Row ``j`` is stamped ``j + 1``.  Unit ``u`` of a stream is
    ``fd_compress`` of its row; dyadic node ``(L, i)`` merges its two
    children by re-compressing their concatenation.  A unit whose row is
    zero in every given stream is an idle tick and holds nothing."""

    def __init__(self, rows, ell: int):
        self.rows = dict(rows) if isinstance(rows, dict) else dict(
            enumerate(rows))
        self.ell, self.memo = int(ell), {}
        self.T, self.d = next(iter(self.rows.values())).shape
        self.live = np.zeros(self.T, bool)
        for r in self.rows.values():
            self.live |= np.asarray(r).any(axis=1)

    def _compress(self, mat):
        return np.asarray(fd_compress(jnp.asarray(mat), self.ell))

    def _merge2(self, a, b):
        return self._compress(np.concatenate([a, b], axis=0))

    def node(self, s: int, L: int, i: int):
        """Stream ``s``'s sketch of dyadic node ``(L, i)``; ``None`` when
        the node holds no live unit."""
        key = (s, L, i)
        if key not in self.memo:
            if L == 0:
                v = (self._compress(self.rows[s][i - 1][None])
                     if 1 <= i <= self.T and self.live[i - 1] else None)
            else:
                a = self.node(s, L - 1, 2 * i)
                b = self.node(s, L - 1, 2 * i + 1)
                v = b if a is None else a if b is None else self._merge2(a, b)
            self.memo[key] = v
        return self.memo[key]

    def interval(self, t1: int, t2: int, S: int, ranges=None):
        """The sketch of ``[t1, t2)`` over the streams in ``ranges``
        (default: all ``S``)."""
        segs = []
        for lo, hi in ((0, S),) if ranges is None else ranges:
            canonical_cover(0, S, lo, hi, segs)

        def seg(L, i, lo, hi):
            if hi - lo == 1:
                return self.node(lo, L, i)
            mid = (lo + hi) // 2
            return self._merge2(seg(L, i, lo, mid), seg(L, i, mid, hi))

        acc = None
        for L, i in dyadic_cover(t1, t2):
            if self.node(segs[0][0], L, i) is None:
                continue
            v = None
            for lo, hi in segs:
                sv = seg(L, i, lo, hi)
                v = sv if v is None else self._merge2(v, sv)
            acc = v if acc is None else self._merge2(acc, v)
        return (np.zeros((2 * self.ell, self.d), np.float32) if acc is None
                else acc)
