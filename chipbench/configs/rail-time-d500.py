"""Rows and plain reference of the rail-time-d500 deployment.

Rows: the RAIL analogue's row model (``rail_like`` in
``repro.data.streams``, copied): 4 to 11 distinct columns, each valued 1
or 2, every row scaled by the smallest squared norm in the pool (so
squared norms lie in [1, 11], in quarters).  A time unit holds an
arrival with probability ``arrival_p``, else a zero row: an idle unit.
Rows come from a seeded pool in which a unit is idle where the pool row
is zero; row ``k`` of stream ``u`` is pool row ``(7919 u + 104729 k) mod
P``.  Under saturated traffic ordinal ``k`` of a stream is time unit
``k + 1``.

Reference: Time-DS-FD (section 5; Algorithms 5 to 7 with thresholds
``theta_j = 2^j``) written from the paper in float64, one layer at a
time over a stream's rows in time order:

* each update first promotes the auxiliary sketch to main once it has
  absorbed ``ell theta_j`` of squared norm, and starts a new auxiliary;
* a row with ``||a||^2 >= theta_j`` goes into both snapshot rings as it
  is (the heavy-row bypass); a zero row (an idle unit) does nothing else;
* any other row is appended to both sketches' ``2 ell`` row buffers;
  a full buffer is shrunk (FrequentDirections: subtract ``sigma_ell^2``
  from every squared singular value, keep ``ell - 1`` rows), and rows
  whose squared norm reaches ``theta_j`` are dumped into the ring; a
  buffer whose running bound on ``sigma_1^2`` reaches ``theta_j`` is
  rotated to its singular directions, and those reaching ``theta_j``
  dumped;
* a ring holds ``cap`` snapshots; one more evicts the oldest.

A run covers a few hundred units of a 50000-unit window, so the window
is the whole stream so far: a layer covers it while its main sketch has
seen every row (at most one swap) and its ring has evicted nothing.  The
answer is the lowest covering layer's ring rows, in the order they were
written, then its buffer rows, compressed by the query's FD to ``2
ell`` rows as the program's query does.  A run that leaves that regime
(a clock past ``N``, or no layer covering) makes the comparison void,
and the run incorrect.  The number compared for an answer with sketch
Gram ``H`` is ``||H - R^T R||_2 / ||R^T R||_2``; ``R^T R`` is not the
window's exact Gram, from which a sketch that answers nothing is only
2% away on these rows, whose spectrum is flat.

Control: the same reference at ``ell / 2`` (the epsilon guarantee
broken, doubled) put in the program's place.
"""

from __future__ import annotations

import math

import numpy as np


class Rows:
    def __init__(self, cfg: dict, seed: int):
        sys_, spec = cfg["system"], cfg["rows"]
        d, P = int(sys_["d"]), int(spec["pool"])
        lo, hi = (int(x) for x in spec["nnz"])
        vlo, vhi = (int(x) for x in spec["values"])
        self.d, self.P = d, P
        g = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32,
                                   5])
        nnz = g.integers(lo, hi + 1, P)
        cols = np.argpartition(g.random((P, d)), hi, axis=1)[:, :hi]
        vals = g.integers(vlo, vhi + 1, (P, hi)).astype(np.float32)
        vals[np.arange(hi)[None, :] >= nnz[:, None]] = 0.0
        pool = np.zeros((P, d), np.float32)
        np.put_along_axis(pool, cols, vals, axis=1)
        sq = np.sum(pool * pool, axis=1)
        pool /= np.float32(np.sqrt(max(float(sq.min()), 1.0)))
        pool[g.random(P) >= float(spec["arrival_p"])] = 0.0
        self.pool = pool

    def make(self, users: np.ndarray, ords: np.ndarray) -> np.ndarray:
        idx = (np.asarray(users, np.int64) * 7919
               + np.asarray(ords, np.int64) * 104729) % self.P
        return self.pool[idx]


def levels(cfg: dict) -> int:
    """Layers of the Time-DS-FD stack: ``ceil(log2(eps N R)) + 1``."""
    sy = cfg["system"]
    return max(math.ceil(math.log2(max(
        float(sy["eps"]) * int(sy["window"]) * float(sy["R"]), 2.0))), 1) + 1


def ring_cap(eps: float, beta: float = 4.0) -> int:
    return int(2 * (1.0 + 4.0 / beta) / eps) + 4


def svd_rows(buf: np.ndarray):
    """Rows ``sigma_i v_i`` sorted by ``sigma`` (as many as ``buf``)."""
    _, s, vt = np.linalg.svd(buf, full_matrices=False)
    return s[:, None] * vt, s * s


class Sketch:
    """One FD sketch of a layer with its snapshot ring, in float64."""

    def __init__(self, start: int, ell: int, d: int, cap: int):
        self.start, self.ell, self.cap = start, ell, cap
        self.buf = np.zeros((2 * ell, d))
        self.nbuf = 0
        self.sig1 = 0.0
        self.energy = 0.0
        self.ring = []            # snapshot rows in the order written
        self.written = 0

    def append(self, row):
        self.ring.append(row)
        self.written += 1

    @property
    def evicted(self) -> bool:
        return self.written > self.cap

    def absorb(self, row, e: float, theta: float):
        self.buf[self.nbuf] = row
        self.nbuf += 1
        self.sig1 += e
        self.energy += e
        m = self.buf.shape[0]
        if self.nbuf >= m:                      # shrink, then dump
            rows, s2 = svd_rows(self.buf)
            s2n = np.maximum(s2 - s2[self.ell - 1], 0.0)
            rows = rows * np.sqrt(s2n / np.maximum(s2, 1e-300))[:, None]
            self._dump(rows, s2n, self.ell - 1, theta)
        elif self.sig1 >= theta:                # rotate, then dump
            rows, s2 = svd_rows(self.buf)
            self._dump(rows, s2, min(self.nbuf, m), theta)

    def _dump(self, rows, s2, nrows: int, theta: float):
        ndump = int(np.sum(s2[:nrows] >= theta))
        for r in rows[:ndump]:
            self.append(r)
        self.buf = np.zeros_like(self.buf)
        keep = rows[ndump:nrows]
        self.buf[:keep.shape[0]] = keep
        self.nbuf = keep.shape[0]
        self.sig1 = float(keep[0] @ keep[0]) if keep.shape[0] else 0.0

    def query_rows(self) -> np.ndarray:
        return np.concatenate([np.asarray(self.ring).reshape(-1,
                                                             self.buf.shape[1]),
                               self.buf[:self.nbuf]])


def layer(rows, ts, theta: float, ell: int, cap: int):
    """One layer over a stream's rows; its main sketch if that still
    covers the whole stream at the end, else ``None``."""
    d = rows.shape[1]
    main = aux = Sketch(1, ell, d, cap)     # both start alike at clock 1
    for row, t in zip(rows, ts):
        if aux.energy >= ell * theta:       # swap: promote the auxiliary
            main, aux = aux, Sketch(int(t), ell, d, cap)
            if main.start != 1:
                return None
        e = float(row @ row)
        sketches = (main,) if main is aux else (main, aux)
        if e >= theta:                      # heavy-row bypass
            for sk in sketches:
                sk.append(row)
        elif e > 0.0:
            for sk in sketches:
                sk.absorb(row, e, theta)
        if main.evicted:
            return None
    return main


def fd_compress(rows: np.ndarray, ell: int) -> np.ndarray:
    """The query's FD: absorb the non-zero rows into a ``2 ell`` buffer,
    shrinking it when full."""
    m = 2 * ell
    buf = np.zeros((m, rows.shape[1]))
    n = 0
    for r in rows:
        if r @ r <= 0.0:
            continue
        buf[n] = r
        n += 1
        if n == m:
            out, s2 = svd_rows(buf)
            s2n = np.maximum(s2 - s2[ell - 1], 0.0)
            buf = out * np.sqrt(s2n / np.maximum(s2, 1e-300))[:, None]
            n = ell - 1
    return buf


def answer(rows, ts, cfg: dict, ell: int):
    """Gram of the reference's answer at the stream's last clock, or
    ``None`` where no layer covers the stream."""
    cap = ring_cap(float(cfg["system"]["eps"]))
    rows = rows.astype(np.float64)
    for j in range(levels(cfg)):
        main = layer(rows, ts, 2.0 ** j, ell, cap)
        if main is not None:
            B = fd_compress(main.query_rows(), ell)
            return B.T @ B
    return None


def compare(answers, history, cfg: dict, *, control: bool = False) -> dict:
    sys_ = cfg["system"]
    N = int(sys_["window"])
    ell = int(round(1.0 / float(sys_["eps"])))
    worst = 0.0
    for ans in answers:
        (u,) = ans["users"]
        t = int(ans["t"])
        if t > N:
            return {"fd_gap": float("inf")}        # expiry: regime left
        rows, ts = history(int(u), t)
        R = answer(rows, ts, cfg, ell)
        H = answer(rows, ts, cfg, ell // 2) if control else ans["gram"]
        if R is None or H is None:
            return {"fd_gap": float("inf")}        # no layer covers
        gap = float(np.linalg.norm(H - R, 2) / max(np.linalg.norm(R, 2),
                                                   1e-30))
        worst = max(worst, gap)
    return {"fd_gap": worst}
