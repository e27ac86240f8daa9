"""Rows and plain reference of the synthetic-d300 deployment.

Rows: the paper's Random Noisy matrix (section 7.1), ``a = (s * D) U^T +
n / zeta`` with ``s, n ~ N(0, I_d)``, ``D_ii = 1 - i / d`` and ``U`` a
seeded random orthonormal basis, each row normalized to unit norm (the
sequence-based, row-normalized setting).  Rows come from a seeded pool;
row ``k`` of stream ``u`` is pool row ``(7919 u + 104729 k) mod P``.

Reference: a run ingests far fewer than N = 100000 rows a stream, so no
row expires, no snapshot is dumped (a dump needs a direction of squared
norm ``theta = N / ell``) and the auxiliary sketch is never promoted.
DS-FD then is plain FrequentDirections (Liberty 2013) with a ``2 ell``
row buffer: append each row; when the buffer holds ``2 ell`` rows, take
its SVD, subtract ``sigma_ell^2`` from every squared singular value and
keep the ``ell - 1`` rows that stay positive.  The reference runs that
in float64 over the stream's rows and checks the regime as it goes: a
row that would have expired, or a buffer whose top squared singular
value plus a full buffer's energy reaches ``theta``, makes the
comparison void, and the run incorrect.  The number compared for an
answer with sketch Gram ``H`` is ``||H - R^T R||_2 / ||R^T R||_2``.

Control: the same reference at ``ell / 2`` (the epsilon guarantee
broken, doubled) put in the program's place.
"""

from __future__ import annotations

import numpy as np


class Rows:
    def __init__(self, cfg: dict, seed: int):
        sys_, spec = cfg["system"], cfg["rows"]
        d, P = int(sys_["d"]), int(spec["pool"])
        self.d, self.P = d, P
        g = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32,
                                   3])
        U, _ = np.linalg.qr(g.standard_normal((d, d)))
        Dd = 1.0 - np.arange(d) / d
        s = g.standard_normal((P, d), dtype=np.float32)
        n = g.standard_normal((P, d), dtype=np.float32)
        pool = (s * Dd.astype(np.float32)) @ U.T.astype(np.float32)
        pool += n / np.float32(spec["zeta"])
        pool /= np.linalg.norm(pool, axis=1, keepdims=True)
        self.pool = pool.astype(np.float32)

    def make(self, users: np.ndarray, ords: np.ndarray) -> np.ndarray:
        idx = (np.asarray(users, np.int64) * 7919
               + np.asarray(ords, np.int64) * 104729) % self.P
        return self.pool[idx]


def fd_reference(rows: np.ndarray, ell: int, theta: float) -> np.ndarray:
    """FrequentDirections of ``rows`` in float64; returns ``B^T B``.
    Raises ``ValueError`` when the stream leaves the no-dump regime."""
    d = rows.shape[1]
    m = 2 * ell
    buf = np.zeros((m, d))
    n = 0
    top = 0.0
    for r in rows.astype(np.float64):
        buf[n] = r
        n += 1
        if n == m:
            _, s, vt = np.linalg.svd(buf, full_matrices=False)
            s2 = np.maximum(s * s - s[ell - 1] ** 2, 0.0)
            buf = np.zeros((m, d))
            buf[:ell - 1] = np.sqrt(s2[:ell - 1])[:, None] * vt[:ell - 1]
            n = ell - 1
            top = float(s2[0])
        if top + m >= theta:
            raise ValueError("stream left the no-dump regime "
                             f"(top {top} + {m} >= theta {theta})")
    return buf.T @ buf


def compare(answers, history, cfg: dict, *, control: bool = False) -> dict:
    sys_ = cfg["system"]
    N = int(sys_["window"])
    ell = int(round(1.0 / float(sys_["eps"])))
    theta = N / ell
    worst = 0.0
    for ans in answers:
        (u,) = ans["users"]
        rows, ts = history(int(u), ans["t"])
        if ts.size and ts.min() <= ans["t"] - N:
            return {"fd_gap": float("inf")}        # expiry: regime left
        try:
            R = fd_reference(rows, ell, theta)
            H = (fd_reference(rows, ell // 2, theta) if control
                 else ans["gram"])
        except ValueError:
            return {"fd_gap": float("inf")}
        gap = float(np.linalg.norm(H - R, 2) / max(np.linalg.norm(R, 2),
                                                   1e-30))
        worst = max(worst, gap)
    return {"fd_gap": worst}
