"""Rows and plain reference of the telemetry-d64 deployment.

Rows: unit-norm rows; row ``k`` of user ``u`` is ``sqrt(a) dir[e, u] +
sqrt(1 - a) n`` renormalized, with ``e = k // epoch_rows`` and ``n``
isotropic noise taken from a seeded pool.  ``dir[e, u]`` is ``sqrt(b)
c[e] + sqrt(1 - b) g[e, u]`` renormalized: a fleet-wide direction ``c``
and the user's own ``g`` (the model of ``chip_smoke.Traffic``, copied).

Reference: the exact float64 Gram of the rows a user's window holds at
the answer's clock, ``sum(a a^T)`` over the rows stamped in ``(t - N,
t]``; a cohort's is the sum over its users.  The number compared for an
answer with sketch Gram ``H`` is ``||G - H||_2 / (N * users)``: the
error on the scale of the guarantee, a full window of unit rows for each
user in the answer.  (A user that received few rows lately holds far
less than ``N`` in its window, and DS-FD's error stays on the scale of
``eps N`` there, so the energy in the window is not the scale.)

Control: the same reference with the window guarantee broken, every row
absorbed up to ``t`` and none expired, put in the program's place.
"""

from __future__ import annotations

import numpy as np


def _unit(x):
    return x / np.linalg.norm(x, axis=-1, keepdims=True)


class Rows:
    def __init__(self, cfg: dict, seed: int):
        sys_, spec = cfg["system"], cfg["rows"]
        self.S, self.d = int(sys_["streams"]), int(sys_["d"])
        self.a, self.b = float(spec["dominant"]), float(spec["shared"])
        self.epoch = int(spec["epoch_rows"])
        self.P = int(spec["pool"])
        self.seed = int(seed)
        g = np.random.default_rng([self.seed & 0xFFFFFFFF, self.seed >> 32, 2])
        self.noise = (g.standard_normal((self.P, self.d), dtype=np.float32)
                      / np.float32(np.sqrt(self.d)))
        self._dirs = {}

    def dirs(self, e: int) -> np.ndarray:
        if e not in self._dirs:
            s = [self.seed & 0xFFFFFFFF, self.seed >> 32]
            g = _unit(np.random.default_rng(s + [1, e]).standard_normal(
                (self.S, self.d), dtype=np.float32))
            c = _unit(np.random.default_rng(s + [5, e]).standard_normal(
                self.d, dtype=np.float32))
            self._dirs[e] = _unit(np.float32(np.sqrt(self.b)) * c
                                  + np.float32(np.sqrt(1 - self.b)) * g)
        return self._dirs[e]

    def make(self, users: np.ndarray, ords: np.ndarray) -> np.ndarray:
        users = np.asarray(users, np.int64)
        ords = np.asarray(ords, np.int64)
        out = np.empty((users.size, self.d), np.float32)
        epochs = ords // self.epoch
        for e in np.unique(epochs):
            sel = epochs == e
            out[sel] = self.dirs(int(e))[users[sel]]
        idx = (users * 7919 + ords * 104729) % self.P
        out *= np.float32(np.sqrt(self.a))
        out += np.float32(np.sqrt(1 - self.a)) * self.noise[idx]
        return _unit(out)


def compare(answers, history, cfg: dict, *, control: bool = False) -> dict:
    """Worst error of the user answers and of the cohort answers, and the
    mean error of the user answers, against the exact window Gram.
    ``history(u, t)`` gives (rows, stamps) of every row user ``u`` had
    absorbed by clock ``t``."""
    N = int(cfg["system"]["window"])
    errs = {"user": [], "cohort": []}
    for ans in answers:
        G = 0.0
        C = 0.0
        for u in ans["users"]:
            rows, ts = history(int(u), ans["t"])
            rows = rows.astype(np.float64)
            live = rows[ts > ans["t"] - N]
            G = G + live.T @ live
            if control:
                C = C + rows.T @ rows
        H = C if control else ans["gram"]
        G = np.asarray(G, np.float64)
        errs[ans["kind"]].append(
            float(np.linalg.norm(G - H, 2) / (N * len(ans["users"]))))
    out = {}
    if errs["user"]:
        out["user_err"] = max(errs["user"])
        out["user_err_mean"] = float(np.mean(errs["user"]))
    if errs["cohort"]:
        out["cohort_err"] = max(errs["cohort"])
    return out
