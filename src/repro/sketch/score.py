"""The scoring plane: residual anomaly scores against the sketch basis.

The sketch ``B`` is a basis, not just a compressor — the FD covariance
guarantee makes ``‖x‖² − ‖x Vᵀ‖²`` (energy outside the span of the live
sketch rows) a principled per-row anomaly score.  This module turns that
into the ``score`` capability (see ``repro.sketch.capability``) for every
registered variant:

* :func:`make_jax_score` wraps a raw ``(state, X, t) → (n,)`` scorer into
  the public ``score(state, X, t=None)`` — one jitted program per t-mode —
  and tags the raw function on it (``_per_stream``).  The fleet lift
  (``vmap_streams`` / ``shard_streams``) reads that tag and turns the raw
  scorer into a fleet program the same way it turns the block update
  into one: a whole ``(S, B, d)`` slab is scored in the tick that
  ingests it.
* :func:`make_host_score` is the numpy adapter for the host baselines
  (lmfd / difd / swr / swor): same residual against the orthonormal row
  space of whatever ``query()`` returns, computed with numpy SVD.
* :class:`ScorePlane` holds the per-user EWMA anomaly thresholds the
  serving engine maintains at ingest (``SketchFleetEngine(score=True)``):
  float64 host-side accumulators so checkpointed engines restore and keep
  scoring bit-identically.
"""

from __future__ import annotations

from typing import Callable, Dict, Optional

import numpy as np

import jax
import jax.numpy as jnp


def make_jax_score(raw: Callable) -> Callable:
    """Public ``score(state, X, t=None)`` from a raw ``(state, X, t)``
    residual program.  ``t=None`` and explicit-``t`` are two separately
    jitted programs (the None branch is a Python-level specialization, not
    a traced value)."""
    jit_t = jax.jit(raw)
    jit_nt = jax.jit(lambda state, X: raw(state, X, None))

    def score(state, X, t=None):
        X = jnp.asarray(X)
        if t is None:
            return jit_nt(state, X)
        return jit_t(state, X, jnp.asarray(t, jnp.int32))

    score._per_stream = raw
    return score


def host_residual_scores(rows: np.ndarray, X: np.ndarray) -> np.ndarray:
    """Numpy residual of ``X``'s rows against the row space of ``rows``."""
    rows = np.asarray(rows, np.float64)
    X = np.asarray(X, np.float64)
    tot = np.sum(X * X, axis=-1)
    live = rows[np.linalg.norm(rows, axis=-1) > 0.0]
    if live.size == 0:
        return np.maximum(tot, 0.0).astype(np.float32)
    _, s, vt = np.linalg.svd(live, full_matrices=False)
    V = vt[s > 1e-9 * max(float(s[0]), 1e-30)]
    coef = X @ V.T
    res = tot - np.sum(coef * coef, axis=-1)
    return np.maximum(res, 0.0).astype(np.float32)


def make_host_score(query_rows: Callable) -> Callable:
    """The host-baseline ``score`` adapter: residual against whatever row
    stack the baseline's ``query_rows`` returns (its native compressed
    sketch), via numpy SVD on the host."""

    def score(state, X, t=None):
        return host_residual_scores(np.asarray(query_rows(state, t)),
                                    np.asarray(X))

    return score


class ScorePlane:
    """Per-user EWMA anomaly thresholds over per-tick residual scores.

    For each stream the plane tracks an exponentially-weighted mean and
    variance of its per-tick peak score; once ``warmup`` ticks of history
    exist, a tick whose peak exceeds ``mean + zscore·σ`` flags the user.
    All state is small host-side float64/int64 (S-sized vectors) so it
    rides engine checkpoints exactly and restores bit-identically.
    """

    KEYS = ("score_mean", "score_var", "score_count", "score_flag",
            "score_last")

    def __init__(self, streams: int, *, ema: float = 0.05,
                 zscore: float = 4.0, warmup: int = 5):
        self.S = int(streams)
        self.ema = float(ema)
        self.zscore = float(zscore)
        self.warmup = int(warmup)
        self.mean = np.zeros(self.S, np.float64)
        self.var = np.zeros(self.S, np.float64)
        self.count = np.zeros(self.S, np.int64)
        self.flagged = np.zeros(self.S, bool)
        self.last = np.zeros(self.S, np.float64)

    def observe(self, scores: np.ndarray, counts: np.ndarray) -> np.ndarray:
        """Fold one tick: ``scores`` is the (S, B) slab score matrix,
        ``counts`` the (S,) number of *real* rows per stream this tick
        (slab rows beyond a stream's count are padding and are ignored).
        Returns the local stream ids newly flagged this tick."""
        counts = np.asarray(counts, np.int64)
        idx = np.flatnonzero(counts > 0)
        if idx.size == 0:
            return idx
        sc = np.asarray(scores, np.float64)[idx]
        mask = np.arange(sc.shape[1])[None, :] < counts[idx, None]
        peak = np.where(mask, sc, -np.inf).max(axis=1)
        warm = self.count[idx] >= self.warmup
        thr = self.mean[idx] + self.zscore * np.sqrt(
            np.maximum(self.var[idx], 0.0))
        newly = idx[warm & (peak > thr)]
        self.flagged[newly] = True
        self.last[idx] = peak
        a = self.ema
        delta = peak - self.mean[idx]
        self.mean[idx] += a * delta
        self.var[idx] = (1.0 - a) * (self.var[idx] + a * delta * delta)
        self.count[idx] += 1
        return newly

    def anomalies(self, *, reset: bool = False) -> np.ndarray:
        """Local stream ids currently flagged; ``reset=True`` clears the
        flags after reading (the mean/var history is kept either way)."""
        out = np.flatnonzero(self.flagged)
        if reset:
            self.flagged[:] = False
        return out

    def state_dict(self) -> Dict[str, np.ndarray]:
        return {"score_mean": self.mean.copy(),
                "score_var": self.var.copy(),
                "score_count": self.count.copy(),
                "score_flag": self.flagged.copy(),
                "score_last": self.last.copy()}

    def load_state_dict(self, arrays: Dict[str, np.ndarray]) -> None:
        self.mean = np.asarray(arrays["score_mean"], np.float64).copy()
        self.var = np.asarray(arrays["score_var"], np.float64).copy()
        self.count = np.asarray(arrays["score_count"], np.int64).copy()
        self.flagged = np.asarray(arrays["score_flag"], bool).copy()
        self.last = np.asarray(arrays["score_last"], np.float64).copy()
        if self.mean.shape[0] != self.S:
            raise ValueError(
                f"score plane holds {self.S} streams but the checkpoint "
                f"carries {self.mean.shape[0]} — same stream partition "
                "required")

    def spec(self) -> Dict[str, float]:
        return {"ema": self.ema, "zscore": self.zscore,
                "warmup": self.warmup}
