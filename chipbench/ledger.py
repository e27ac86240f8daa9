"""The harness's own record of where every admitted row went.

The engine's tick contract is that of a synchronous tick: each ``step()``
takes, for every user, the first ``min(pending, block)`` rows of that
user's FIFO, stamped ``t+1 .. t+n``.  ``Ledger`` mirrors that from what
the harness submitted, checks every tick that its count equals what
``step()`` returned, and afterwards answers which rows (with their
timestamps) a user's window held at any clock.
"""

from __future__ import annotations

import numpy as np


def within_user_rank(users: np.ndarray) -> np.ndarray:
    """Rank of each entry among the entries of the same user, in order."""
    order = np.argsort(users, kind="stable")
    su = users[order]
    idx = np.arange(su.size)
    first = np.ones(su.size, bool)
    first[1:] = su[1:] != su[:-1]
    start = np.maximum.accumulate(np.where(first, idx, 0))
    rank = np.empty(su.size, np.int64)
    rank[order] = idx - start
    return rank


class Ledger:
    def __init__(self, streams: int, block: int):
        self.S, self.block = int(streams), int(block)
        self.admitted = np.zeros(self.S, np.int64)
        self.pending = np.zeros(self.S, np.int64)
        self.takes: list = []          # (S,) rows taken per tick
        self.t_before: list = []       # engine clock before each tick
        self.mismatches = 0            # ticks whose count differed

    def admit(self, users: np.ndarray) -> np.ndarray:
        """Record rows accepted by ``submit_many``; returns each row's
        ordinal in its user's stream."""
        users = np.asarray(users, np.int64)
        ords = self.admitted[users] + within_user_rank(users)
        counts = np.bincount(users, minlength=self.S)
        self.admitted += counts
        self.pending += counts
        return ords

    def ordinals(self, users: np.ndarray) -> np.ndarray:
        """The ordinals the next ``admit(users)`` will give, unrecorded."""
        users = np.asarray(users, np.int64)
        return self.admitted[users] + within_user_rank(users)

    def tick(self, t_before: int, returned: int) -> int:
        """Mirror one ``step()``; returns the rows the mirror expected."""
        take = np.minimum(self.pending, self.block)
        self.pending -= take
        self.takes.append(take.astype(np.int16))
        self.t_before.append(int(t_before))
        expected = int(take.sum())
        if expected != int(returned):
            self.mismatches += 1
        return expected

    # -- after the run -------------------------------------------------------

    def stream(self, user: int, t: int):
        """(ordinals, timestamps) of every row ``user`` had absorbed by
        engine clock ``t``, in order."""
        takes = np.array([int(k[user]) for k in self.takes], np.int64)
        t0 = np.asarray(self.t_before, np.int64)
        ts = np.repeat(t0, takes) + 1 + (
            np.arange(int(takes.sum())) - np.repeat(
                np.cumsum(takes) - takes, takes))
        keep = ts <= t
        return np.arange(ts.size)[keep], ts[keep]
