"""Async fleet ingest: bounded admission + double-buffered slab assembly.

``SketchFleetEngine`` advances S per-user sliding-window sketches as one
SPMD program, but rows only reach that program through a host-side
``(S, block, d)`` slab assembled in Python.  Before this module the
engine built a fresh slab row-by-row inside ``step()`` and handed the
numpy array straight to the jitted update — every tick paid allocation,
a full per-user Python loop, and the host→device transfer, all serial
with the device.  This module makes ingest a subsystem of its own:

``AdmissionQueue``
    The only holder of not-yet-ingested rows.  ``submit(user, row)``
    validates at admission time (user id inside ``[0, S)``, row
    convertible to a ``(d,)`` float32 vector) so malformed input fails
    with a clear ``ValueError`` instead of an inscrutable XLA shape
    error several ticks later, and applies bounded backpressure:
    ``submit`` returns ``True`` (accepted) or ``False`` (deferred —
    the queue is at ``capacity``) instead of growing without bound.
    ``submit_many(users, rows)`` is the batched form: one vectorized
    validation + one copy into the queue's row pool for the whole batch.

    Storage is a flat structure-of-arrays row pool (one int32 user-id
    array + one float32 row matrix, in admission order — which IS
    per-user FIFO order), not S Python deques.  Slab assembly
    (``take_block``) is a numpy group-rank scatter: a stable argsort by
    user id ranks each pending row within its user's FIFO, a boolean
    mask selects ranks below the per-user budget, and one fancy-index
    scatter writes every selected row into ``buf[user, rank]`` — zero
    per-row Python.  The live-user set is maintained incrementally
    (O(#touched) per tick, never a full O(S) sweep), so idle/sparse
    ticks on large fleets stay cheap.

``SyncIngest``
    The pre-pipeline path, kept as the measured baseline and for
    callers that want zero buffering between ``submit`` and device
    state: one fresh host slab per tick, packed at dispatch time,
    transferred by the jitted update.

``AsyncIngest``
    The double-buffered admission pipeline.  Two preallocated host
    slabs alternate: while the device consumes slab *k*, the rows for
    slab *k+1* are packed into the other buffer (one vectorized
    scatter, only previously-dirty streams re-zeroed) and prefetched
    onto the fleet mesh with ``jax.device_put`` — so when the engine
    next asks for a slab it receives an already-placed device array and
    the sharded update launches without a transfer on the critical
    path.  The prefetch transfers a private copy of the packed slab
    (``device_put`` can be zero-copy on CPU, so transferring the reused
    buffer itself would alias host memory a later tick repacks under a
    still-running update), so packing never waits on the device.  The
    engine keeps at most one tick in flight: after packing slab *k+1*
    it waits for tick *k−1*, never for tick *k*.

Tick/clock contract (what makes async bit-identical to sync): a tick
ingests, for every user, the first ``min(block, pending_u)`` rows of
that user's FIFO queue *as of the moment the tick's update is
dispatched*, in user order, at timestamps ``t+1 .. t+block``.  The
async pipeline stages slabs early, so rows submitted between staging
and dispatch are topped up into the staged slab at the swap point
(re-prefetching it); therefore the slab any tick dispatches is exactly
the slab the synchronous path would have built, and fleet state, clock,
and every ``query_user`` / ``query_cohort`` answer are bit-identical
between the two modes for the same interleaving of ``submit`` and
``step`` calls.  Staged-but-not-dispatched rows still count toward
``backlog`` and are unwound back to the queue front by
``flush_to_queue()`` before an engine checkpoint, so the checkpoint
format is pipeline-agnostic.
"""

from __future__ import annotations

from collections import deque
from typing import Any, Callable, Deque, Dict, List, Optional, Tuple

import numpy as np

__all__ = ["AdmissionQueue", "AsyncIngest", "IngestBacklogError",
           "SyncIngest", "make_pipeline"]


class IngestBacklogError(RuntimeError):
    """``run(max_ticks)`` exhausted its tick budget with rows still
    pending — the drain did NOT complete.  ``remaining`` is the backlog
    left behind, so callers that catch can resume with a larger budget."""

    def __init__(self, message: str, remaining: int):
        super().__init__(message)
        self.remaining = int(remaining)


class AdmissionQueue:
    """Bounded per-user FIFO admission of ``(d,)`` float32 rows.

    ``capacity`` bounds the *total* admitted-but-not-ingested rows
    across all users — queued rows plus any the pipeline is holding in
    a staged slab (``reserved``) — so a caller can size host memory to
    it (``None`` = unbounded, the historical behavior).  ``submit`` never
    raises for a full queue — it returns ``False`` so the caller can
    defer/shed — but malformed submissions (bad user id, wrong
    shape/dtype) raise ``ValueError`` immediately: admission is the
    last place an actionable error message is still possible.

    Internally rows live in one flat structure-of-arrays pool in
    admission order (see module docstring); ``queues`` is a read-only
    per-user *view* materialized on access for diagnostics and
    back-compat — mutate through ``submit``/``take_block``, never
    through it.
    """

    def __init__(self, streams: int, d: int,
                 capacity: Optional[int] = None):
        self.S = int(streams)
        self.d = int(d)
        if capacity is not None and int(capacity) < 1:
            raise ValueError(f"queue capacity {capacity} must be >= 1 "
                             "(or None for unbounded)")
        self.capacity = None if capacity is None else int(capacity)
        # flat row pool: valid rows live at [_start, _len) in admission
        # order (admission order restricted to one user = that user's
        # FIFO order, which is the only ordering the tick contract needs)
        self._ubuf = np.zeros((64,), np.int32)
        self._rbuf = np.zeros((64, self.d), np.float32)
        self._start = 0
        self._len = 0
        self._counts = np.zeros((self.S,), np.int64)  # pending per user
        self._live: set = set()              # users with pending rows
        # rows admitted but currently held OUTSIDE the queue (a staged
        # slab in the async pipeline): they left the pool but are not on
        # the device yet, so they still count against ``capacity``
        self.reserved = 0
        # bumped on every admission — lets a pipeline detect "no rows
        # arrived since I staged" in O(1) instead of walking the users
        self.seq = 0

    # -- row pool -----------------------------------------------------------

    def _ensure(self, extra: int) -> None:
        """Make room for ``extra`` appended rows: compact the consumed
        prefix away and double the pool until it fits (amortized O(1))."""
        if self._len + extra <= self._ubuf.shape[0]:
            return
        n = self._len - self._start
        cap = max(self._ubuf.shape[0], 64)
        while cap < n + extra:
            cap *= 2
        ubuf = np.zeros((cap,), np.int32)
        rbuf = np.zeros((cap, self.d), np.float32)
        ubuf[:n] = self._ubuf[self._start:self._len]
        rbuf[:n] = self._rbuf[self._start:self._len]
        self._ubuf, self._rbuf = ubuf, rbuf
        self._start, self._len = 0, n

    def _pending_views(self) -> Tuple[np.ndarray, np.ndarray]:
        return (self._ubuf[self._start:self._len],
                self._rbuf[self._start:self._len])

    # -- admission ----------------------------------------------------------

    def _validate(self, user, row) -> Tuple[int, np.ndarray]:
        if isinstance(user, bool) or not isinstance(user, (int, np.integer)):
            raise ValueError(
                f"user id must be an integer, got {type(user).__name__} "
                f"({user!r})")
        u = int(user)
        if not 0 <= u < self.S:
            raise ValueError(
                f"user id {u} outside the fleet's [0, {self.S}) stream "
                "range")
        arr = np.asarray(row)
        if arr.shape != (self.d,):
            raise ValueError(
                f"user {u}: row has shape {arr.shape}, expected a "
                f"({self.d},) float32 vector")
        if not (np.issubdtype(arr.dtype, np.floating)
                or np.issubdtype(arr.dtype, np.integer)):
            raise ValueError(
                f"user {u}: row dtype {arr.dtype} is not real-numeric — "
                f"expected a ({self.d},) float32 vector")
        return u, np.ascontiguousarray(arr, np.float32)

    def submit(self, user, row) -> bool:
        """Admit one row; ``True`` = accepted, ``False`` = deferred
        (queue at capacity — resubmit after a drain)."""
        u, arr = self._validate(user, row)
        if self.capacity is not None \
                and self.backlog + self.reserved >= self.capacity:
            return False
        self._ensure(1)
        self._ubuf[self._len] = u
        self._rbuf[self._len] = arr
        self._len += 1
        self._counts[u] += 1
        self._live.add(u)
        self.seq += 1
        return True

    def submit_many(self, users, rows) -> np.ndarray:
        """Batched admission: one vectorized validation + ONE copy into
        the row pool for the whole ``(n,) users / (n, d) rows`` batch —
        no per-row Python.  Per-user FIFO order is the batch order.

        Malformed input raises ``ValueError`` (nothing is admitted);
        capacity applies prefix-accept semantics: the longest prefix
        that fits is admitted and an ``(n,)`` bool mask says which rows
        were accepted (all-``True`` when everything fit — resubmit the
        ``~mask`` suffix after a drain)."""
        ua = np.asarray(users)
        if ua.ndim != 1 or (ua.size and (
                ua.dtype == np.bool_
                or not np.issubdtype(ua.dtype, np.integer))):
            raise ValueError(
                f"users must be a 1-D integer array, got shape "
                f"{ua.shape} dtype {ua.dtype}")
        ra = np.asarray(rows)
        if ra.shape != (ua.size, self.d):
            raise ValueError(
                f"rows has shape {ra.shape}, expected "
                f"({ua.size}, {self.d}) to match {ua.size} user id(s)")
        if ua.size and not (np.issubdtype(ra.dtype, np.floating)
                            or np.issubdtype(ra.dtype, np.integer)):
            raise ValueError(
                f"rows dtype {ra.dtype} is not real-numeric — expected "
                f"float32 rows")
        if ua.size:
            bad = (ua < 0) | (ua >= self.S)
            if bad.any():
                raise ValueError(
                    f"user id {int(ua[bad][0])} outside the fleet's "
                    f"[0, {self.S}) stream range")
        n = int(ua.size)
        mask = np.zeros((n,), bool)
        if n == 0:
            return mask
        if self.capacity is None:
            k = n
        else:
            free = self.capacity - (self.backlog + self.reserved)
            k = max(0, min(n, free))
        if k == 0:
            return mask
        ua = ua[:k].astype(np.int32, copy=False)
        self._ensure(k)
        self._ubuf[self._len:self._len + k] = ua
        self._rbuf[self._len:self._len + k] = ra[:k]
        self._len += k
        self._counts += np.bincount(ua, minlength=self.S)
        self._live.update(int(u) for u in np.unique(ua))
        self.seq += 1
        mask[:k] = True
        return mask

    def push_front(self, user: int, rows: List[np.ndarray]) -> None:
        """Return rows to the *front* of a user's queue in their original
        FIFO order (checkpoint unwind of a staged slab).  Bypasses the
        capacity bound: these rows were already admitted once."""
        k = len(rows)
        if not k:
            return
        if self._start < k:
            # no headroom at the pool front: reopen some by re-packing
            n = self._len - self._start
            cap = max(self._ubuf.shape[0], 64)
            while cap < n + 2 * k:
                cap *= 2
            ubuf = np.zeros((cap,), np.int32)
            rbuf = np.zeros((cap, self.d), np.float32)
            ubuf[k:k + n] = self._ubuf[self._start:self._len]
            rbuf[k:k + n] = self._rbuf[self._start:self._len]
            self._ubuf, self._rbuf = ubuf, rbuf
            self._start, self._len = k, k + n
        self._start -= k
        self._ubuf[self._start:self._start + k] = int(user)
        self._rbuf[self._start:self._start + k] = np.asarray(rows, np.float32)
        self._counts[user] += k
        self._live.add(int(user))
        self.seq += 1

    @property
    def backlog(self) -> int:
        return self._len - self._start

    def live_users(self) -> List[int]:
        """Users with pending rows, in (deterministic) user order."""
        return sorted(self._live)

    @property
    def queues(self) -> List[Deque[np.ndarray]]:
        """Read-only per-user FIFO view of the flat row pool (diagnostic
        / back-compat — the engine's ``_pending`` and checkpoint tests
        read it).  Mutations to the returned deques are NOT seen by the
        queue."""
        qs: List[Deque[np.ndarray]] = [deque() for _ in range(self.S)]
        users, rows = self._pending_views()
        for i in np.argsort(users, kind="stable"):
            qs[int(users[i])].append(rows[i].copy())
        return qs

    # -- draining -----------------------------------------------------------

    def take_block(self, buf: np.ndarray, block: int,
                   base: Optional[np.ndarray] = None
                   ) -> Tuple[List[int], List[int], int]:
        """Scatter, for every user, their first ``min(block - base_u,
        pending_u)`` FIFO rows into ``buf[u, base_u:]`` — one vectorized
        numpy pass, no per-row Python.

        ``buf`` is the (S, block, d) slab (rows being written are
        assumed zeroed); ``base`` (default all-zero) gives per-user
        write offsets, which is how the async pipeline tops up an
        already-staged slab.  Returns ``(touched, counts, nrows)`` with
        ``touched`` the users that received ≥ 1 row (ascending) and
        ``counts`` how many each received."""
        if self.backlog == 0:
            return [], [], 0
        if base is None:
            allow = np.full((self.S,), int(block), np.int64)
        else:
            allow = np.maximum(int(block) - np.asarray(base, np.int64), 0)
            # O(S) early-out BEFORE touching the pool: the steady-state
            # top-up of a fully-staged slab has allow ≡ 0, and sorting
            # the whole backlog just to take nothing would put an
            # O(backlog log backlog) term on every paced tick
            if not np.any(np.minimum(allow, self._counts) > 0):
                return [], [], 0
        users, rows = self._pending_views()
        # rank of each pending row within its user's FIFO: stable-sort
        # by user, subtract each group's start index, scatter back
        order = np.argsort(users, kind="stable")
        su = users[order]
        starts = np.flatnonzero(np.r_[True, su[1:] != su[:-1]])
        sizes = np.diff(np.r_[starts, su.size])
        rank_sorted = np.arange(su.size) - np.repeat(starts, sizes)
        rank = np.empty((su.size,), np.int64)
        rank[order] = rank_sorted
        sel = rank < allow[users]
        nrows = int(np.count_nonzero(sel))
        if nrows == 0:
            return [], [], 0
        tu, tr = users[sel], rank[sel]
        if base is not None:
            tr = tr + np.asarray(base, np.int64)[tu]
        buf[tu, tr] = rows[sel]
        taken = np.bincount(tu, minlength=self.S)
        self._counts -= taken
        # compact the survivors to the pool front (fancy-index = copies,
        # so the overlapping write is safe)
        keep = ~sel
        nkeep = int(np.count_nonzero(keep))
        if nkeep:
            self._ubuf[:nkeep] = users[keep]
            self._rbuf[:nkeep] = rows[keep]
        self._start, self._len = 0, nkeep
        # incremental live-set maintenance: only users that lost rows
        # this tick can have gone empty — never a full O(S) sweep
        touched = np.flatnonzero(taken)
        exhausted = touched[self._counts[touched] == 0]
        self._live.difference_update(int(u) for u in exhausted)
        return ([int(u) for u in touched],
                [int(c) for c in taken[touched]], nrows)

    def take_rowwise(self, buf: np.ndarray, block: int
                     ) -> Tuple[List[int], List[int], int]:
        """Legacy name for :meth:`take_block` (the assembly used to walk
        every user popping row-by-row; it is now the same vectorized
        scatter)."""
        return self.take_block(buf, block)

    def take_user_into(self, user: int, buf: np.ndarray, at: int,
                       block: int) -> int:
        """Pop up to ``block - at`` rows of ``user`` into
        ``buf[user, at:]``; returns how many were taken."""
        base = np.full((self.S,), int(block), np.int64)
        base[user] = int(at)
        _, _, n = self.take_block(buf, block, base=base)
        return n

    # -- persistence --------------------------------------------------------

    def snapshot(self) -> Tuple[np.ndarray, np.ndarray]:
        """Flat ``(pending_user, pending_rows)`` arrays — users walked in
        order, per-user FIFO preserved (the engine checkpoint format)."""
        users, rows = self._pending_views()
        if users.size == 0:
            return (np.zeros((0,), np.int32),
                    np.zeros((0, self.d), np.float32))
        order = np.argsort(users, kind="stable")
        return (np.ascontiguousarray(users[order], np.int32),
                np.ascontiguousarray(rows[order], np.float32))

    def load(self, users: np.ndarray, rows: np.ndarray) -> None:
        """Refill from a :meth:`snapshot` pair (checkpoint restore).
        Bypasses the capacity bound: these rows were admitted once."""
        ua = np.asarray(users, np.int32).reshape(-1)
        k = int(ua.size)
        if k:
            self._ensure(k)
            self._ubuf[self._len:self._len + k] = ua
            self._rbuf[self._len:self._len + k] = np.asarray(
                rows, np.float32).reshape(k, self.d)
            self._len += k
            self._counts += np.bincount(ua, minlength=self.S)
            self._live.update(int(u) for u in np.unique(ua))
        self.seq += 1


class SyncIngest:
    """The pre-pipeline ingest path: assemble a fresh host slab at
    dispatch time (one vectorized scatter) and let the jitted update
    transfer it.  Zero buffering between ``submit`` and device state."""

    mode = "sync"

    def __init__(self, queue: AdmissionQueue, block: int,
                 put: Callable[[np.ndarray], Any]):
        del put                       # transfer happens at dispatch
        self.queue = queue
        self.block = int(block)

    @property
    def staged_rows(self) -> int:
        return 0

    def staged_snapshot(self) -> List[Tuple[int, List[np.ndarray]]]:
        return []

    def next_slab(self) -> Tuple[Any, List[int], List[int], int]:
        q = self.queue
        if q.backlog == 0:            # idle tick: no slab, no allocation
            return None, [], [], 0
        slab = np.zeros((q.S, self.block, q.d), np.float32)
        touched, counts, nrows = q.take_block(slab, self.block)
        return slab, touched, counts, nrows

    def after_dispatch(self, consumed: Any = None) -> None:
        pass

    def flush_to_queue(self) -> None:
        pass


class AsyncIngest:
    """Double-buffered admission pipeline (see module docstring).

    ``put`` is the prefetch: host slab → device array placed with the
    fleet's slab sharding (``jax.device_put``).  Two host packing
    buffers alternate — one backs the staged (prefetched) slab so its
    rows stay addressable for top-up and checkpoint unwind, the other
    packs the next tick.  The prefetch hands the device a private copy
    of the packed slab, so buffer reuse never races device compute and
    the pipeline itself never waits on the device.
    """

    mode = "async"

    def __init__(self, queue: AdmissionQueue, block: int,
                 put: Callable[[np.ndarray], Any]):
        self.queue = queue
        self.block = int(block)
        self._put = put
        shape = (queue.S, block, queue.d)
        self._bufs = [np.zeros(shape, np.float32) for _ in range(2)]
        # per-buffer array of stream ids whose (block, d) rows were
        # written last pack — zeroed wholesale before the next pack
        self._dirty: List[np.ndarray] = [np.zeros((0,), np.int64)] * 2
        self._cur = 0                              # next buffer to pack
        # (buf index, device slab, touched, counts, nrows, queue seq at
        # staging time — unchanged seq ⇒ the staged slab is still exact)
        self._staged: Optional[Tuple[int, Any, List[int], List[int],
                                     int, int]] = None

    @property
    def staged_rows(self) -> int:
        return 0 if self._staged is None else self._staged[4]

    # -- buffer lifecycle ---------------------------------------------------

    def _assemble(self, i: int) -> Tuple[List[int], List[int], int]:
        buf = self._bufs[i]
        if self._dirty[i].size:
            buf[self._dirty[i]] = 0.0
        touched, counts, nrows = self.queue.take_block(buf, self.block)
        self._dirty[i] = np.asarray(touched, np.int64)
        return touched, counts, nrows

    def _prefetch(self, i: int) -> Any:
        # the device array is fed a private COPY of the packing buffer:
        # ``device_put`` may be zero-copy on CPU, so handing it the
        # reused buffer directly would alias host memory the next tick
        # repacks — corrupting a still-running update.  The copy makes
        # buffer reuse race-free with no cross-tick synchronization (the
        # packing buffer itself stays live for top-up/unwind while the
        # slab is staged, which is why there are two of them).
        return self._put(np.array(self._bufs[i]))

    # -- pipeline interface -------------------------------------------------

    def next_slab(self) -> Tuple[Any, List[int], List[int], int]:
        """The slab for THIS tick: the staged one (topped up with any
        rows submitted since it was packed — the sync contract) or,
        cold, one assembled on the spot."""
        if self._staged is None:
            i = self._cur
            touched, counts, nrows = self._assemble(i)
            if nrows == 0:
                return None, [], [], 0
            self._cur ^= 1
            return self._prefetch(i), touched, counts, nrows
        i, dev, touched, counts, nrows, seq = self._staged
        self._staged = None
        self.queue.reserved -= nrows
        self._cur = i ^ 1
        if self.queue.backlog and self.queue.seq != seq:
            # top-up: a synchronous tick would include rows submitted
            # after staging, up to `block` per user — match it exactly
            # with one base-offset scatter into the staged buffer
            cnt = np.zeros((self.queue.S,), np.int64)
            cnt[touched] = counts
            t2, c2, extra = self.queue.take_block(self._bufs[i], self.block,
                                                  base=cnt)
            if extra:
                cnt[t2] += c2
                touched = [int(u) for u in np.flatnonzero(cnt)]
                counts = [int(cnt[u]) for u in touched]
                nrows += extra
                self._dirty[i] = np.asarray(touched, np.int64)
                # the staged prefetch is stale; do NOT pay a second
                # transfer here — hand back a private host copy and let
                # the update transfer it at dispatch, exactly the sync
                # path's cost.  (The copy, not the reused buffer itself:
                # a zero-copy ``device_put`` downstream would alias
                # memory the tick after next repacks.)  A topped-up tick
                # therefore costs the same as sync, never more; the
                # discarded staging transfer was paid off the critical
                # path inside the previous tick's compute shadow.
                dev = np.array(self._bufs[i])
        return dev, touched, counts, nrows

    def after_dispatch(self, consumed: Any = None) -> None:
        """Stage the next slab while the device consumes the current one
        — the overlap that hides host assembly behind device compute."""
        del consumed                   # prefetch copies: nothing to guard
        if self._staged is not None or self.queue.backlog == 0:
            return
        i = self._cur
        touched, counts, nrows = self._assemble(i)
        self._cur ^= 1
        self._staged = (i, self._prefetch(i), touched, counts, nrows,
                        self.queue.seq)
        self.queue.reserved += nrows       # staged rows still fill capacity

    def staged_snapshot(self) -> List[Tuple[int, List[np.ndarray]]]:
        """Copies of the staged slab's rows as ``(user, rows)`` pairs in
        user order (each user's rows in FIFO order) — empty when nothing
        is staged."""
        if self._staged is None:
            return []
        i, _, touched, counts = self._staged[:4]
        buf = self._bufs[i]
        return [(u, [buf[u, b].copy() for b in range(k)])
                for u, k in zip(touched, counts)]

    def flush_to_queue(self) -> None:
        """Unwind the staged slab's rows back to the queue *front* (FIFO
        preserved) — checkpoints serialize the queue alone, so the
        on-disk format is pipeline-agnostic."""
        if self._staged is None:
            return
        rows = self.staged_snapshot()
        i, nrows = self._staged[0], self._staged[4]
        self._staged = None
        self.queue.reserved -= nrows   # rows return to queue accounting
        self._cur = i                  # the unwound buffer packs next
        for u, user_rows in rows:
            self.queue.push_front(u, user_rows)


_PIPELINES: Dict[str, type] = {"sync": SyncIngest, "async": AsyncIngest}


def make_pipeline(mode: str, queue: AdmissionQueue, *, block: int,
                  put: Callable[[np.ndarray], Any]):
    """Build an ingest pipeline: ``"async"`` (double-buffered, the
    default engine path) or ``"sync"`` (the legacy assemble-at-dispatch
    baseline).  Both produce bit-identical fleet state."""
    cls = _PIPELINES.get(mode)
    if cls is None:
        raise ValueError(
            f"unknown ingest mode {mode!r}; available: "
            f"{tuple(sorted(_PIPELINES))}")
    return cls(queue, block, put)
