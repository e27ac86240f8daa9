"""The fleet engine's record of its own ticks, and its device counters.

Every ``SketchFleetEngine.step()`` is one tick, timed in phases.  The
spans, in the order a tick runs them, all inside ``engine.step``:

    engine.next_slab      take the tick's slab from the ingest pipeline
    engine.score          enqueue the slab's scoring program (score=True)
    engine.dispatch       enqueue the fleet update program (update_block)
    engine.tree_advance   dirty the touched streams in the cohort cache
    engine.score_observe  feed the slab's scores to the EWMA flags
    engine.history        retire expired content (history=True)
    engine.prefetch       pack and place the next slab (async ingest)
    engine.wait           wait until the previous tick's state is ready

A phase a tick does not run (an idle poll stops after ``next_slab``; the
score and history phases need their planes) reads NaN.

Two records are kept of the same spans:

* Always, a fixed-size ring of per-tick records on ``time.perf_counter``
  (``TickLog``; ``SketchFleetEngine.ticks()`` returns it): per tick the
  step count, the engine clock before the tick, the start and end of
  ``engine.step`` and of each phase, the rows taken, the users touched,
  and the slab's occupancy, rows ÷ (streams × block).  Recording costs a
  few clock reads a tick: no allocation, no device sync.
* While the JAX profiler runs, each span is also a
  ``jax.profiler.TraceAnnotation`` (``engine.step`` a
  ``StepTraceAnnotation`` carrying the step count), so the phases land in
  the device trace beside the programs they enqueue.

The device side carries named scopes instead (``jax.named_scope``, in
the compiled programs' ``op_name`` metadata): ``dsfd.expire``,
``dsfd.swap`` and ``dsfd.absorb`` (inside it ``dsfd.insert``,
``dsfd.shrink``, ``dsfd.rotate``, ``dsfd.dump``, ``dsfd.krylov``) in the
update program, ``fleet.score`` and ``fleet.query`` in the scoring and
cohort-merge programs.

``census`` and ``census_diff`` are the counters
``SketchFleetEngine.counters()`` reads from the fleet state on demand.
"""

from __future__ import annotations

import collections
import time
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

PHASES = ("next_slab", "score", "dispatch", "tree_advance", "score_observe",
          "history", "prefetch", "wait")
(NEXT_SLAB, SCORE, DISPATCH, TREE_ADVANCE, SCORE_OBSERVE, HISTORY, PREFETCH,
 WAIT) = range(len(PHASES))

RECORD = np.dtype(
    [("step", np.int64), ("clock", np.int64), ("start", np.float64),
     ("end", np.float64)]
    + [(f"{p}_{edge}", np.float64) for p in PHASES
       for edge in ("start", "end")]
    + [("rows", np.int64), ("users", np.int64), ("occupancy", np.float64)])

# the tick logs of the engines built last in this process, newest last:
# a log outlives its engine, so the ticks before a teardown stay readable
# (the benchmark's readers run after the harness has dropped its engine)
_RECENT: collections.deque = collections.deque(maxlen=4)


def recent() -> List["TickLog"]:
    """The tick logs of the last engines built in this process, newest
    last."""
    return list(_RECENT)


class _Span:
    """One span of the log: a column pair of the current tick's row, and
    a profiler annotation while the profiler runs."""

    __slots__ = ("log", "col", "name", "ann")

    def __init__(self, log: "TickLog", col: int, name: str):
        self.log, self.col, self.name, self.ann = log, col, name, None

    def __enter__(self):
        log = self.log
        if log.profiling:
            self.ann = jax.profiler.TraceAnnotation(self.name)
            self.ann.__enter__()
        log.marks[log.row, self.col] = time.perf_counter()
        return self

    def __exit__(self, *exc):
        log = self.log
        log.marks[log.row, self.col + 1] = time.perf_counter()
        if self.ann is not None:
            self.ann.__exit__(*exc)
            self.ann = None
        return False


class TickLog:
    """Fixed-size ring of per-tick records on ``time.perf_counter``.

    ``slab_rows`` is the slab's capacity (streams × block), the
    denominator of occupancy.  ``update_hlo``, set by the engine, returns
    the compiled HLO text of the update program the ticks ran, for
    mapping a device trace's op names to their scopes after the engine
    is gone.
    """

    def __init__(self, slab_rows: int, capacity: int = 4096):
        self.slab_rows = int(slab_rows)
        self.capacity = int(capacity)
        self.marks = np.full((self.capacity, 2 + 2 * len(PHASES)), np.nan)
        self.counts = np.zeros((self.capacity, 4), np.int64)
        self.written = 0           # ticks recorded in all
        self.row = 0
        self.profiling = False
        self.update_hlo: Optional[Callable[[], str]] = None
        self._spans = tuple(_Span(self, 2 + 2 * i, "engine." + p)
                            for i, p in enumerate(PHASES))
        self._ann = None
        _RECENT.append(self)

    def step(self, clock: int) -> "TickLog":
        """Open the next tick's record; the ``with`` block is
        ``engine.step``."""
        self.row = self.written % self.capacity
        self.counts[self.row, :2] = (self.written, clock)
        self.counts[self.row, 2:] = 0
        self.marks[self.row, 2:] = np.nan
        self.profiling = jax.profiler.TraceAnnotation.is_enabled()
        return self

    def __enter__(self):
        if self.profiling:
            self._ann = jax.profiler.StepTraceAnnotation(
                "engine.step", step_num=self.written)
            self._ann.__enter__()
        self.marks[self.row, 0] = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.marks[self.row, 1] = time.perf_counter()
        self.written += 1
        if self._ann is not None:
            self._ann.__exit__(*exc)
            self._ann = None
        return False

    def span(self, phase: int) -> _Span:
        """The span of one phase (an index into ``PHASES``) of the open
        tick."""
        return self._spans[phase]

    def taken(self, rows: int, users: int) -> None:
        """The open tick took ``rows`` rows of ``users`` users."""
        self.counts[self.row, 2] = rows
        self.counts[self.row, 3] = users

    def records(self) -> np.ndarray:
        """The recorded ticks, oldest first, as a ``RECORD`` array (at
        most ``capacity``; a copy)."""
        n = min(self.written, self.capacity)
        order = (np.arange(self.written - n, self.written)
                 % self.capacity)
        out = np.zeros(n, RECORD)
        names = RECORD.names
        for j, name in enumerate(names[:2]):
            out[name] = self.counts[order, j]
        for j, name in enumerate(names[2:-3]):
            out[name] = self.marks[order, j]
        out["rows"] = self.counts[order, 2]
        out["users"] = self.counts[order, 3]
        out["occupancy"] = out["rows"] / max(self.slab_rows, 1)
        return out


# ---------------------------------------------------------------------------
# Counters from the fleet state
# ---------------------------------------------------------------------------

@jax.jit
def census(state) -> Dict[str, jax.Array]:
    """Per stream (and layer) of a DS-FD-family fleet state: each
    sketch's ring write cursor (it counts the snapshots dumped into the
    sketch), its live snapshots, and the auxiliary sketch's start (a
    swap restarts the auxiliary, so its start moves)."""
    def live(sk):
        return jnp.sum(sk.snap_valid, axis=-1, dtype=jnp.int32)

    return {"main_next": state.main.snap_next,
            "aux_next": state.aux.snap_next,
            "main_live": live(state.main), "aux_live": live(state.aux),
            "aux_start": state.aux.start_t}


@jax.jit
def census_diff(before, after) -> Dict[str, jax.Array]:
    """Fleet totals between two censuses: snapshots dumped, snapshots
    that left a ring by expiry or eviction, and main/aux swaps.

    A swap shows as a new auxiliary start; it promotes the auxiliary to
    main and starts a fresh auxiliary.  With more than one swap of a
    stream between the two censuses, or dumps into a main sketch that a
    swap then retired, the difference cannot see everything: read
    counters at least once per swap period (window rows of a stream) for
    exact counts."""
    swapped = after["aux_start"] != before["aux_start"]
    # the sketch that is main now was main before, or aux before a swap
    main_before = jnp.where(swapped, before["aux_next"], before["main_next"])
    main_live0 = jnp.where(swapped, before["aux_live"], before["main_live"])
    aux_before = jnp.where(swapped, 0, before["aux_next"])
    aux_live0 = jnp.where(swapped, 0, before["aux_live"])
    dm = after["main_next"] - main_before
    da = after["aux_next"] - aux_before
    left = (main_live0 + dm - after["main_live"]
            + aux_live0 + da - after["aux_live"])
    total = lambda x: jnp.sum(x, dtype=jnp.int32)            # noqa: E731
    return {"dumped": total(dm + da), "left": total(left),
            "swaps": total(swapped)}


def has_census(state) -> bool:
    """Whether ``state`` is a DS-FD-family fleet state (main and aux
    sketches with snapshot rings)."""
    main = getattr(state, "main", None)
    return main is not None and hasattr(main, "snap_next")


def initial_census(state) -> Dict[str, np.ndarray]:
    """The census of a freshly initialized fleet of ``state``'s shapes:
    empty rings, both sketches started at clock 1."""
    shape = np.shape(state.aux.start_t)
    zeros = np.zeros(shape, np.int32)
    return {"main_next": zeros, "aux_next": zeros, "main_live": zeros,
            "aux_live": zeros, "aux_start": np.ones(shape, np.int32)}
