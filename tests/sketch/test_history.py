"""Acceptance suite for the persistent sketch plane (time-travel
interval queries, ``repro.sketch.history``).

The load-bearing pins: ``query_interval(t1, t2)`` over retired content
is BIT-IDENTICAL to an independently reimplemented fold of the raw rows
through the canonical dyadic schedule (``repro.testing.IntervalOracle``
shares no code with the plane — scalar ``fd_compress`` calls, explicit
recursion), on four paths: hot-only, cold-faulted (spill forced via a
tiny hot tier), post-checkpoint-restore, and 2-process
``FleetTopology``.  Warm queries stay within the ``2⌈log₂(t2−t1)⌉``
node-merge budget.  Eviction (AggTree GC) and retirement (history
index) are conserved on a shared clock sequence.
"""

import os
import threading

import numpy as np
import pytest

from repro.serve.engine import SketchFleetEngine
from repro.sketch.history import (HistoryPlane, dyadic_cover,
                                  install_query_interval,
                                  interval_merge_budget)
from repro.sketch.query import Cohort
from repro.testing import IntervalOracle
from repro.train.checkpoint import HISTORY_MARKER

S, D, ELL, W, BLOCK, N = 8, 12, 4, 16, 4, 48
EPS = 0.25                       # -> ell=4 for dsfd


def _rows(seed=0, n=N, idle_ticks=()):
    """(S, n, d) float32 rows; row j of stream s is stamped ts=j+1 by the
    engine's slab packing.  ``idle_ticks``: tick indices whose block of
    units is zeroed (what an ``advance_time=True`` idle tick ingests)."""
    rng = np.random.default_rng(seed)
    rows = rng.normal(size=(S, n, D)).astype(np.float32)
    for k in idle_ticks:
        rows[:, k * BLOCK:(k + 1) * BLOCK, :] = 0.0
    return rows


def _engine(rows, **kw):
    eng = SketchFleetEngine("dsfd", d=D, streams=S, eps=EPS, window=W,
                            block=BLOCK, history=True, **kw)
    n = rows.shape[1]
    live = rows.any(axis=2)               # zero rows are idle ticks:
    users = np.repeat(np.arange(S), n)    # submit only the real ones and
    flat = rows.reshape(-1, D)            # advance time for the rest
    mask = live.reshape(-1)
    if mask.all():
        assert eng.submit_many(users, flat).all()
        eng.run()
    else:
        for k in range(n // BLOCK):
            sel = slice(None), slice(k * BLOCK, (k + 1) * BLOCK)
            blk = rows[sel]
            if blk.any():
                u = np.repeat(np.arange(S), BLOCK)
                assert eng.submit_many(u, blk.reshape(-1, D)).all()
                eng.step()
            else:
                eng.step(advance_time=True)
    return eng


INTERVALS = [(1, 33), (0, 33), (5, 29), (16, 17), (1, 2), (7, 23)]
COHORTS = [(None, ((0, S),)),
           (range(0, 4), ((0, 4),)),
           (Cohort.range(1, 2) | Cohort.range(5, 7), ((1, 2), (5, 7)))]


# ---------------------------------------------------------------------------
# Dyadic cover structure
# ---------------------------------------------------------------------------


def test_dyadic_cover_properties():
    rng = np.random.default_rng(7)
    for _ in range(200):
        t1 = int(rng.integers(0, 500))
        t2 = t1 + 1 + int(rng.integers(0, 500))
        cover = dyadic_cover(t1, t2)
        # exact disjoint cover, in order
        cursor = t1
        for L, i in cover:
            assert i * (1 << L) == cursor          # aligned at the cursor
            cursor += 1 << L
        assert cursor == t2
        # the merge budget: |cover| - 1 <= 2*ceil(log2(len))
        assert len(cover) - 1 <= interval_merge_budget(t1, t2)
    with pytest.raises(ValueError):
        dyadic_cover(3, 3)
    with pytest.raises(ValueError):
        dyadic_cover(-1, 3)


# ---------------------------------------------------------------------------
# Bit-identity: hot, warm budget, cold-faulted, restore, topology
# ---------------------------------------------------------------------------


def test_hot_only_bit_identical_to_oracle():
    rows = _rows()
    eng = _engine(rows)
    assert eng.history.retired_through == eng.t - W == 32
    oracle = IntervalOracle(rows, ELL)
    for t1, t2 in INTERVALS:
        for users, ranges in COHORTS:
            np.testing.assert_array_equal(
                eng.query_interval(users, t1, t2),
                oracle.interval(t1, t2, S, ranges))
    # nothing spilled, nothing faulted on the unbounded hot tier
    assert eng.history.store.spills == 0
    assert eng.history.store.faults == 0


def test_warm_query_within_merge_budget():
    eng = _engine(_rows())
    h = eng.history
    for t1, t2 in INTERVALS:
        eng.query_interval(None, t1, t2)      # warms nodes + reductions
        m0 = h.merges
        eng.query_interval(None, t1, t2)
        assert h.merges - m0 <= interval_merge_budget(t1, t2), \
            f"[{t1}, {t2}): {h.merges - m0} merges"


def test_cold_faulted_bit_identical(tmp_path):
    rows = _rows()
    spill = str(tmp_path / "spill")
    eng = _engine(rows, history_hot_nodes=2, history_dir=spill)
    st = eng.history.store
    assert st.spills > 0 and len(st.on_disk) > 0    # spill actually forced
    assert os.path.isfile(os.path.join(spill, HISTORY_MARKER))
    # cold nodes live in the shared checkpoint layout: manifest + leaf npy
    node = sorted(os.listdir(spill))
    node = [n for n in node if n.startswith("node_")][0]
    step = os.path.join(spill, node, "step_000000000")
    assert os.path.isfile(os.path.join(step, "manifest.json"))
    f0 = st.faults
    oracle = IntervalOracle(rows, ELL)
    for t1, t2 in INTERVALS:
        for users, ranges in COHORTS:
            np.testing.assert_array_equal(
                eng.query_interval(users, t1, t2),
                oracle.interval(t1, t2, S, ranges))
    assert st.faults > f0                           # answers crossed tiers


def test_checkpoint_restore_answers_identically(tmp_path):
    rows = _rows()
    spill = str(tmp_path / "spill")
    eng = _engine(rows, history_hot_nodes=2, history_dir=spill)
    want = {(t1, t2): eng.query_interval(None, t1, t2)
            for t1, t2 in INTERVALS}
    ck = str(tmp_path / "ck")
    eng.checkpoint(ck)
    rest = SketchFleetEngine.from_checkpoint(ck)
    assert rest.history is not None
    assert rest.history.retired_through == eng.history.retired_through
    for (t1, t2), v in want.items():
        np.testing.assert_array_equal(rest.query_interval(None, t1, t2), v)
    # the restored fleet carries the live protocol hook too
    np.testing.assert_array_equal(
        rest.fleet.query_interval(rest.state, 5, 29), want[(5, 29)])
    # retirement continues identically after the restore
    for e in (eng, rest):
        for _ in range(4):
            e.step(advance_time=True)
    assert rest.history.retired_through == eng.history.retired_through == 48
    np.testing.assert_array_equal(eng.query_interval(None, 30, 49),
                                  rest.query_interval(None, 30, 49))


def test_restore_refuses_partition_mismatch(tmp_path):
    meta, _ = _engine(_rows()).history.state_dict()
    meta = dict(meta, scope=[0, 4])        # somebody else's slice
    with pytest.raises(ValueError, match="same stream partition"):
        HistoryPlane.from_state_dict(meta, {})


def test_two_process_topology_bit_identical():
    from repro.parallel.topology import FleetTopology, MemTransport

    rows = _rows(idle_ticks=(4,))
    single = _engine(rows)
    queries = [(None, 1, 33), (None, 5, 29), (range(0, 4), 0, 33),
               ([1, 5, 6], 2, 31)]
    want = [single.query_interval(c, t1, t2) for c, t1, t2 in queries]

    transport = MemTransport()
    res, errs = {}, {}

    def worker(pid):
        try:
            topo = FleetTopology(S, num_processes=2, process_id=pid,
                                 transport=transport, namespace="hist2p")
            plane = HistoryPlane(streams=S, d=D, ell=ELL, window=W,
                                 topology=topo)
            for k in range(N // BLOCK):
                slab = rows[topo.lo:topo.hi, k * BLOCK:(k + 1) * BLOCK, :]
                plane.observe_block(slab, first_ts=k * BLOCK + 1)
                plane.retire_through((k + 1) * BLOCK - W)
            res[pid] = [plane.query_interval(t1, t2, c)
                        for c, t1, t2 in queries]
        except Exception:                      # surfaced after join
            import traceback
            errs[pid] = traceback.format_exc()

    threads = [threading.Thread(target=worker, args=(p,)) for p in (0, 1)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=300)
    assert not errs, f"worker failed:\n{''.join(errs.values())}"
    for pid in (0, 1):
        for got, exp in zip(res[pid], want):
            np.testing.assert_array_equal(got, exp)


# ---------------------------------------------------------------------------
# Retirement semantics
# ---------------------------------------------------------------------------


def test_idle_advance_time_ticks_retire():
    rows = _rows(idle_ticks=(2, 3))
    eng = _engine(rows)
    assert eng.history.retired_through == 32     # idle ticks aged the clock
    oracle = IntervalOracle(rows, ELL)
    # an interval fully inside the idle region is the zero sketch
    idle = eng.query_interval(None, 2 * BLOCK + 1, 4 * BLOCK + 1)
    assert not idle.any()
    for t1, t2 in [(1, 33), (5, 29), (9, 17)]:   # spans crossing the gap
        np.testing.assert_array_equal(eng.query_interval(None, t1, t2),
                                      oracle.interval(t1, t2, S))
    # clock-neutral idle polls retire nothing
    r0, t0 = eng.history.retired_units, eng.t
    eng.step()
    assert (eng.history.retired_units, eng.t) == (r0, t0)


def test_retire_is_idempotent_and_exactly_once():
    eng = _engine(_rows())
    h = eng.history
    assert h.retired_units == h.retired_through == eng.t - W
    assert h.retire_through(h.retired_through) == 0      # no double-retire
    assert h.retired_units == eng.t - W
    with pytest.raises(RuntimeError, match="retired twice"):
        h.store.put((0, 1), None)


def test_eviction_matches_retirement_on_shared_clock():
    """Satellite: on a clock sequence where every advancing tick is
    preceded by exactly one cached-node cohort query, the AggTree GC
    evicts exactly as many nodes as the history plane retires units —
    no leak, no double-retire (block=1: one unit per tick)."""
    eng = SketchFleetEngine("dsfd", d=D, streams=S, eps=EPS, window=4,
                            block=1, history=True)
    rng = np.random.default_rng(3)
    assert eng.tree.evicted_nodes == 0 and eng.history.retired_units == 0
    # warmup: fill the window with NO queries between ticks — the GC has
    # nothing cached to evict, and nothing has expired yet: 0 == 0
    for j in range(4):
        eng.submit(0, rng.normal(size=D).astype(np.float32))
        eng.step()
    assert eng.tree.evicted_nodes == 0 and eng.history.retired_units == 0
    # steady state: query cohort [0, 2) (caches exactly its one canonical
    # node), then tick — the advance evicts that node AND retires the one
    # unit that just fell off the window
    for j in range(10):
        eng.query_cohort(Cohort.range(0, 2))
        assert eng.tree.cached_nodes == 1
        eng.submit(0, rng.normal(size=D).astype(np.float32))
        eng.step()
        assert eng.tree.evicted_nodes == j + 1
        assert eng.history.retired_units == j + 1
        eng.step()                     # clock-neutral poll: changes nothing
        assert eng.tree.evicted_nodes == eng.history.retired_units == j + 1
    assert eng.tree.evicted_nodes == eng.history.retired_units == 10


# ---------------------------------------------------------------------------
# Raisers & bounds
# ---------------------------------------------------------------------------


def test_unretired_interval_raises():
    eng = _engine(_rows())
    with pytest.raises(ValueError, match="live window"):
        eng.query_interval(None, 1, eng.history.retired_through + 2)
    with pytest.raises(ValueError, match="0 <= t1 < t2"):
        eng.query_interval(None, 5, 5)
    with pytest.raises(ValueError, match="0 <= t1 < t2"):
        eng.query_interval(None, -1, 5)
    # boundary: exactly the retired frontier is addressable
    eng.query_interval(None, 1, eng.history.retired_through + 1)


def test_explanatory_raisers():
    from repro.sketch.api import make_sketch, query_interval, vmap_streams

    single = make_sketch("dsfd", d=D, eps=EPS, window=W)
    with pytest.raises(ValueError, match="single sketch"):
        single.query_interval(None, 1, 2)
    host = make_sketch("lmfd", d=D, eps=EPS, window=W)
    assert host.meta["backend"] == "host"
    with pytest.raises(ValueError, match="host-side baseline"):
        host.query_interval(None, 1, 2)
    fleet = vmap_streams(single, S)
    with pytest.raises(ValueError, match="no history plane"):
        fleet.query_interval(None, 1, 2)
    with pytest.raises(ValueError, match="no history plane"):
        query_interval(fleet, None, 1, 2)
    eng = SketchFleetEngine("dsfd", d=D, streams=S, eps=EPS, window=W,
                            block=BLOCK)                  # history off
    # the engine delegates to the fleet's capability raiser — the message
    # must name the constructor the engine caller can actually use
    with pytest.raises(ValueError, match="no history plane"):
        eng.query_interval(None, 1, 2)
    with pytest.raises(ValueError, match="history=True"):
        eng.query_interval(None, 1, 2)
    with pytest.raises(ValueError, match="hot capacity"):
        SketchFleetEngine("dsfd", d=D, streams=S, eps=EPS, window=W,
                          block=BLOCK, history=True, history_hot_nodes=0,
                          history_dir="/tmp/never")
    with pytest.raises(ValueError, match="somewhere to spill"):
        HistoryPlane(streams=S, d=D, ell=ELL, window=W, hot_capacity=4)


def test_install_query_interval_protocol_hook():
    from repro.sketch.api import make_sketch, query_interval, vmap_streams

    rows = _rows()
    eng = _engine(rows)
    fleet = vmap_streams(make_sketch("dsfd", d=D, eps=EPS, window=W), S)
    fleet = install_query_interval(fleet, eng.history)
    assert fleet.meta["hist_box"]["plane"] is eng.history
    np.testing.assert_array_equal(
        query_interval(fleet, None, 5, 29),
        eng.query_interval(None, 5, 29))
