"""The fleet engine's record of itself: per-tick spans and counts
(``ticks()``), device counters read from the fleet state
(``counters()``), and the named scopes of its update program."""

import re

import jax
import numpy as np
import pytest

from repro.serve import trace
from repro.serve.engine import SketchFleetEngine

S, D, BLOCK = 4, 8, 4


def _engine(**kw):
    kw = dict(d=D, streams=S, eps=1 / 2, window=16, block=BLOCK, **kw)
    return SketchFleetEngine("dsfd", **kw)


def _unit_rows(n, seed=0):
    x = np.random.default_rng(seed).normal(size=(n, D)).astype(np.float32)
    return x / np.linalg.norm(x, axis=1, keepdims=True)


def _full_tick(eng, rows):
    users = np.repeat(np.arange(S), BLOCK)
    assert eng.submit_many(users, rows[:users.size]).all()
    return eng.step()


def test_tick_record_has_every_phase_in_order():
    eng = _engine(score=True, history=True)
    X = _unit_rows(2 * S * BLOCK)
    _full_tick(eng, X)
    _full_tick(eng, X[S * BLOCK:])
    rec = eng.ticks()
    assert list(rec["step"]) == [0, 1]
    assert list(rec["clock"]) == [0, BLOCK]
    edges = ["start"] + [f"{p}_{e}" for p in trace.PHASES
                         for e in ("start", "end")] + ["end"]
    for r in rec:
        t = np.array([r[k] for k in edges])
        assert not np.isnan(t).any(), dict(zip(edges, t))
        assert (np.diff(t) >= 0).all(), dict(zip(edges, t))
        # the wait for the previous tick lies inside engine.step
        assert r["start"] <= r["wait_start"] <= r["wait_end"] <= r["end"]


def test_idle_poll_runs_only_next_slab():
    eng = _engine()
    assert eng.step() == 0
    r = eng.ticks()[-1]
    assert r["rows"] == 0 and r["users"] == 0 and r["occupancy"] == 0.0
    assert r["next_slab_start"] <= r["next_slab_end"] <= r["end"]
    for p in trace.PHASES[1:]:
        assert np.isnan(r[f"{p}_start"]) and np.isnan(r[f"{p}_end"]), p


def test_occupancy_of_a_partly_filled_slab():
    eng = _engine()
    X = _unit_rows(7)
    users = np.array([0, 0, 0, 2, 2, 2, 2])
    assert eng.submit_many(users, X).all()
    assert eng.step() == 7
    r = eng.ticks()[-1]
    assert (r["rows"], r["users"]) == (7, 2)
    assert r["occupancy"] == pytest.approx(7 / (S * BLOCK))


def test_tick_log_keeps_the_newest_ticks_in_order():
    log = trace.TickLog(slab_rows=8, capacity=4)
    for k in range(6):
        with log.step(clock=10 * k):
            with log.span(trace.NEXT_SLAB):
                pass
            log.taken(k, 1)
    rec = log.records()
    assert list(rec["step"]) == [2, 3, 4, 5]
    assert list(rec["clock"]) == [20, 30, 40, 50]
    assert list(rec["occupancy"]) == [k / 8 for k in (2, 3, 4, 5)]
    assert (np.diff(rec["start"]) > 0).all()


def test_tick_log_outlives_its_engine():
    eng = _engine()
    log = eng.log
    _full_tick(eng, _unit_rows(S * BLOCK))
    del eng
    assert trace.recent()[-1] is log
    assert log.records()["rows"].tolist() == [S * BLOCK]
    assert "dsfd.dump" in log.update_hlo()


def _ring_dumps(state, t0, t1):
    """Snapshots in the main and aux rings dumped at times (t0, t1]."""
    n = 0
    for sk in (state.main, state.aux):
        t = np.asarray(sk.snap_t)
        n += int(((t > t0) & (t <= t1)).sum())
    return n


def _state_diff(before, after):
    """The counters, per stream, from two fleet states pulled to the
    host: a swap promotes the auxiliary sketch and starts a new one."""
    b = jax.tree.map(np.asarray, before)
    a = jax.tree.map(np.asarray, after)
    dumped = left = swaps = 0
    for s in range(S):
        live = lambda sk: int(sk.snap_valid[s].sum())        # noqa: E731
        if a.aux.start_t[s] != b.aux.start_t[s]:
            swaps += 1
            dm = int(a.main.snap_next[s] - b.aux.snap_next[s])
            da = int(a.aux.snap_next[s])
            left += (live(b.aux) + dm - live(a.main)) + (da - live(a.aux))
        else:
            dm = int(a.main.snap_next[s] - b.main.snap_next[s])
            da = int(a.aux.snap_next[s] - b.aux.snap_next[s])
            left += (live(b.main) + dm - live(a.main)
                     + live(b.aux) + da - live(a.aux))
        dumped += dm + da
    return {"dumped": dumped, "left": left, "swaps": swaps}


def test_counters_equal_the_state_diff_over_dumps_and_swaps():
    """Rows all along one direction: every few rows the buffer's top
    direction reaches θ and is dumped, and every window of rows the
    auxiliary sketch is promoted (a swap)."""
    eng = _engine()
    row = np.zeros((S * BLOCK, D), np.float32)
    row[:, 0] = 1.0
    totals = {"dumped": 0, "left": 0, "swaps": 0}
    prev = eng.state
    for _ in range(12):
        t0 = eng.t
        _full_tick(eng, row)
        got = eng.counters()
        want = _state_diff(prev, eng.state)
        assert {k: got[k] for k in want} == want
        # every snapshot dumped this tick is still in a ring (no ring
        # wraps within a tick here) — unless a swap retired its sketch
        if not want["swaps"]:
            assert got["dumped"] == _ring_dumps(eng.state, t0, eng.t)
        for k in totals:
            totals[k] += got[k]
        prev = eng.state
    # 48 unit rows a stream, a swap every window (16 rows) of aux energy
    assert totals["swaps"] == 2 * S
    assert totals["dumped"] > 0 and totals["left"] > 0
    assert eng.counters()["dumped"] == 0          # nothing since the last


def test_first_counters_count_from_the_initial_fleet():
    eng = _engine()
    row = np.zeros((S * BLOCK, D), np.float32)
    row[:, 0] = 1.0
    fresh = eng.state
    for _ in range(3):
        _full_tick(eng, row)
    got = eng.counters()
    assert {k: got[k] for k in ("dumped", "left", "swaps")} == _state_diff(
        fresh, eng.state)
    assert got["update_temp_bytes"] > 0


def test_counters_of_a_variant_without_snapshots():
    eng = SketchFleetEngine("fd", d=D, streams=S, eps=1 / 2, block=BLOCK)
    _full_tick(eng, _unit_rows(S * BLOCK))
    got = eng.counters()
    assert got["dumped"] is None and got["swaps"] is None
    assert got["update_output_bytes"] > 0


def test_update_program_names_the_dsfd_phases():
    eng = _engine()
    text = eng.compiled_update().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    scopes = {part for n in names for part in n.split("/")
              if part.startswith("dsfd.")}
    for scope in ("dsfd.absorb", "dsfd.insert", "dsfd.shrink",
                  "dsfd.rotate", "dsfd.dump", "dsfd.expire", "dsfd.swap"):
        assert scope in scopes, sorted(scopes)
    # the dump loop runs inside absorb
    assert any("dsfd.absorb/" in n and "dsfd.dump" in n for n in names)


def test_score_and_cohort_merge_programs_are_named():
    eng = _engine(score=True)
    slab = np.zeros((S, BLOCK, D), np.float32)
    score = jax.jit(lambda st, x: eng.fleet.score(st, x, 3)).lower(
        eng.state, slab).compile().as_text()
    assert "fleet.score" in score
    one = jax.tree.map(lambda x: x[0], eng.state)
    merge = eng.tree._jmerge.lower(one, one, 3).compile().as_text()
    assert "fleet.query" in merge
