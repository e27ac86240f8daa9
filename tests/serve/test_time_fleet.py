"""Time-DS-FD through the fleet engine's normal path: ``SketchFleetEngine
("time-dsfd")`` → ``shard_streams`` → the block scan → ``layered_update``.

Every user is saturated, so each tick stamps ``block`` time units of
every stream; half of the units are idle (zero rows).  The run is long
enough that windows fill, rows expire and sketches swap."""

import jax
import numpy as np
import pytest

from repro.core.seq_dsfd import layered_query, layered_run_stream
from repro.serve.engine import SketchFleetEngine

S, D, EPS, N, BLOCK, R = 8, 16, 1 / 4, 64, 8, 16.0
TICKS = 40                      # 320 time units: five windows


def _rows(seed=0):
    """(TICKS, S, BLOCK, D) rows: a few strong directions shared by all
    users plus noise, squared norms in [1, R], half of the units idle."""
    g = np.random.default_rng(seed)
    basis = g.normal(size=(3, D))
    X = g.normal(size=(TICKS, S, BLOCK, 3)) @ basis * 2.0
    X += g.normal(size=(TICKS, S, BLOCK, D))
    X /= np.linalg.norm(X, axis=-1, keepdims=True)
    X *= np.sqrt(g.uniform(1.0, R, size=X.shape[:-1] + (1,)))
    X[g.random(X.shape[:-1]) < 0.5] = 0.0
    return X.astype(np.float32)


def _run(X, fault=None):
    eng = SketchFleetEngine("time-dsfd", d=D, streams=S, eps=EPS, window=N,
                            block=BLOCK, R=R)
    if fault is not None:
        fault(eng)
    users = np.repeat(np.arange(S), BLOCK)
    for slab in X:
        assert eng.submit_many(users, slab.reshape(-1, D)).all()
        assert eng.step() == S * BLOCK
    return eng


def _window(X, users, t):
    """The rows the users' windows hold at clock ``t`` (stamps 1..t)."""
    A = X[:, users].transpose(1, 0, 2, 3).reshape(len(users), -1, D)
    ts = np.arange(1, A.shape[1] + 1)
    return A[:, (ts > t - N) & (ts <= t)].reshape(-1, D).astype(np.float64)


def _errors(eng, X):
    """Relative covariance errors ‖A_WᵀA_W − BᵀB‖₂ / ‖A_W‖_F² of every
    user's answer and of a few cohorts' answers."""
    out = []
    cohorts = [[u] for u in range(S)] + [list(range(S)), [1, 2, 5]]
    for users in cohorts:
        B = (eng.query_user(users[0]) if len(users) == 1
             else eng.query_cohort(users)).astype(np.float64)
        AW = _window(X, users, eng.t)
        G = AW.T @ AW
        out.append(np.linalg.norm(G - B.T @ B, 2) / np.sum(AW * AW))
    return np.array(out)


def _single_stream_gap(eng, X):
    """Largest relative gap between each user's fleet answer and the
    single-stream ``layered_run_stream`` on the same rows and stamps."""
    cfg = eng.base.meta["cfg"]
    worst = 0.0
    for u in range(S):
        rows = X[:, u].reshape(-1, D)
        ts = np.arange(1, rows.shape[0] + 1, dtype=np.int32)
        state, _ = layered_run_stream(cfg, rows, ts)
        want = np.asarray(layered_query(cfg, state, eng.t), np.float64)
        got = eng.query_user(u).astype(np.float64)
        H = want.T @ want
        worst = max(worst, np.linalg.norm(got.T @ got - H, 2)
                    / np.linalg.norm(H, 2))
    return worst


def _check(eng, X):
    return (_errors(eng, X) <= 4 * EPS).all() and (
        _single_stream_gap(eng, X) < 1e-4)


def _drop_half_the_rows(eng):
    update = eng.fleet.update_block

    def half(state, rows, ts):
        keep = np.ones((1, rows.shape[1], 1), np.float32)
        keep[:, rows.shape[1] // 2:] = 0.0
        return update(state, rows * keep, ts)

    eng.fleet = eng.fleet._replace(update_block=half)


@pytest.fixture(scope="module")
def run():
    X = _rows()
    return _run(X), X


def test_windows_fill_expire_and_swap(run):
    eng, _ = run
    assert eng.t == TICKS * BLOCK > 4 * N
    c = eng.counters()
    assert c["swaps"] > 0 and c["left"] > 0 and c["dumped"] > 0
    # the layer stack is on the path: several layers are chosen
    assert len(c["query_layer"]) == eng.base.meta["cfg"].levels
    assert sum(c["query_layer"]) == S


def test_user_and_cohort_answers_meet_the_time_dsfd_bound(run):
    eng, X = run
    err = _errors(eng, X)
    assert (err <= 4 * EPS).all(), err


def test_fleet_equals_the_single_stream_run(run):
    eng, X = run
    assert _single_stream_gap(eng, X) < 1e-4


@pytest.mark.parametrize("fault", [None, _drop_half_the_rows],
                         ids=["sound", "dropped_rows"])
def test_check_passes_sound_and_fails_dropped_rows(run, fault):
    X = run[1]
    eng = run[0] if fault is None else _run(X, fault)
    assert _check(eng, X) == (fault is None)


def test_fleet_state_is_one_layer_stack_per_stream(run):
    eng, _ = run
    levels = eng.base.meta["cfg"].levels
    for leaf in jax.tree.leaves(eng.state):
        assert leaf.shape[:2] == (S, levels)
