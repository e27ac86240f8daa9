"""Seq-DS-FD / Time-DS-FD (Theorems 4.1, Corollary 5.1): error ≤ βε‖A_W‖_F²,
level selection, idle ticks, heavy-row bypass."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.seq_dsfd import (make_seq_config, make_time_config,
                                 layered_run_stream, layered_init,
                                 layered_update, layered_select)
from repro.core.errors import cova_error_gram

BETA = 4.0


def _eval(A, ts, cfg, eps, N, q=100):
    state, outs = layered_run_stream(cfg, jnp.asarray(A), jnp.asarray(ts),
                                     query_every=q)
    outs = np.asarray(outs)
    worst = 0.0
    for i in range(outs.shape[0]):
        t = int(ts[i])
        if t % q != 0 or (i + 1 < len(ts) and int(ts[i + 1]) == t):
            continue
        in_win = (ts >= t - N + 1) & (ts <= t)
        AW = A[in_win]
        G = AW.T @ AW
        fro = max(float(np.sum(AW * AW)), 1e-9)
        e = float(cova_error_gram(jnp.asarray(G), jnp.asarray(outs[i])))
        worst = max(worst, e / (BETA * eps * fro))
    return worst, state


def test_seq_unnormalized_bound():
    rng = np.random.default_rng(11)
    n, d, N, eps, R = 3000, 16, 400, 1 / 8, 64.0
    A = rng.normal(size=(n, d)).astype(np.float32)
    A /= np.linalg.norm(A, axis=1, keepdims=True)
    scale = np.exp(rng.uniform(0, np.log(np.sqrt(R)), size=(n, 1)))
    scale[rng.random(n) < 0.02] = np.sqrt(R)
    A = (A * scale).astype(np.float32)
    cfg = make_seq_config(d, eps, N, R)
    worst, _ = _eval(A, np.arange(1, n + 1), cfg, eps, N)
    assert worst <= 1.0, f"Seq-DS-FD error {worst:.2f}·βε‖A_W‖² exceeds Thm 4.1"


def test_seq_reduces_to_dsfd_when_R1():
    cfg = make_seq_config(16, 1 / 8, 300, R=1.0)
    assert cfg.levels == 1


def test_time_based_with_idle_and_bursts():
    rng = np.random.default_rng(13)
    d, N, eps, R = 16, 300, 1 / 8, 16.0
    n = 2000
    ts = np.cumsum(rng.geometric(0.4, size=n))          # gaps → idle periods
    burst_at = rng.choice(n, size=20, replace=False)
    ts[burst_at] = ts[np.maximum(burst_at - 1, 0)]      # duplicates → bursts
    ts = np.sort(ts)
    A = rng.normal(size=(n, d)).astype(np.float32)
    A /= np.linalg.norm(A, axis=1, keepdims=True)
    A *= np.exp(rng.uniform(0, np.log(np.sqrt(R)), size=(n, 1)))
    A = A.astype(np.float32)
    cfg = make_time_config(d, eps, N, R)
    worst, _ = _eval(A, ts, cfg, eps, N)
    assert worst <= 1.0, f"Time-DS-FD error {worst:.2f}·βε‖A_W‖² breaks Cor 5.1"


def test_level_selection_adapts_to_energy():
    """Low-energy windows should answer from low levels, high-energy from
    higher ones (Figure 2 semantics)."""
    d, N, eps, R = 8, 200, 1 / 4, 256.0
    cfg = make_seq_config(d, eps, N, R)
    state = layered_init(cfg)
    v = np.zeros(d, np.float32); v[0] = 1.0
    upd = jax.jit(lambda s, r, t: layered_update(cfg, s, r, t))

    # phase 1: unit-norm rows → low energy
    for t in range(1, 2 * N):
        state = upd(state, jnp.asarray(v), t)
    j_low = int(layered_select(cfg, state, 2 * N - 1))

    # phase 2: heavy rows (‖a‖² = R) → high energy flux
    w = v * np.sqrt(R)
    for t in range(2 * N, 4 * N):
        state = upd(state, jnp.asarray(w.astype(np.float32)), t)
    j_high = int(layered_select(cfg, state, 4 * N - 1))
    assert j_high > j_low, (j_low, j_high)


def test_heavy_row_bypass_is_lossless():
    """Rows with ‖a‖² ≥ θ_j are snapshotted verbatim at every level j where
    they are heavy (Algorithm 6 lines 4-6) — zero error contribution."""
    d, N, eps, R = 8, 100, 1 / 4, 64.0
    cfg = make_seq_config(d, eps, N, R)
    state = layered_init(cfg)
    rng = np.random.default_rng(5)
    rows = []
    for t in range(1, 80):
        r = rng.normal(size=d)
        r = (r / np.linalg.norm(r) * (np.sqrt(R) if t % 7 == 0 else 1.0))
        rows.append(r.astype(np.float32))
        state = layered_update(cfg, state, jnp.asarray(rows[-1]), t)
    A = np.stack(rows)
    from repro.core.seq_dsfd import layered_query_rows
    B = np.asarray(layered_query_rows(cfg, state, 79))
    G = A.T @ A
    err = float(cova_error_gram(jnp.asarray(G), jnp.asarray(B)))
    assert err <= BETA * eps * np.sum(A * A)


# Algorithm 7's cover test: the window is [max(now − N + 1, 1), now]

FINDING_COV = [416, 415, 379, 440, 406, 341, 231] + [1] * 13


def _with_cov_start(cfg, cov):
    state = layered_init(cfg)
    main = state.main._replace(cov_start=jnp.asarray(cov, jnp.int32))
    return state._replace(main=main)


def test_select_before_the_window_fills_takes_the_lowest_lossless_layer():
    """RAIL's shape (d=500, ε=1/8, N=50,000, R=64: 20 layers) at t=480:
    layers 0-6 have lost rows, layers 7-19 none.  The window is the whole
    stream so far, which layer 7 covers; the top layer is not needed."""
    cfg = make_time_config(500, 1 / 8, 50_000, 64.0)
    assert cfg.levels == 20
    state = _with_cov_start(cfg, FINDING_COV)
    assert int(layered_select(cfg, state, 480)) == 7
    # a layer that lost nothing covers at every clock before N
    assert int(layered_select(cfg, state, 50_000)) == 7
    # nothing lossless: the top layer, as before
    assert int(layered_select(cfg, _with_cov_start(cfg, [2] * 20), 480)) == 19


@pytest.mark.parametrize("now", [50_001, 50_340, 50_416, 50_500, 90_000])
def test_select_after_the_window_fills_is_unchanged(now):
    """Past N the window starts at now − N + 1 > 1: the lowest layer
    with cov_start ≤ now − N + 1, else the top one."""
    cfg = make_time_config(500, 1 / 8, 50_000, 64.0)
    cov = np.asarray(FINDING_COV) + np.arange(20) * 997
    state = _with_cov_start(cfg, cov)
    ok = np.flatnonzero(cov <= now - 50_000 + 1)
    want = int(ok[0]) if ok.size else 19
    assert int(layered_select(cfg, state, now)) == want


def test_select_on_a_stream_before_its_window_fills():
    """Heavy rows fill and evict the low layers' rings long before N: the
    query answers from the lowest layer whose cov_start is still 1, and
    that layer's answer meets the bound over every row so far."""
    d, N, eps, R = 8, 4096, 1 / 4, 64.0
    cfg = make_time_config(d, eps, N, R)
    rng = np.random.default_rng(3)
    n = 300
    A = rng.normal(size=(n, d)).astype(np.float32)
    A /= np.linalg.norm(A, axis=1, keepdims=True)
    A *= np.sqrt(rng.uniform(1.0, 16.0, size=(n, 1))).astype(np.float32)
    ts = np.arange(1, n + 1)
    state, _ = layered_run_stream(cfg, jnp.asarray(A), jnp.asarray(ts))
    cov = np.asarray(state.main.cov_start)
    assert cov[0] > 1 and (cov == 1).any()
    j = int(layered_select(cfg, state, n))
    assert j == int(np.flatnonzero(cov == 1)[0]) < cfg.levels - 1
    from repro.core.seq_dsfd import layered_query_rows
    B = np.asarray(layered_query_rows(cfg, state, n))
    err = float(cova_error_gram(jnp.asarray(A.T @ A), jnp.asarray(B)))
    assert err <= BETA * eps * np.sum(A * A)
