"""The FD rotate/shrink primitive ``_svd_rows`` takes its rows and spectrum
from one ``eigh`` of the smaller Gram matrix.  It must agree with the
general SVD it replaced, kept here verbatim as the reference, both on
single buffers and through whole DS-FD and Time-DS-FD streams."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import fd
from repro.core.dsfd import dsfd_run_stream, make_config
from repro.core.seq_dsfd import (layered_init, layered_query_rows,
                                 layered_update, make_time_config)


def _svd_rows_reference(buf):
    """SVD of the buffer; returns (rows = Σ·Vᵀ padded to buf.shape, σ²)."""
    m, d = buf.shape
    # full_matrices=False: S has r = min(m, d) entries, Vt is (r, d).
    _, s, vt = jnp.linalg.svd(buf, full_matrices=False)
    rows = s[:, None] * vt                               # (r, d), sorted desc
    if rows.shape[0] < m:                                # pad when d < m
        rows = jnp.concatenate(
            [rows, jnp.zeros((m - rows.shape[0], d), buf.dtype)], axis=0)
        s = jnp.concatenate([s, jnp.zeros((m - s.shape[0],), s.dtype)])
    return rows, s * s


# (m, d, nonzero rows): the cells' buffers (16, 64), (64, 300) and
# (16, 500); d < m, of full rank and rank deficient; an all-zero buffer;
# ℓ−1 nonzero rows (rank deficient)
_BUFFERS = [(16, 64, 16), (64, 300, 64), (16, 500, 16), (16, 8, 16),
            (16, 8, 5), (16, 64, 0), (16, 64, 7)]


@pytest.mark.parametrize("m,d,k", _BUFFERS,
                         ids=[f"{m}x{d}-rows{k}" for m, d, k in _BUFFERS])
def test_gram_rows_match_the_svd(m, d, k):
    rng = np.random.default_rng(m * 1000 + d + k)
    buf = np.zeros((m, d), np.float32)
    buf[:k] = rng.normal(size=(k, d)) + 2.0 * rng.normal(size=(1, d))
    buf = jnp.asarray(buf)

    rows, s2 = jax.jit(fd._svd_rows)(buf)
    _, s2_ref = _svd_rows_reference(buf)
    assert rows.shape == buf.shape and s2.shape == (m,)
    assert bool(jnp.all(jnp.isfinite(rows))) and bool(jnp.all(s2 >= 0.0))

    fro = float(jnp.sum(buf * buf))
    gram_gap = np.linalg.norm(np.asarray(rows.T @ rows - buf.T @ buf), 2)
    assert gram_gap <= 1e-5 * fro
    assert float(jnp.max(jnp.abs(s2 - s2_ref))) <= 1e-5 * float(s2_ref[0])
    assert bool(jnp.all(jnp.diff(s2) <= 0.0))
    norms = jnp.sum(rows * rows, axis=1)
    assert float(jnp.max(jnp.abs(norms - s2))) <= 1e-5 * float(s2_ref[0])
    # one sign a row, so that programs over one stream agree
    assert bool(jnp.all(-jnp.min(rows, axis=1) <= jnp.max(rows, axis=1)))

    ell = m // 2
    shrunk, _, _ = jax.jit(fd.fd_shrink, static_argnums=1)(buf, ell)
    assert not bool(jnp.any(shrunk[ell - 1:]))
    if k == 0:
        assert not bool(jnp.any(rows))


def _drifting_rows(rng, n, d, zero_share=0.0):
    lead = rng.normal(size=(4, d))
    A = rng.normal(size=(n, d)) + 3.0 * lead[(np.arange(n) * 4) // n]
    A /= np.linalg.norm(A, axis=1, keepdims=True)
    A[rng.random(n) < zero_share] = 0.0
    return A.astype(np.float32)


def _dsfd_case(d, eps, window, n, seed):
    A = _drifting_rows(np.random.default_rng(seed), n, d)
    cfg = make_config(d, eps, window)

    def run():
        state, outs = jax.jit(lambda a: dsfd_run_stream(
            cfg, a, query_every=window // 2))(jnp.asarray(A))
        live = [np.asarray(state.main.snap_valid).sum(),
                np.asarray(state.aux.snap_valid).sum()]
        return np.asarray(outs), np.asarray(live), np.arange(1, n + 1)

    return A, window, window // 2, run


def _time_dsfd_case(seed):
    """3 layers (θ = 1, 2, 4), rows of ‖a‖² in [0.25, 1.5], a third idle."""
    d, eps, window, n = 64, 1 / 8, 32, 600
    rng = np.random.default_rng(seed)
    A = _drifting_rows(rng, n, d, zero_share=1 / 3)
    A *= np.sqrt(rng.uniform(0.25, 1.5, size=(n, 1))).astype(np.float32)
    cfg = make_time_config(d, eps, window, 1.0)
    assert cfg.levels == 3
    q = window // 2

    def stream(rows):
        def step(state, inp):
            t, row = inp
            state = layered_update(cfg, state, row, t)
            out = jax.lax.cond(
                jnp.mod(t, q) == 0,
                lambda s: layered_query_rows(cfg, s, t),
                lambda s: jnp.zeros((cfg.base.cap + cfg.base.m, d),
                                    jnp.float32),
                state)
            return state, out
        ts = jnp.arange(1, n + 1, dtype=jnp.int32)
        return jax.lax.scan(step, layered_init(cfg), (ts, rows))

    def run():
        state, outs = jax.jit(stream)(jnp.asarray(A))
        live = np.concatenate([np.asarray(state.main.snap_valid).sum(1),
                               np.asarray(state.aux.snap_valid).sum(1)])
        return np.asarray(outs), live, np.arange(1, n + 1)

    return A, window, q, run


_STREAMS = {
    "dsfd-d64-eps8": lambda: _dsfd_case(64, 1 / 8, 256, 1200, 1),
    "dsfd-d300-eps32": lambda: _dsfd_case(300, 1 / 32, 320, 700, 2),
    "time-dsfd-3-layers": lambda: _time_dsfd_case(3),
}


@pytest.mark.parametrize("case", list(_STREAMS))
def test_streams_through_the_gram_primitive_match_the_svd(monkeypatch, case):
    """Every query's rows hold the same covariance, to 1e-4 of the window's
    ‖A_W‖_F², and the same snapshots stay live, whichever primitive runs
    under ``fd_shrink`` and ``fd_rotate``."""
    A, window, q, run = _STREAMS[case]()
    got, live, ts = run()
    # traces are cached across calls: drop them whenever the primitive
    # changes, so that neither run, nor a later test, is served the other's
    jax.clear_caches()
    try:
        with monkeypatch.context() as mp:
            mp.setattr(fd, "_svd_rows", _svd_rows_reference)
            want, live_ref, _ = run()
    finally:
        jax.clear_caches()

    assert not np.array_equal(got, want), "the reference did not run"
    assert np.array_equal(live, live_ref) and live.sum() > 0
    asked = 0
    for i, t in enumerate(ts):
        if t % q:
            continue
        aw = A[max(t - window, 0):t]
        fro = float(np.sum(aw.astype(np.float64) ** 2))
        gap = np.linalg.norm(got[i].T.astype(np.float64) @ got[i]
                             - want[i].T.astype(np.float64) @ want[i], 2)
        assert gap < 1e-4 * fro, (t, gap / fro)
        asked += 1
    assert asked >= 4
