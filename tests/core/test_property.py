"""Property-based tests (hypothesis) for the system's core invariants:

* FD:    BᵀB ⪯ AᵀA  and  ‖AᵀA − BᵀB‖₂ ≤ ‖A‖_F²/ℓ  (Ghashami et al.)
* DS-FD: windowed cova-error ≤ 4εN (Theorem 3.1) on arbitrary normalized
  streams; snapshot count ≤ ring capacity (space proof).
* Seq-DS-FD: error ≤ βε‖A_W‖_F² for rows with ‖a‖² ∈ [1, R] (Theorem 4.1).
* Mergeability: FD(A) merged with FD(B) obeys the additive error bound.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.dsfd import (dsfd_run_stream, make_config)
from repro.core.fd import fd_absorb, fd_compress, fd_init
from repro.core.seq_dsfd import make_seq_config
from benchmarks.common import run_layered

jax.config.update("jax_platform_name", "cpu")


def _spec_err(A, B):
    M = A.T.astype(np.float64) @ A.astype(np.float64) \
        - B.T.astype(np.float64) @ B.astype(np.float64)
    return np.linalg.norm(M, 2)


@st.composite
def _matrix(draw, max_n=160, max_d=10):
    n = draw(st.integers(24, max_n))
    d = draw(st.integers(3, max_d))
    seed = draw(st.integers(0, 2**31 - 1))
    rng = np.random.default_rng(seed)
    kind = draw(st.sampled_from(["gauss", "lowrank", "spiked", "onehot"]))
    if kind == "gauss":
        A = rng.normal(size=(n, d))
    elif kind == "lowrank":
        r = draw(st.integers(1, max(d // 2, 1)))
        A = rng.normal(size=(n, r)) @ rng.normal(size=(r, d))
        A += 0.05 * rng.normal(size=(n, d))
    elif kind == "spiked":
        A = rng.normal(size=(n, d))
        A[:, 0] *= 10.0
    else:
        A = np.eye(d)[rng.integers(0, d, n)] + 0.0
        A += 1e-3 * rng.normal(size=(n, d))
    return A.astype(np.float32)


@settings(max_examples=12, deadline=None)
@given(_matrix(), st.integers(2, 8))
def test_fd_spectral_bounds(A, ell):
    n, d = A.shape
    ell = min(ell, d)
    st0 = fd_init(ell, d)
    st1 = fd_absorb(st0, jnp.asarray(A), ell=ell)
    B = np.asarray(st1.buf)
    err = _spec_err(A, B)
    fro2 = float(np.sum(A * A))
    assert err <= fro2 / ell + 1e-3 * fro2
    # BᵀB ⪯ AᵀA: min eig of (AᵀA − BᵀB) ≥ −tol
    M = A.T.astype(np.float64) @ A - B.T.astype(np.float64) @ B
    lam_min = np.linalg.eigvalsh(M).min()
    assert lam_min >= -1e-2 * fro2 / max(n, 1) - 1e-4 * fro2


@settings(max_examples=8, deadline=None)
@given(_matrix(max_n=220), st.sampled_from([0.25, 0.5]))
def test_dsfd_window_error_theorem31(A, eps):
    A = A / np.maximum(np.linalg.norm(A, axis=1, keepdims=True), 1e-9)
    n, d = A.shape
    N = max(n // 3, 8)
    cfg = make_config(d, eps, N, mode="fast")
    _, outs = dsfd_run_stream(cfg, jnp.asarray(A), query_every=max(N // 2, 1))
    outs = np.asarray(outs)
    for i in range(n):
        t = i + 1
        if t % max(N // 2, 1) or t < N:
            continue
        AW = A[t - N: t]
        err = _spec_err(AW, outs[i])
        assert err <= 4 * eps * N * (1 + 1e-3), (t, err, 4 * eps * N)


@settings(max_examples=6, deadline=None)
@given(_matrix(max_n=200), st.integers(0, 10_000))
def test_seq_dsfd_unnormalized_theorem41(A, seed):
    rng = np.random.default_rng(seed)
    R = 16.0
    A = A / np.maximum(np.linalg.norm(A, axis=1, keepdims=True), 1e-9)
    A = A * np.sqrt(rng.uniform(1.0, R, size=(len(A), 1))).astype(np.float32)
    n, d = A.shape
    N = max(n // 3, 8)
    beta = 4.0
    eps = 0.25
    q = max(N // 2, 1)
    queries, _, _ = run_layered(A, eps, N, R, query_every=q, beta=beta)
    for t, B in queries.items():
        if t < N:
            continue
        AW = A[t - N: t]
        fro2 = float(np.sum(AW * AW))
        assert _spec_err(AW, B) <= beta * eps * fro2 * (1 + 1e-3)


@settings(max_examples=10, deadline=None)
@given(_matrix(max_n=120), _matrix(max_n=120), st.integers(3, 6))
def test_fd_mergeable(A, B_mat, ell):
    d = min(A.shape[1], B_mat.shape[1])
    A, B_mat = A[:, :d], B_mat[:, :d]
    ell = min(ell, d)
    sk = fd_compress(jnp.asarray(np.vstack([A, B_mat])), ell)
    both = np.vstack([A, B_mat])
    err = _spec_err(both, np.asarray(sk))
    assert err <= float(np.sum(both * both)) / ell * (1 + 1e-3)


def _check_merge_additive(A, B_mat, eps, R):
    """Additive mergeability at the protocol level (the tentpole bound):

        err(merge(s1, s2)) ≤ err(s1) + err(s2) + ‖B₁;B₂‖_F²/ℓ

    s1 ← stream A, s2 ← stream B (arbitrary split of one logical stream),
    rows rescaled to ‖a‖² ∈ [1, R], no expiry (window ≥ both streams) so
    the exact union covariance is computable."""
    import pytest

    from repro.sketch.api import make_sketch

    d = min(A.shape[1], B_mat.shape[1])
    if d < 2:
        pytest.skip("degenerate width")
    A, B_mat = A[:, :d], B_mat[:, :d]

    def rescale(M, lo_hi_seed):
        rng = np.random.default_rng(lo_hi_seed)
        M = M / np.maximum(np.linalg.norm(M, axis=1, keepdims=True), 1e-9)
        return (M * np.sqrt(rng.uniform(1.0, R, size=(len(M), 1)))
                ).astype(np.float32)

    A, B_mat = rescale(A, 0), rescale(B_mat, 1)
    n1, n2 = len(A), len(B_mat)
    window = 4 * (n1 + n2)                      # no expiry
    sk = make_sketch("dsfd", d=d, eps=eps, window=window)
    ell = sk.meta["ell"]

    s1 = sk.update_block(sk.init(), jnp.asarray(A),
                         np.arange(1, n1 + 1, dtype=np.int32))
    s2 = sk.update_block(sk.init(), jnp.asarray(B_mat),
                         np.arange(n1 + 1, n1 + n2 + 1, dtype=np.int32))
    q1 = np.asarray(sk.query_rows(s1, n1 + n2), np.float64)
    q2 = np.asarray(sk.query_rows(s2, n1 + n2), np.float64)
    merged = sk.merge(s1, s2, n1 + n2)
    q = np.asarray(sk.query(merged, n1 + n2))

    union = np.vstack([A, B_mat])
    budget = (_spec_err(A, q1) + _spec_err(B_mat, q2)
              + (np.sum(q1 * q1) + np.sum(q2 * q2)) / ell)
    err = _spec_err(union, q)
    assert err <= budget * (1 + 1e-3) + 1e-6, (err, budget)


@settings(max_examples=8, deadline=None)
@given(_matrix(max_n=120), _matrix(max_n=120), st.sampled_from([0.25, 0.5]),
       st.sampled_from([1.0, 4.0, 16.0]))
def test_merge_additive_bound(A, B_mat, eps, R):
    """Hypothesis sweep: arbitrary split points + row scales in [1, R]."""
    _check_merge_additive(A, B_mat, eps, R)


@pytest.mark.parametrize("seed,eps,R", [(0, 0.25, 1.0), (1, 0.25, 16.0),
                                        (2, 0.5, 4.0), (3, 0.125, 16.0)])
def test_merge_additive_bound_fixed_seeds(seed, eps, R):
    """The same additive-bound check on pinned draws (split point varies
    with seed) — fixed regression cases beside hypothesis's search."""
    rng = np.random.default_rng(seed)
    n, d = int(rng.integers(40, 160)), int(rng.integers(3, 12))
    k = int(rng.integers(8, n - 8))            # arbitrary split point
    M = rng.normal(size=(n, d)).astype(np.float32)
    _check_merge_additive(M[:k], M[k:], eps, R)


@settings(max_examples=8, deadline=None)
@given(_matrix(max_n=200))
def test_dsfd_space_bound(A):
    """Live snapshots never exceed the ring capacity derived from the
    space proof (Thm 3.1 / 4.1) — the fixed-shape ring never overflows
    silently (cov_start tracks evictions)."""
    from repro.core.dsfd import dsfd_init, dsfd_update
    A = A / np.maximum(np.linalg.norm(A, axis=1, keepdims=True), 1e-9)
    n, d = A.shape
    N = max(n // 4, 6)
    eps = 0.25
    cfg = make_config(d, eps, N)

    @jax.jit
    def run(data):
        def step(state, inp):
            t, row = inp
            state = dsfd_update(cfg, state, row, t)
            live = jnp.sum(state.main.snap_valid)
            return state, live
        ts = jnp.arange(1, n + 1, dtype=jnp.int32)
        return jax.lax.scan(step, dsfd_init(cfg), (ts, data))[1]

    live = np.asarray(run(jnp.asarray(A)))
    assert live.max() <= cfg.cap
