"""DS-FD sliding-window correctness: Theorem 3.1 (error ≤ 4εN), space bound
(live snapshots ≤ 2/ε + O(1)), and cross-mode agreement."""

import numpy as np
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.dsfd import make_config, dsfd_run_stream
from repro.core.errors import cova_error_gram, window_gram_np

RNG = np.random.default_rng(0)


def _streams(n, d, rng):
    """Three canonical stream families (iid / piecewise directions / spike)."""
    A0 = rng.normal(size=(n, d)).astype(np.float32)
    A0 /= np.linalg.norm(A0, axis=1, keepdims=True)

    dirs = rng.normal(size=(8, d))
    dirs /= np.linalg.norm(dirs, axis=1, keepdims=True)
    A1 = np.zeros((n, d), np.float32)
    for i in range(n):
        v = dirs[(i // (n // 8)) % 8] + 0.05 * rng.normal(size=d)
        A1[i] = v / np.linalg.norm(v)

    A2 = np.zeros((n, d), np.float32)
    A2[: n // 3] = dirs[0]
    A2[n // 3:] = dirs[1]
    return {"iid": A0, "piecewise": A1, "spike": A2}


def _worst_rel(A, cfg, eps, N, q=50):
    _, outs = dsfd_run_stream(cfg, jnp.asarray(A), query_every=q)
    outs = np.asarray(outs)
    worst = 0.0
    for i in range(outs.shape[0]):
        t = i + 1
        if t % q:
            continue
        G = window_gram_np(A, t, N)
        e = float(cova_error_gram(jnp.asarray(G), jnp.asarray(outs[i])))
        worst = max(worst, e / (eps * min(t, N)))
    return worst


@pytest.mark.parametrize("mode", ["fast", "exact", "krylov"])
@pytest.mark.parametrize("stream", ["iid", "piecewise", "spike"])
def test_theorem_3_1_error_bound(mode, stream):
    n, d, N, eps = 1500, 12, 300, 1 / 6
    A = _streams(n, d, np.random.default_rng(42))[stream]
    cfg = make_config(d, eps, N, mode=mode)
    worst = _worst_rel(A, cfg, eps, N)
    assert worst <= 4.0, f"cova-err {worst:.2f} εN breaks Thm 3.1"


def test_space_bound_live_snapshots():
    """Theorem 3.1: at most 2/ε live snapshots at any instant."""
    n, d, N, eps = 2000, 10, 400, 1 / 8
    A = _streams(n, d, np.random.default_rng(7))["piecewise"]
    cfg = make_config(d, eps, N)

    # run in chunks and check the live-snapshot census at many time points
    from repro.core.dsfd import dsfd_init, dsfd_update
    import jax
    state = dsfd_init(cfg)
    step = jax.jit(lambda s, r, t: dsfd_update(cfg, s, r, t))
    for i in range(n):
        state = step(state, jnp.asarray(A[i]), i + 1)
        if (i + 1) % 100 == 0:
            live = int(np.sum(np.asarray(state.main.snap_valid)))
            assert live <= 2 / eps + 2, f"live snapshots {live} > 2/ε"


def test_window_forgetting():
    """Energy fully outside the window must not dominate the answer."""
    d, N, eps = 8, 200, 1 / 4
    v0 = np.zeros(d, np.float32); v0[0] = 1.0
    v1 = np.zeros(d, np.float32); v1[1] = 1.0
    A = np.concatenate([np.tile(v0, (600, 1)), np.tile(v1, (400, 1))])
    cfg = make_config(d, eps, N)
    _, outs = dsfd_run_stream(cfg, jnp.asarray(A.astype(np.float32)),
                              query_every=100)
    B = np.asarray(outs)[-1]          # t = 1000, window = pure v1
    G = B.T @ B
    # old direction v0 must carry ≤ 4εN energy; live direction ≈ N
    assert G[0, 0] <= 4 * eps * N + 1e-3
    assert abs(G[1, 1] - N) <= 4 * eps * N + 1e-3


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 2**31 - 1),
       ellpow=st.integers(2, 3),
       dpow=st.integers(3, 4))
def test_dsfd_bound_property(seed, ellpow, dpow):
    """Property: Theorem 3.1 holds on random piecewise-rank-1 streams."""
    d = 2 ** dpow
    eps = 1.0 / 2 ** ellpow
    N, n = 160, 800
    rng = np.random.default_rng(seed)
    A = _streams(n, d, rng)["piecewise"]
    cfg = make_config(d, eps, N)
    assert _worst_rel(A, cfg, eps, N, q=80) <= 4.0


def test_modes_agree_roughly():
    """fast vs exact vs krylov: same bound class, similar answers."""
    n, d, N, eps = 900, 10, 300, 1 / 5
    A = _streams(n, d, np.random.default_rng(3))["piecewise"]
    outs = {}
    for mode in ("fast", "exact", "krylov"):
        cfg = make_config(d, eps, N, mode=mode)
        _, o = dsfd_run_stream(cfg, jnp.asarray(A), query_every=300)
        outs[mode] = np.asarray(o)[-1]
    g = {k: v.T @ v for k, v in outs.items()}
    scale = np.linalg.norm(g["exact"], 2)
    assert np.linalg.norm(g["fast"] - g["exact"], 2) <= 0.5 * scale + 1e-3
    assert np.linalg.norm(g["krylov"] - g["exact"], 2) <= 0.5 * scale + 1e-3


def _loop_dump_sorted_rows(sk, rows, nrows, now, theta):
    """The snapshot dump as a masked loop of ring appends: the oracle the
    one-store ``_dump_sorted_rows`` must match bit for bit."""
    import jax
    from repro.core.dsfd import _ring_append

    m = rows.shape[0]
    with jax.named_scope("dsfd.dump"):
        norms = jnp.sum(rows * rows, axis=1)
        # sorted ⇒ prefix
        ndump = jnp.sum((norms >= theta).astype(jnp.int32))

        def body(j, sk):
            def do(sk):
                s = jnp.where(j == 0, sk.last_t + 1, now)
                return _ring_append(sk, rows[j], s, now)
            return jax.lax.cond(j < ndump, do, lambda sk: sk, sk)

        sk = jax.lax.fori_loop(0, m, body, sk)

        kept = jnp.roll(rows, -ndump, axis=0)
        nkeep = jnp.maximum(nrows - ndump, 0)
        kept = jnp.where(jnp.arange(m)[:, None] < nkeep, kept, 0.0)
        sig1 = jnp.sum(kept[0] * kept[0])
        return sk._replace(buf=kept, nbuf=nkeep.astype(jnp.int32),
                           sig1=sig1)


def _fleet_run(cfg, rows, thetas, swap_energy):
    """(S, T, d) rows through the vmapped ``dsfd_update``, one θ a step."""
    import jax
    from repro.core.dsfd import dsfd_init, dsfd_update

    ts = jnp.arange(1, rows.shape[1] + 1, dtype=jnp.int32)

    def stream(rows):
        def step(state, inp):
            t, row, theta = inp
            return dsfd_update(cfg, state, row, t, theta=theta,
                               swap_energy=swap_energy), None
        return jax.lax.scan(step, dsfd_init(cfg), (ts, rows, thetas))[0]

    return jax.jit(jax.vmap(stream))(rows)


_DUMP_CASES = ["d64-eps8", "d300-eps32", "small-ring-wraps", "ndump-0-and-m"]


def _dump_case(name):
    """(cfg, rows, θ per step, swap energy) of one exactness case."""
    rng = np.random.default_rng(_DUMP_CASES.index(name))

    def rows_with_drift(S, T, d, zero_share=0.0):
        lead = rng.normal(size=(S, 1, d))
        A = rng.normal(size=(S, T, d)) + 3.0 * lead
        A /= np.linalg.norm(A, axis=2, keepdims=True)
        A[rng.random((S, T)) < zero_share] = 0.0
        return jnp.asarray(A, jnp.float32)

    if name == "d64-eps8":
        cfg = make_config(64, 1 / 8, 48)
        rows = rows_with_drift(3, 160, 64, zero_share=0.1)
        return cfg, rows, jnp.full((160,), 48 / 8, jnp.float32), None
    if name == "d300-eps32":
        cfg = make_config(300, 1 / 32, 96)
        rows = rows_with_drift(2, 140, 300)
        return cfg, rows, jnp.full((140,), 96 / 32, jnp.float32), None
    if name == "small-ring-wraps":
        # θ far below a row's energy and no swaps: the 12-slot ring wraps
        # many times, every wrap evicting live snapshots
        cfg = make_config(16, 1 / 4, 10**6, beta=1e9)
        rows = rows_with_drift(3, 240, 16, zero_share=0.2)
        return cfg, rows, jnp.full((240,), 0.5, jnp.float32), 1e9
    if name == "ndump-0-and-m":
        # exact mode: θ huge for m−1 steps (nothing dumps), then tiny on
        # the step that fills the buffer, which dumps all m rows
        cfg = make_config(16, 1 / 4, 10**6, mode="exact")
        T = 4 * cfg.m
        rows = jnp.asarray(rng.normal(size=(2, T, 16)), jnp.float32)
        fill = (np.arange(1, T + 1) % cfg.m) == 0
        return cfg, rows, jnp.asarray(np.where(fill, 1e-6, 1e30),
                                      jnp.float32), 1e9
    raise KeyError(name)


@pytest.mark.parametrize("case", _DUMP_CASES)
def test_ring_store_dump_matches_the_append_loop(monkeypatch, case):
    """The one masked store of ``_dump_sorted_rows`` leaves every leaf of
    the vmapped fleet state bit for bit as the loop of ring appends does."""
    import jax
    from repro.core import dsfd

    cfg, rows, thetas, swap = _dump_case(case)
    got = _fleet_run(cfg, rows, thetas, swap)
    monkeypatch.setattr(dsfd, "_dump_sorted_rows", _loop_dump_sorted_rows)
    want = _fleet_run(cfg, rows, thetas, swap)

    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        assert np.array_equal(np.asarray(g), np.asarray(w)), path
    appended = int(np.max(np.asarray(want.main.snap_next)))
    assert appended > 0, "the case dumped nothing"
    if case == "small-ring-wraps":
        assert appended > 10 * cfg.cap
    if case == "ndump-0-and-m":
        assert appended == 4 * cfg.m


def test_ring_too_small_for_a_dump_is_refused():
    """A dump writes up to m = 2ℓ slots at once, so a ring of fewer slots
    is refused at trace time rather than overwritten out of order."""
    import dataclasses
    from repro.core.dsfd import dsfd_init, dsfd_update

    cfg = dataclasses.replace(make_config(16, 1 / 4, 64), cap=7)
    with pytest.raises(ValueError, match="7 slots"):
        dsfd_update(cfg, dsfd_init(cfg), jnp.ones((16,), jnp.float32), 1)
