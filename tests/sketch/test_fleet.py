"""Fleet scale-out: every placement of the fleet lift (``vmap_streams``,
``shard_streams`` over a mesh, and over a topology) must be a pure layout
change — same per-stream states as sequential execution — and
``merge_streams`` must tree-reduce a fleet to one global-window sketch obeying the additive FD
bound.  The 2-fake-device SPMD path runs in a subprocess (XLA device count
is fixed at import time); CI job 2 additionally runs this whole file under
``XLA_FLAGS=--xla_force_host_platform_device_count=2``.
"""

import os
import subprocess
import sys
import textwrap

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.sketch.api import (make_sketch, merge_streams, shard_streams,
                              vmap_streams)


def _streams(S, n, d, seed=3):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(S, n, d)).astype(np.float32)
    X /= np.linalg.norm(X, axis=2, keepdims=True)
    return X


def _rel_err(AW, B):
    B = np.asarray(B, np.float64)
    M = AW.T.astype(np.float64) @ AW - B.T @ B
    return float(np.linalg.norm(M, 2) / np.sum(AW * AW))


def _place(sk, S, placement):
    if placement == "none":
        return vmap_streams(sk, S)
    if placement == "mesh":                   # whatever devices exist
        return shard_streams(sk, S)
    from repro.parallel.topology import FleetTopology
    return shard_streams(sk, S, topology=FleetTopology(
        S, num_processes=1, process_id=0))


@pytest.mark.parametrize("name", ["dsfd", "seq-dsfd", "time-dsfd"])
@pytest.mark.parametrize("placement", ["none", "mesh", "topology"])
def test_shard_streams_matches_sequential_reference(placement, name):
    """Every placement of the one fleet lift is a pure layout change: each
    stream's rows and space equal that stream's run alone through the
    base sketch."""
    S, n, d, N, R = 8, 64, 8, 24, 4.0
    X = _streams(S, n, d)
    hyper = {}
    if name != "dsfd":
        # the layered variants take ‖a‖² ∈ [1, R]; unit rows would put
        # every window energy exactly on a swap threshold, where one ulp
        # of summation order decides the swap
        hyper = {"R": R}
        norms = np.random.default_rng(4).uniform(1.0, R, size=(S, n, 1))
        X = (X * np.sqrt(norms)).astype(np.float32)
    ts = jnp.arange(1, n + 1, dtype=jnp.int32)
    sk = make_sketch(name, d=d, eps=1 / 4, window=N, **hyper)
    sh = _place(sk, S, placement)
    if placement != "none":
        assert sh.meta["devices"] == jax.device_count()
    state = sh.update_block(sh.init(), jnp.asarray(X), ts)
    rows_v = np.asarray(sh.query_rows(state, n))
    fs = sh.space(state)                      # FleetSpace: per-stream + total
    space_v = np.asarray(fs.per_stream)
    assert int(fs.total) == int(space_v.sum()) + fs.cache_rows
    for s in range(S):
        st_s = sk.update_block(sk.init(), jnp.asarray(X[s]), ts)
        np.testing.assert_allclose(
            rows_v[s], np.asarray(sk.query_rows(st_s, n)), atol=1e-5)
        assert int(space_v[s]) == int(sk.space(st_s))


def test_shard_streams_rejects_bad_inputs():
    with pytest.raises(ValueError):           # host backend
        shard_streams(make_sketch("lmfd", d=8, eps=0.25, window=32), 4)
    if jax.device_count() > 1:                # indivisible fleet size
        sk = make_sketch("dsfd", d=8, eps=1 / 4, window=16)
        with pytest.raises(ValueError):
            shard_streams(sk, jax.device_count() + 1)


@pytest.mark.parametrize("S", [4, 5])          # even + odd tree-reduction
def test_merge_streams_global_window_sketch(S):
    n, d, N = 90, 10, 30
    X = _streams(S, n, d, seed=7)
    ts = jnp.arange(1, n + 1, dtype=jnp.int32)
    sk = make_sketch("dsfd", d=d, eps=1 / 4, window=N)
    fleet = vmap_streams(sk, S)
    state = fleet.update_block(fleet.init(), jnp.asarray(X), ts)
    with pytest.warns(DeprecationWarning):     # deprecated alias, still exact
        g = merge_streams(fleet, state, n)
    union = np.vstack([X[s, n - N:] for s in range(S)])
    # additive mergeability: S-way union stays within S× the per-stream
    # bound plus the tree-compression term — 4ε relative is generous here
    err = _rel_err(union, sk.query(g, n))
    assert err <= 4 * (1 / 4), f"global sketch rel err {err:.3f}"
    # the merged state is a live base-variant state: it keeps absorbing
    g2 = sk.update(g, jnp.asarray(X[0, 0]), n + 1)
    assert int(sk.space(g2)) >= 1


def test_merge_streams_rejects_non_fleet():
    sk = make_sketch("dsfd", d=8, eps=1 / 4, window=16)
    with pytest.warns(DeprecationWarning), pytest.raises(ValueError):
        merge_streams(sk, sk.init(), 1)


_TWO_DEVICE_SCRIPT = textwrap.dedent("""
    import numpy as np, jax, jax.numpy as jnp
    from repro.sketch.api import make_sketch, merge_streams, shard_streams
    assert jax.device_count() == 2, jax.device_count()
    S, n, d, N = 4, 40, 6, 16
    rng = np.random.default_rng(0)
    X = rng.normal(size=(S, n, d)).astype(np.float32)
    X /= np.linalg.norm(X, axis=2, keepdims=True)
    ts = jnp.arange(1, n + 1, dtype=jnp.int32)
    sk = make_sketch("dsfd", d=d, eps=0.25, window=N)
    sh = shard_streams(sk, S)
    state = sh.update_block(sh.init(), jnp.asarray(X), ts)
    rows_v = np.asarray(sh.query_rows(state, n))
    for s in range(S):
        st_s = sk.update_block(sk.init(), jnp.asarray(X[s]), ts)
        np.testing.assert_allclose(
            rows_v[s], np.asarray(sk.query_rows(st_s, n)), atol=1e-5)
    g = merge_streams(sh, state, n)
    assert np.asarray(sk.query(g, n)).shape == (2 * sk.meta["ell"], d)
    print("OK")
""")


def test_shard_streams_two_fake_devices_subprocess():
    """The real SPMD path: 2 forced host devices, shard vs sequential."""
    if int(os.environ.get("XLA_FLAGS", "").count("device_count")):
        pytest.skip("already running under forced device count (CI job 2)")
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               JAX_PLATFORM_NAME="cpu",
               PYTHONPATH=os.pathsep.join(
                   filter(None, [os.environ.get("PYTHONPATH", "")]
                          + [os.path.join(os.path.dirname(__file__),
                                          "..", "..", "src")])))
    res = subprocess.run([sys.executable, "-c", _TWO_DEVICE_SCRIPT],
                         capture_output=True, text=True, timeout=540,
                         env=env)
    assert res.returncode == 0, res.stderr[-3000:]
    assert "OK" in res.stdout
