"""Query-plane suite: Cohort algebra, AggTree correctness (bit-exact
against a from-scratch midpoint-split merge fold), the warm-query merge
budget (the acceptance criterion: ≤ 2·log₂S node merges per query over a
1024-stream fleet after warm-up), cache-invalidation soundness, and the
checkpoint rebuild-on-mismatch fallback.  The 2-fake-device SPMD path runs
in a subprocess (XLA device count is fixed at import time).
"""

import math
import os
import subprocess
import sys
import textwrap

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.sketch.api import (ALL, Cohort, FleetSpace, agg_tree, make_sketch,
                              merge_streams, query_cohort, shard_streams,
                              vmap_streams)
from repro.sketch.query import AggTree, as_cohort, full_reduce_streams
from repro.testing import cohort_fold


def _streams(S, n, d, seed=3):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(S, n, d)).astype(np.float32)
    X /= np.linalg.norm(X, axis=2, keepdims=True)
    return X


def _assert_trees_equal(a, b, msg=""):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb), msg
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=msg)


# ---------------------------------------------------------------------------
# Cohort algebra
# ---------------------------------------------------------------------------


def test_cohort_normalization_and_union():
    c = Cohort.of(3, 7, 8, 9) | Cohort.range(0, 2)
    assert c.ranges == ((0, 2), (3, 4), (7, 10))
    assert len(c) == 6 and c.indices() == (0, 1, 3, 7, 8, 9)
    assert 8 in c and 2 not in c
    # adjacency coalesces; overlap merges; order is irrelevant
    assert (Cohort.range(4, 8) | Cohort.range(0, 4)) == Cohort.range(0, 8)
    assert (Cohort.range(0, 6) | Cohort.range(3, 8)) == Cohort.range(0, 8)
    # equal cohorts hash equal (they are cache keys)
    assert hash(Cohort.of(1, 2)) == hash(Cohort.range(1, 3))
    # single-iterable form of .of
    assert Cohort.of([4, 1, 2]) == Cohort.of(1, 2, 4)


def test_cohort_all_semantics():
    assert ALL.is_all
    assert (ALL | Cohort.range(3, 5)).is_all
    assert (Cohort.range(3, 5) | ALL).is_all
    assert ALL.resolve(6) == ((0, 6),)
    assert ALL.indices(4) == (0, 1, 2, 3)
    assert 10 ** 9 in ALL
    with pytest.raises(TypeError):
        len(ALL)                       # unresolved extent
    with pytest.raises(TypeError):
        ALL.indices()                  # must not silently truncate


def test_cohort_rejects_bad_ranges():
    with pytest.raises(ValueError):
        Cohort.range(3, 3)             # empty
    with pytest.raises(ValueError):
        Cohort.range(5, 2)             # inverted
    with pytest.raises(ValueError):
        Cohort.of(-1)                  # negative index
    with pytest.raises(ValueError):
        Cohort.range(4, 9).resolve(8)  # exceeds fleet
    with pytest.raises(ValueError):
        Cohort().resolve(8)            # empty cohort
    assert as_cohort(None) is ALL
    assert as_cohort(3) == Cohort.of(3)
    assert as_cohort(range(2, 5)) == Cohort.range(2, 5)


def test_cohort_adjacent_ranges_coalesce():
    # touching ranges collapse to one — cohorts are values, so the
    # coalesced forms compare/hash equal and share the AggTree cache key
    assert Cohort.range(2, 4) | Cohort.range(4, 6) == Cohort.range(2, 6)
    assert hash(Cohort.range(2, 4) | Cohort.range(4, 6)) \
        == hash(Cohort.range(2, 6))
    assert Cohort.of(3).union(Cohort.of(4)).resolve(8) == ((3, 5),)
    assert (Cohort.range(0, 3) | Cohort.range(2, 5)).resolve(8) == ((0, 5),)
    # non-adjacent ranges stay separate
    assert Cohort.of(1, 3).resolve(8) == ((1, 2), (3, 4))


def test_query_cohort_rejects_empty_and_out_of_range():
    S, n, d = 4, 10, 5
    sk = make_sketch("dsfd", d=d, eps=0.25, window=8)
    fleet = vmap_streams(sk, S)
    state = fleet.update_block(fleet.init(), jnp.asarray(_streams(S, n, d)),
                               jnp.arange(1, n + 1, dtype=jnp.int32))
    with pytest.raises(ValueError, match="empty cohort"):
        query_cohort(fleet, state, Cohort(), n)
    with pytest.raises(ValueError, match="exceeds fleet"):
        query_cohort(fleet, state, Cohort.of(S), n)        # first bad id
    with pytest.raises(ValueError, match="exceeds fleet"):
        query_cohort(fleet, state, Cohort.range(2, S + 1), n)


def test_single_sketch_query_cohort_raises():
    sk = make_sketch("dsfd", d=8, eps=0.25, window=16)
    with pytest.raises(ValueError, match="vmap_streams/shard_streams"):
        sk.query_cohort(sk.init(), ALL, 1)
    with pytest.raises(ValueError, match="fleet"):
        query_cohort(sk, sk.init(), ALL, 1)


# ---------------------------------------------------------------------------
# Correctness: bit-exact vs from-scratch fold, arbitrary fleet sizes
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("S", [5, 6, 8])       # non-power-of-two pinned
@pytest.mark.parametrize("name,hyper", [("dsfd", {}),
                                        ("time-dsfd", {"R": 4.0})])
def test_query_cohort_matches_fold(S, name, hyper):
    n, d, N = 40, 6, 16
    X = _streams(S, n, d, seed=S)
    ts = jnp.arange(1, n + 1, dtype=jnp.int32)
    sk = make_sketch(name, d=d, eps=0.25, window=N, **hyper)
    fleet = vmap_streams(sk, S)
    state = fleet.update_block(fleet.init(), jnp.asarray(X), ts)

    g = query_cohort(fleet, state, ALL, n)
    _assert_trees_equal(
        g, cohort_fold(sk, state, S, [(0, S)], n),
        f"{name} S={S}: query_cohort(ALL) != from-scratch fold")

    rng = np.random.default_rng(17)
    for _ in range(4):                          # random contiguous + composed
        lo = int(rng.integers(0, S - 1))
        hi = int(rng.integers(lo + 1, S + 1))
        cohorts = [Cohort.range(lo, hi)]
        extra = int(rng.integers(0, S))
        cohorts.append(Cohort.range(lo, hi) | Cohort.of(extra))
        for c in cohorts:
            got = query_cohort(fleet, state, c, n)
            _assert_trees_equal(
                got, cohort_fold(sk, state, S, c.resolve(S), n),
                f"{name} S={S}: cohort {c} != from-scratch fold")


def test_merge_streams_is_deprecated_query_cohort_all_alias():
    S, n, d = 5, 30, 6
    X = _streams(S, n, d)
    ts = jnp.arange(1, n + 1, dtype=jnp.int32)
    sk = make_sketch("dsfd", d=d, eps=0.25, window=12)
    fleet = vmap_streams(sk, S)
    state = fleet.update_block(fleet.init(), jnp.asarray(X), ts)
    # deprecated: the warning must name the replacement call
    with pytest.warns(DeprecationWarning, match="query_cohort"):
        merged = merge_streams(fleet, state, n)
    _assert_trees_equal(merged, query_cohort(fleet, state, ALL, n))
    # and the alias is correct for arbitrary (non-power-of-two) S: the
    # pad-free midpoint split, pinned against the independent oracle
    _assert_trees_equal(merged, cohort_fold(sk, state, S, [(0, S)], n))


def test_merge_streams_warning_points_at_the_caller():
    """stacklevel=2 pin: the DeprecationWarning must be attributed to the
    CALLER's file (this test), not to api.py — otherwise `python -W
    error::DeprecationWarning` tracebacks and log filters point users at
    library internals instead of their own call site."""
    import warnings

    S, n, d = 3, 8, 4
    sk = make_sketch("dsfd", d=d, eps=0.25, window=12)
    fleet = vmap_streams(sk, S)
    state = fleet.update_block(fleet.init(), jnp.asarray(_streams(S, n, d)),
                               jnp.arange(1, n + 1, dtype=jnp.int32))
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        merge_streams(fleet, state, n)
    dep = [w for w in caught if issubclass(w.category, DeprecationWarning)]
    assert len(dep) == 1
    assert dep[0].filename == __file__, dep[0].filename


def test_query_cohort_sharded_fleet_matches_vmap():
    """shard_streams is a layout change; its query plane must answer
    identically to the vmap fleet's (whatever local device count)."""
    S, n, d = 6, 32, 5
    X = _streams(S, n, d, seed=9)
    ts = jnp.arange(1, n + 1, dtype=jnp.int32)
    sk = make_sketch("dsfd", d=d, eps=0.25, window=12)
    vf = vmap_streams(sk, S)
    shf = shard_streams(sk, S)
    sv = vf.update_block(vf.init(), jnp.asarray(X), ts)
    ss = shf.update_block(shf.init(), jnp.asarray(X), ts)
    for c in (ALL, Cohort.range(1, 5), Cohort.of(0, 3, 5)):
        _assert_trees_equal(query_cohort(shf, ss, c, n),
                            query_cohort(vf, sv, c, n),
                            f"shard vs vmap cohort {c}")


def test_full_reduce_streams_arbitrary_size_and_bound():
    """The uncached baseline stays correct for odd fleets (pad-free tail
    carry) and still obeys the additive union error bound."""
    S, n, d, N = 7, 60, 8, 20
    X = _streams(S, n, d, seed=5)
    ts = jnp.arange(1, n + 1, dtype=jnp.int32)
    sk = make_sketch("dsfd", d=d, eps=0.25, window=N)
    fleet = vmap_streams(sk, S)
    state = fleet.update_block(fleet.init(), jnp.asarray(X), ts)
    g = full_reduce_streams(fleet, state, n)
    B = np.asarray(sk.query(g, n), np.float64)
    union = np.vstack([X[s, n - N:] for s in range(S)]).astype(np.float64)
    err = np.linalg.norm(union.T @ union - B.T @ B, 2) / np.sum(union * union)
    assert err <= 4 * 0.25, f"full_reduce rel err {err:.3f}"


# ---------------------------------------------------------------------------
# The acceptance criterion: warm merge budget over a 1024-stream fleet
# ---------------------------------------------------------------------------


def test_warm_cohort_query_merge_budget_1024_streams():
    S, n, d, N = 1024, 12, 6, 8
    X = _streams(S, n, d, seed=2)
    ts = jnp.arange(1, n + 1, dtype=jnp.int32)
    sk = make_sketch("dsfd", d=d, eps=0.5, window=N)
    fleet = vmap_streams(sk, S)
    state = fleet.update_block(fleet.init(), jnp.asarray(X), ts)
    tree = agg_tree(fleet)

    # cold full build: exactly S-1 node merges, every internal node cached
    g = query_cohort(fleet, state, ALL, n)
    assert tree.merges == S - 1
    assert tree.cached_nodes == S - 1

    budget = 2 * int(math.log2(S))              # the stated per-query bound
    rng = np.random.default_rng(0)
    for _ in range(8):
        lo = int(rng.integers(0, S - 1))
        hi = int(rng.integers(lo + 1, S + 1))
        before = tree.merges
        query_cohort(fleet, state, Cohort.range(lo, hi), n)
        spent = tree.merges - before
        assert spent <= budget, \
            f"[{lo},{hi}): {spent} node merges > 2·log2(S) = {budget}"
        # a repeated identical query is free (result memo)
        before = tree.merges
        query_cohort(fleet, state, Cohort.range(lo, hi), n)
        assert tree.merges == before

    # warm whole-fleet aggregate is free, and still the exact fold answer
    before = tree.merges
    g2 = query_cohort(fleet, state, ALL, n)
    assert tree.merges == before
    _assert_trees_equal(g, g2)
    lo = 900                                    # spot-check exactness warm
    c = Cohort.range(lo, lo + 24)
    _assert_trees_equal(
        query_cohort(fleet, state, c, n),
        cohort_fold(sk, state, S, c.resolve(S), n),
        "warm cohort answer != from-scratch fold")


# ---------------------------------------------------------------------------
# Invalidation soundness
# ---------------------------------------------------------------------------


def test_unannounced_state_change_resets_cache():
    S, n, d = 8, 20, 5
    X = _streams(S, n, d, seed=1)
    ts = jnp.arange(1, n + 1, dtype=jnp.int32)
    sk = make_sketch("dsfd", d=d, eps=0.25, window=12)
    fleet = vmap_streams(sk, S)
    state = fleet.update_block(fleet.init(), jnp.asarray(X), ts)
    query_cohort(fleet, state, ALL, n)
    tree = agg_tree(fleet)
    assert tree.cached_nodes == S - 1 and tree.resets == 0

    ts2 = jnp.arange(n + 1, 2 * n + 1, dtype=jnp.int32)
    state2 = fleet.update_block(state, jnp.asarray(X), ts2)
    got = query_cohort(fleet, state2, Cohort.range(2, 7), 2 * n)
    assert tree.resets == 1                     # wholesale, sound
    _assert_trees_equal(
        got, cohort_fold(sk, state2, S, ((2, 7),), 2 * n),
        "post-reset answer != from-scratch fold on the new state")


def test_advance_dirties_only_touched_paths():
    S, n, d = 8, 20, 5
    X = _streams(S, n, d, seed=6)
    ts = jnp.arange(1, n + 1, dtype=jnp.int32)
    sk = make_sketch("dsfd", d=d, eps=0.25, window=12)
    fleet = vmap_streams(sk, S)
    state = fleet.update_block(fleet.init(), jnp.asarray(X), ts)
    tree = agg_tree(fleet)
    tree.query(state, ALL, n)
    assert sorted(tree._nodes) == [(0, 2), (0, 4), (0, 8), (2, 4), (4, 6),
                                   (4, 8), (6, 8)]

    ts2 = jnp.arange(n + 1, n + 2, dtype=jnp.int32)
    state2 = fleet.update_block(
        state, jnp.asarray(_streams(S, 1, d, seed=7)), ts2)
    tree.advance(state2, touched=[3])
    # only stream 3's root-to-leaf path is gone
    assert sorted(tree._nodes) == [(0, 2), (4, 6), (4, 8), (6, 8)]
    assert tree.resets == 0                     # announced, not a reset
    got = tree.query(state2, ALL, n + 1)
    _assert_trees_equal(
        got, cohort_fold(sk, state2, S, ((0, S),), n + 1),
        "post-advance answer != from-scratch fold")

    # superseded-tag GC: a later query retags only its own path; the next
    # advance drops nodes whose tag the forward-moving clock left behind
    tree.query(state2, Cohort.range(0, 2), n + 2)      # (0,2) now tag n+2
    state3 = fleet.update_block(
        state2, jnp.asarray(_streams(S, 1, d, seed=8)),
        jnp.arange(n + 2, n + 3, dtype=jnp.int32))
    tree.advance(state3, touched=[7])
    assert sorted(tree._nodes) == [(0, 2)], sorted(tree._nodes)


def test_aggtree_rejects_host_backend_and_bad_size():
    with pytest.raises(ValueError, match="JAX-backed"):
        AggTree(make_sketch("lmfd", d=8, eps=0.25, window=16), 4)
    with pytest.raises(ValueError, match="< 1"):
        AggTree(make_sketch("dsfd", d=8, eps=0.25, window=16), 0)


def test_fleet_space_reports_per_stream_total_and_cache():
    S, n, d = 6, 24, 5
    X = _streams(S, n, d, seed=4)
    ts = jnp.arange(1, n + 1, dtype=jnp.int32)
    sk = make_sketch("dsfd", d=d, eps=0.25, window=12)
    fleet = vmap_streams(sk, S)
    state = fleet.update_block(fleet.init(), jnp.asarray(X), ts)

    sp = fleet.space(state)
    assert isinstance(sp, FleetSpace)
    per = np.asarray(sp.per_stream)
    assert per.shape == (S,)
    assert sp.cache_rows == 0                   # no aggregate queries yet
    assert int(sp.total) == int(per.sum())

    query_cohort(fleet, state, ALL, n)          # warm the tree
    sp2 = fleet.space(state)
    assert sp2.cache_rows > 0
    assert int(sp2.total) == int(per.sum()) + sp2.cache_rows
    # each cached node is a compressed base state: ≤ 2ℓ live rows
    assert sp2.cache_rows <= (S - 1) * 2 * sk.meta["ell"]


# ---------------------------------------------------------------------------
# Persistence: state_dict round-trip + rebuild-on-mismatch fallback
# ---------------------------------------------------------------------------


def test_aggtree_state_dict_roundtrip_and_mismatch_fallback():
    S, n, d = 6, 20, 5
    X = _streams(S, n, d, seed=8)
    ts = jnp.arange(1, n + 1, dtype=jnp.int32)
    sk = make_sketch("dsfd", d=d, eps=0.25, window=12)
    fleet = vmap_streams(sk, S)
    state = fleet.update_block(fleet.init(), jnp.asarray(X), ts)
    tree = AggTree(sk, S)
    g = tree.query(state, ALL, n)
    meta, arrays = tree.state_dict()
    assert meta["streams"] == S and len(meta["nodes"]) == S - 1

    fresh = AggTree(sk, S)
    assert fresh.load_state_dict(meta, arrays, state)
    assert fresh.cached_nodes == S - 1
    _assert_trees_equal(fresh.query(state, ALL, n), g)
    assert fresh.merges == 0                    # answered fully from cache

    # corrupted arrays (missing leaf) → cold cache, not a crash
    broken = dict(arrays)
    broken.pop(sorted(broken)[0])
    fb = AggTree(sk, S)
    assert not fb.load_state_dict(meta, broken, state)
    assert fb.cached_nodes == 0
    _assert_trees_equal(fb.query(state, ALL, n), g)   # rebuilt lazily

    # wrong-shape leaf → same fallback
    bad = {k: (v if i else np.zeros((1, 1), v.dtype))
           for i, (k, v) in enumerate(sorted(arrays.items()))}
    fb2 = AggTree(sk, S)
    assert not fb2.load_state_dict(meta, bad, state)
    assert fb2.cached_nodes == 0

    # absent meta (pre-query-plane checkpoint) → cold cache
    fb3 = AggTree(sk, S)
    assert not fb3.load_state_dict(None, {}, state)
    assert fb3.cached_nodes == 0


# ---------------------------------------------------------------------------
# The 2-fake-device SPMD path
# ---------------------------------------------------------------------------


_TWO_DEVICE_QUERY_SCRIPT = textwrap.dedent("""
    import numpy as np, jax, jax.numpy as jnp
    from repro.sketch.api import (ALL, Cohort, make_sketch, query_cohort,
                                  shard_streams)
    assert jax.device_count() == 2, jax.device_count()
    S, n, d, N = 6, 30, 5, 12
    rng = np.random.default_rng(0)
    X = rng.normal(size=(S, n, d)).astype(np.float32)
    X /= np.linalg.norm(X, axis=2, keepdims=True)
    ts = jnp.arange(1, n + 1, dtype=jnp.int32)
    sk = make_sketch("dsfd", d=d, eps=0.25, window=N)
    sh = shard_streams(sk, S)
    state = sh.update_block(sh.init(), jnp.asarray(X), ts)
    jm = jax.jit(lambda a, b, t: sk.merge(a, b, t))
    def fold(lo, hi):
        if hi - lo == 1:
            return jax.tree.map(lambda x: x[lo], state)
        mid = (lo + hi) // 2
        return jm(fold(lo, mid), fold(mid, hi), jnp.asarray(n, jnp.int32))
    for c, ref in ((ALL, fold(0, S)), (Cohort.range(3, 6), fold(3, 6))):
        got = query_cohort(sh, state, c, n)
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    print("OK")
""")


def test_query_cohort_two_fake_devices_subprocess():
    if int(os.environ.get("XLA_FLAGS", "").count("device_count")):
        pytest.skip("already running under forced device count (CI job 2)")
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               JAX_PLATFORM_NAME="cpu",
               PYTHONPATH=os.pathsep.join(
                   filter(None, [os.environ.get("PYTHONPATH", "")]
                          + [os.path.join(os.path.dirname(__file__),
                                          "..", "..", "src")])))
    res = subprocess.run([sys.executable, "-c", _TWO_DEVICE_QUERY_SCRIPT],
                         capture_output=True, text=True, timeout=540,
                         env=env)
    assert res.returncode == 0, res.stderr[-3000:]
    assert "OK" in res.stdout
