"""The ingest subsystem (``repro.serve.ingest``) + the rewired
``SketchFleetEngine`` ingest path.

Pins the tick/clock contract — async (double-buffered, prefetched) ingest
is bit-identical to the synchronous assemble-at-dispatch path for the
same interleaving of ``submit`` and ``step`` calls, including across a
mid-stream ``checkpoint`` → ``from_checkpoint`` restore and under a
forced-2-device mesh — plus the ingest-path bug sweep: admission
validation, bounded backpressure, clock-neutral idle ticks, and the
``run(max_ticks)`` budget-exhaustion contract.
"""

import os
import subprocess
import sys
import textwrap

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.serve.engine import SketchFleetEngine
from repro.serve.ingest import (AdmissionQueue, AsyncIngest,
                                IngestBacklogError, SyncIngest,
                                make_pipeline)

S, D, N_WIN, BLOCK = 4, 6, 16, 4


def _rows(n, seed=0, users=S, d=D):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(users, n, d)).astype(np.float32)
    X /= np.linalg.norm(X, axis=2, keepdims=True)
    return X


def _engine(**kw):
    defaults = dict(d=D, streams=S, eps=0.25, window=N_WIN, block=BLOCK)
    defaults.update(kw)
    return SketchFleetEngine("dsfd", **defaults)


def _feed(eng, X, rows):
    for i in range(rows):
        for u in range(X.shape[0]):
            eng.submit(u, X[u, i])


# ---------------------------------------------------------------------------
# Admission validation (fail at submit, not inside the jitted update)
# ---------------------------------------------------------------------------


def test_submit_rejects_out_of_range_user():
    eng = _engine()
    row = np.zeros(D, np.float32)
    with pytest.raises(ValueError, match=rf"user id -1 .*\[0, {S}\)"):
        eng.submit(-1, row)
    with pytest.raises(ValueError, match=rf"user id {S} .*\[0, {S}\)"):
        eng.submit(S, row)
    with pytest.raises(ValueError, match="must be an integer"):
        eng.submit(1.5, row)
    with pytest.raises(ValueError, match="must be an integer"):
        eng.submit(True, row)
    assert eng.backlog == 0                      # nothing was admitted


def test_submit_rejects_malformed_rows():
    eng = _engine()
    with pytest.raises(ValueError, match=rf"shape \(3,\), expected a "
                                         rf"\({D},\) float32"):
        eng.submit(0, np.zeros(3, np.float32))
    with pytest.raises(ValueError, match=r"shape \(2, 6\)"):
        eng.submit(0, np.zeros((2, D), np.float32))
    with pytest.raises(ValueError, match="not real-numeric"):
        eng.submit(0, np.array(["x"] * D))
    with pytest.raises(ValueError, match="not real-numeric"):
        eng.submit(0, np.zeros(D, np.complex64))
    assert eng.backlog == 0
    # numeric but non-f32 input is admitted and cast (old behavior)
    assert eng.submit(0, np.arange(D, dtype=np.int64))
    assert eng.submit(0, np.ones(D, np.float64))
    assert eng.backlog == 2


def test_numpy_int_user_ids_are_accepted():
    eng = _engine()
    assert eng.submit(np.int32(1), np.zeros(D, np.float32))
    assert eng.backlog == 1


# ---------------------------------------------------------------------------
# Bounded backpressure
# ---------------------------------------------------------------------------


def test_submit_backpressure_defers_at_capacity():
    X = _rows(4)
    eng = _engine(queue_capacity=3)
    assert eng.submit(0, X[0, 0]) is True
    assert eng.submit(0, X[0, 1]) is True
    assert eng.submit(1, X[1, 0]) is True
    assert eng.submit(2, X[2, 0]) is False       # deferred, not grown
    assert eng.backlog == 3
    eng.step()                                   # drain frees capacity
    assert eng.submit(2, X[2, 0]) is True


def test_staged_rows_still_fill_the_capacity_bound():
    """Rows held in the async pipeline's staged slab left the FIFOs but
    are still admitted-not-ingested: capacity must count them, or the
    documented bound silently inflates by up to S*block rows."""
    X = _rows(3 * BLOCK)
    cap = 2 * S * BLOCK
    eng = _engine(queue_capacity=cap)
    _feed(eng, X, 2 * BLOCK)                     # exactly at capacity
    assert eng.submit(0, X[0, 0]) is False
    eng.step()                                   # ingests S*BLOCK, stages
    assert eng.pipe.staged_rows == S * BLOCK     # ...the other S*BLOCK
    assert eng.backlog == S * BLOCK
    accepted = sum(eng.submit(u, X[u, i])
                   for i in range(2 * BLOCK) for u in range(S))
    assert accepted == cap - S * BLOCK           # staged rows held space
    assert eng.backlog == cap
    eng.run()                                    # everything still drains
    assert eng.backlog == 0


def test_queue_capacity_validation():
    with pytest.raises(ValueError, match="capacity"):
        AdmissionQueue(S, D, capacity=0)


def test_capacity_bound_ignores_staged_unwind(tmp_path):
    """flush_to_queue/load bypass the bound — those rows were admitted
    once; a full queue must never lose them."""
    X = _rows(8)
    eng = _engine(queue_capacity=S * BLOCK)
    _feed(eng, X, BLOCK)
    eng.step()                                   # async path stages a slab
    eng.checkpoint(str(tmp_path))                # staged rows unwound
    res = SketchFleetEngine.from_checkpoint(str(tmp_path))
    assert res.backlog == eng.backlog
    assert res.queue.capacity == S * BLOCK


# ---------------------------------------------------------------------------
# Idle ticks are clock-neutral (the window-expiry regression)
# ---------------------------------------------------------------------------


def test_idle_step_is_clock_neutral():
    X = _rows(N_WIN)
    eng = _engine()
    _feed(eng, X, N_WIN)
    eng.run()
    t0, q0 = eng.t, eng.query_user(0)
    assert np.abs(q0).sum() > 0                  # live window content
    for _ in range(10):                          # idle polling loop
        assert eng.step() == 0
    assert eng.t == t0, "idle ticks advanced the fleet clock"
    np.testing.assert_array_equal(eng.query_user(0), q0)


def test_idle_polling_no_longer_expires_window_content():
    """The old behavior: enough idle step() calls aged live snapshots out
    of the window.  Polling must now be free; explicit advance_time=True
    restores wall-clock aging and visibly expires snapshot content."""
    X = _rows(2 * N_WIN)                         # enough rows to snapshot
    eng = _engine()
    _feed(eng, X, 2 * N_WIN)
    eng.run()
    q0 = eng.query_user(0)
    assert np.abs(q0).sum() > 0
    for _ in range(2 * N_WIN // BLOCK + 2):
        eng.step()                               # clock-neutral polls
    np.testing.assert_array_equal(eng.query_user(0), q0)

    t_before = eng.t
    for _ in range(2 * N_WIN // BLOCK + 2):      # opt-in: idle ticks age
        assert eng.step(advance_time=True) == 0
    assert eng.t == t_before + (2 * N_WIN // BLOCK + 2) * BLOCK
    # the whole window aged past every ingested row: snapshot content
    # expired (only the bounded FD residual buffer may survive — DS-FD
    # cannot expire it row-by-row, by design)
    assert not np.array_equal(eng.query_user(0), q0), \
        "advance_time idle ticks did not age the window"


def test_advance_time_matches_legacy_always_advancing_engine():
    """step(advance_time=True) on every tick reproduces the old shared-
    clock semantics exactly (same state as an engine that ingests the
    same rows with interleaved idle ticks)."""
    X = _rows(2 * BLOCK)
    a = _engine(ingest="sync")
    b = _engine(ingest="sync")
    # a: rows, idle (advancing), rows   b: the same via explicit ts gap
    _feed(a, X, BLOCK)
    a.run()
    a.step(advance_time=True)
    for i in range(BLOCK, 2 * BLOCK):
        for u in range(S):
            a.submit(u, X[u, i])
    a.run()
    _feed(b, X, BLOCK)
    b.run()
    b.step(advance_time=True)
    for i in range(BLOCK, 2 * BLOCK):
        for u in range(S):
            b.submit(u, X[u, i])
    b.run()
    assert a.t == b.t
    np.testing.assert_array_equal(a.query_global(), b.query_global())


# ---------------------------------------------------------------------------
# run(max_ticks) budget exhaustion is loud
# ---------------------------------------------------------------------------


def test_run_raises_on_exhausted_budget():
    X = _rows(10)
    eng = _engine()
    _feed(eng, X, 10)
    with pytest.raises(IngestBacklogError, match="did NOT complete") as ei:
        eng.run(max_ticks=1)
    assert ei.value.remaining == eng.backlog > 0


def test_run_warn_mode_returns_ticks_and_keeps_backlog():
    X = _rows(10)
    eng = _engine()
    _feed(eng, X, 10)
    with pytest.warns(RuntimeWarning, match="did NOT complete"):
        ticks = eng.run(max_ticks=2, on_budget="warn")
    assert ticks == 2 and eng.backlog > 0
    # a completed drain is silent in both modes
    assert eng.run() > 0
    assert eng.backlog == 0
    with pytest.raises(ValueError, match="on_budget"):
        eng.run(on_budget="ignore")


# ---------------------------------------------------------------------------
# The tick/clock contract: async ≡ sync, bit for bit
# ---------------------------------------------------------------------------


def _drive(eng, X, script):
    """Replay a submit/step script: ("rows", i) submits column i to every
    user, ("row", u, i) one row, ("step",) ticks, ("run",) drains."""
    for op in script:
        if op[0] == "rows":
            for u in range(X.shape[0]):
                eng.submit(u, X[u, op[1]])
        elif op[0] == "row":
            eng.submit(op[1], X[op[1], op[2]])
        elif op[0] == "step":
            eng.step()
        elif op[0] == "run":
            eng.run()
    return eng


SCRIPTS = {
    "drain": [("rows", i) for i in range(10)] + [("run",)],
    "interleaved": [("rows", 0), ("step",), ("rows", 1), ("rows", 2),
                    ("step",), ("step",), ("rows", 3), ("run",)],
    # rows submitted AFTER the async pipeline staged a slab — the
    # top-up path: a sync tick would include them, so async must too
    "top-up": [("rows", 0), ("rows", 1), ("step",), ("row", 0, 2),
               ("row", 3, 2), ("step",), ("step",), ("run",)],
    "sparse": [("row", 1, 0), ("step",), ("row", 3, 1), ("row", 1, 1),
               ("step",), ("run",)],
}


@pytest.mark.parametrize("script", sorted(SCRIPTS))
def test_async_ingest_bit_identical_to_sync(script):
    X = _rows(12, seed=3)
    a = _drive(_engine(ingest="sync"), X, SCRIPTS[script])
    b = _drive(_engine(ingest="async"), X, SCRIPTS[script])
    assert a.t == b.t and a.rows_ingested == b.rows_ingested
    assert a.backlog == b.backlog == 0
    for x, y in zip(jax.tree.leaves(a.state), jax.tree.leaves(b.state)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    for u in range(S):
        np.testing.assert_array_equal(a.query_user(u), b.query_user(u))
    np.testing.assert_array_equal(a.query_global(), b.query_global())
    from repro.sketch.query import Cohort
    np.testing.assert_array_equal(a.query_cohort(Cohort.range(1, 3)),
                                  b.query_cohort(Cohort.range(1, 3)))


def test_async_bit_identical_across_mid_stream_restore(tmp_path):
    """The differential acceptance test: sync oracle vs async engine
    checkpointed mid-stream (with rows staged in the pipeline) and
    restored — fleet state and every query answer stay bit-identical."""
    X = _rows(10, seed=7)
    oracle = _engine(ingest="sync")
    victim = _engine(ingest="async")
    for eng in (oracle, victim):
        _feed(eng, X, 10)
        eng.step()
        eng.step()
    assert victim.pipe.staged_rows > 0           # prefetched slab in flight
    assert victim.backlog == oracle.backlog
    victim.checkpoint(str(tmp_path))
    del victim

    resumed = SketchFleetEngine.from_checkpoint(str(tmp_path))
    assert resumed.ingest == "async"
    assert resumed.t == oracle.t
    assert resumed.backlog == oracle.backlog
    assert resumed.rows_ingested == oracle.rows_ingested
    while oracle.backlog:
        oracle.step()
        resumed.step()
    assert resumed.t == oracle.t
    for x, y in zip(jax.tree.leaves(oracle.state),
                    jax.tree.leaves(resumed.state)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    for u in range(S):
        np.testing.assert_array_equal(oracle.query_user(u),
                                      resumed.query_user(u))
    np.testing.assert_array_equal(oracle.query_global(),
                                  resumed.query_global())


def test_checkpoint_unwind_preserves_fifo_order(tmp_path):
    """Staged rows go back to the queue FRONT: restored per-user order is
    exactly submission order."""
    X = _rows(3 * BLOCK, seed=5)
    eng = _engine()
    _feed(eng, X, 3 * BLOCK)
    eng.step()                                   # ingest block 1, stage 2
    assert eng.pipe.staged_rows > 0
    eng.checkpoint(str(tmp_path))
    res = SketchFleetEngine.from_checkpoint(str(tmp_path))
    for u in range(S):
        got = np.stack(list(res.queue.queues[u]))
        np.testing.assert_array_equal(got, X[u, BLOCK:])


# ---------------------------------------------------------------------------
# Prefetched device slabs & the pipeline primitives
# ---------------------------------------------------------------------------


def test_sharded_update_block_accepts_prefetched_device_slab():
    from repro.sketch.api import make_sketch, shard_streams

    sk = make_sketch("dsfd", d=D, eps=0.25, window=N_WIN)
    fleet = shard_streams(sk, S)
    sharding = fleet.meta["slab_sharding"]
    assert sharding is not None
    slab = _rows(BLOCK, seed=2)
    ts = jnp.arange(1, BLOCK + 1, dtype=jnp.int32)
    dev = jax.device_put(slab, sharding)         # the pipeline's prefetch
    s_dev = fleet.update_block(fleet.init(), dev, ts)
    s_np = fleet.update_block(fleet.init(), slab, ts)        # host path
    s_jnp = fleet.update_block(fleet.init(), jnp.asarray(slab), ts)
    for a, b, c in zip(jax.tree.leaves(s_dev), jax.tree.leaves(s_np),
                       jax.tree.leaves(s_jnp)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))


def test_async_pipeline_stages_and_prefetches():
    eng = _engine()
    assert isinstance(eng.pipe, AsyncIngest)
    X = _rows(2 * BLOCK)
    _feed(eng, X, 2 * BLOCK)
    eng.step()
    # the NEXT slab was packed and prefetched while the device consumed
    # the first one; it is an already-placed jax.Array
    assert eng.pipe.staged_rows == S * BLOCK
    staged_dev = eng.pipe._staged[1]
    assert isinstance(staged_dev, jax.Array)
    if eng.fleet.meta.get("slab_sharding") is not None:
        assert staged_dev.sharding == eng.fleet.meta["slab_sharding"]
    eng.run()
    assert eng.pipe.staged_rows == 0 and eng.backlog == 0


def test_topped_up_slab_does_not_pay_a_second_transfer():
    """Steady streaming (submits between every tick) makes every staged
    slab stale; the top-up must hand back a host copy for the dispatch
    to transfer once — sync cost — not re-prefetch a second device
    array on the critical path."""
    X = _rows(BLOCK + 3, seed=19)
    eng = _engine()
    _feed(eng, X, BLOCK + 2)                     # ingest BLOCK, stage 2
    eng.step()
    assert eng.pipe.staged_rows == 2 * S
    for u in range(S):                           # stale-stage the slab
        eng.submit(u, X[u, BLOCK + 2])
    slab, touched, counts, nrows = eng.pipe.next_slab()  # top-up fires
    assert nrows == 3 * S and touched == list(range(S))
    assert counts == [3] * S
    assert isinstance(slab, np.ndarray), \
        "topped-up slab should be a host copy, not a re-prefetched array"
    # it is a *private* copy: repacking the pipeline buffer later must
    # not reach through it
    assert not np.shares_memory(slab, eng.pipe._bufs[0])
    assert not np.shares_memory(slab, eng.pipe._bufs[1])


def test_pending_snapshot_includes_staged_rows():
    X = _rows(2 * BLOCK, seed=23)
    eng = _engine()
    _feed(eng, X, 2 * BLOCK)
    eng.step()
    assert eng.pipe.staged_rows > 0
    snap = eng._pending
    assert sum(len(q) for q in snap) == eng.backlog
    # staged rows dispatch next, so they lead each user's snapshot; the
    # full per-user order is exactly submission order
    for u in range(S):
        np.testing.assert_array_equal(np.stack(list(snap[u])),
                                      X[u, BLOCK:])


def test_idle_step_resets_dispatch_latency():
    X = _rows(1)
    eng = _engine()
    _feed(eng, X, 1)
    eng.run()
    busy = eng.ticks()[-1]
    assert busy["dispatch_end"] - busy["start"] > 0.0
    assert eng.step() == 0                       # idle poll
    idle = eng.ticks()[-1]
    assert idle["step"] == busy["step"] + 1 and idle["rows"] == 0
    assert np.isnan(idle["dispatch_start"]) and np.isnan(idle["dispatch_end"]), \
        "idle tick must not report the previous tick's dispatch latency"


def test_make_pipeline_rejects_unknown_mode():
    q = AdmissionQueue(S, D)
    with pytest.raises(ValueError, match="unknown ingest mode"):
        make_pipeline("threaded", q, block=BLOCK, put=lambda a: a)
    assert isinstance(make_pipeline("sync", q, block=BLOCK,
                                    put=lambda a: a), SyncIngest)


def test_async_buffers_do_not_leak_rows_across_ticks():
    """Buffer reuse: a user touched in tick k with k rows and in tick
    k+2 with fewer rows must not resurrect tick-k rows (dirty-slot
    zeroing)."""
    X = _rows(BLOCK + 1, seed=13)
    a = _engine(ingest="sync")
    b = _engine(ingest="async")
    for eng in (a, b):
        for i in range(BLOCK):                   # full block for user 0
            eng.submit(0, X[0, i])
        eng.step()
        eng.submit(0, X[0, BLOCK])               # then a single row
        eng.step()
        eng.submit(1, X[1, 0])                   # different user, reuse
        eng.step()
        eng.run()
    np.testing.assert_array_equal(a.query_global(), b.query_global())
    for x, y in zip(jax.tree.leaves(a.state), jax.tree.leaves(b.state)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# The 2-forced-device mesh path (CI job 2 runs this whole file under a
# forced-2-device mesh; the subprocess pins it locally too)
# ---------------------------------------------------------------------------


_TWO_DEVICE_DIFF = textwrap.dedent("""
    import numpy as np, jax, tempfile
    from repro.serve.engine import SketchFleetEngine
    assert jax.device_count() == 2, jax.device_count()
    S, d, n = 4, 6, 10
    rng = np.random.default_rng(17)
    X = rng.normal(size=(S, n, d)).astype(np.float32)
    X /= np.linalg.norm(X, axis=2, keepdims=True)
    def fed(mode):
        eng = SketchFleetEngine("dsfd", d=d, streams=S, eps=0.25,
                                window=16, block=4, ingest=mode)
        for i in range(n):
            for u in range(S):
                eng.submit(u, X[u, i])
        eng.step()
        return eng
    a, b = fed("sync"), fed("async")
    with tempfile.TemporaryDirectory() as tmp:
        b.checkpoint(tmp)
        b = SketchFleetEngine.from_checkpoint(tmp)
    while a.backlog:
        a.step(); b.step()
    assert a.t == b.t
    for u in range(S):
        np.testing.assert_array_equal(a.query_user(u), b.query_user(u))
    np.testing.assert_array_equal(a.query_global(), b.query_global())
    print("TWO-DEV-IDENTICAL")
""")


def test_async_ingest_two_forced_devices_subprocess():
    env = dict(
        os.environ,
        XLA_FLAGS="--xla_force_host_platform_device_count=2",
        JAX_PLATFORM_NAME="cpu",
        PYTHONPATH=os.pathsep.join(
            filter(None, [os.environ.get("PYTHONPATH", "")]
                   + [os.path.join(os.path.dirname(__file__),
                                   "..", "..", "src")])))
    res = subprocess.run([sys.executable, "-c", _TWO_DEVICE_DIFF],
                         capture_output=True, text=True, timeout=540,
                         env=env)
    assert res.returncode == 0, res.stderr[-3000:]
    assert "TWO-DEV-IDENTICAL" in res.stdout


# ---------------------------------------------------------------------------
# Batched admission + the vectorized slab packer
# ---------------------------------------------------------------------------


def test_submit_many_matches_per_row_submits():
    """The zero-copy batched packer contract: a ``submit_many`` batch is
    bit-identical — fleet state, clock, queries — to submitting the same
    rows one by one in the same order."""
    X = _rows(3 * BLOCK, seed=5)
    eng_a, eng_b = _engine(), _engine()
    for i in range(2 * BLOCK):                    # per-row path
        for u in range(S):
            eng_a.submit(u, X[u, i])
    users = np.concatenate(
        [np.arange(S, dtype=np.int64)] * (2 * BLOCK))
    rows = np.concatenate(
        [X[:, i] for i in range(2 * BLOCK)], axis=0)
    mask = eng_b.submit_many(users, rows)         # batched path
    assert mask.shape == (users.size,) and mask.all()
    assert eng_a.backlog == eng_b.backlog
    eng_a.run(); eng_b.run()
    # interleave: batch mid-stream between steps
    eng_a.submit(1, X[1, 2 * BLOCK]); eng_a.submit(3, X[3, 2 * BLOCK])
    eng_b.submit_many(np.array([1, 3]), X[[1, 3], 2 * BLOCK])
    eng_a.run(); eng_b.run()
    assert eng_a.t == eng_b.t
    for la, lb in zip(jax.tree.leaves(eng_a.state),
                      jax.tree.leaves(eng_b.state)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
    np.testing.assert_array_equal(eng_a.query_user(1), eng_b.query_user(1))


def test_submit_many_validation_admits_nothing_on_error():
    eng = _engine()
    good = np.zeros((2, D), np.float32)
    with pytest.raises(ValueError, match=rf"user id {S} .*\[0, {S}\)"):
        eng.submit_many(np.array([0, S]), good)
    with pytest.raises(ValueError, match=r"user id -2 "):
        eng.submit_many(np.array([-2, 1]), good)
    with pytest.raises(ValueError, match="1-D integer array"):
        eng.submit_many(np.array([0.5, 1.5]), good)
    with pytest.raises(ValueError, match="1-D integer array"):
        eng.submit_many(np.array([[0], [1]]), good)
    with pytest.raises(ValueError, match=rf"expected \(2, {D}\)"):
        eng.submit_many(np.array([0, 1]), np.zeros((2, D + 1), np.float32))
    with pytest.raises(ValueError, match="not real-numeric"):
        eng.submit_many(np.array([0, 1]), np.zeros((2, D), np.complex64))
    assert eng.backlog == 0                       # nothing was admitted
    assert eng.submit_many(np.array([], np.int64),
                           np.zeros((0, D), np.float32)).size == 0


def test_submit_many_capacity_prefix_accept():
    """At ``queue_capacity`` the longest fitting prefix is admitted and
    the mask says exactly which rows got in (resubmit the rest later)."""
    X = _rows(6, seed=6)
    eng = _engine(queue_capacity=5)
    users = np.zeros((8,), np.int64)
    rows = np.stack([X[0, i % 6] for i in range(8)])
    mask = eng.submit_many(users, rows)
    np.testing.assert_array_equal(mask, [True] * 5 + [False] * 3)
    assert eng.backlog == 5
    mask2 = eng.submit_many(users[:2], rows[:2])  # full → all deferred
    assert not mask2.any() and eng.backlog == 5
    eng.run()
    assert eng.submit_many(users[:2], rows[:2]).all()


def test_submit_many_preserves_per_user_fifo():
    q = AdmissionQueue(S, D)
    r = _rows(4, seed=7)
    q.submit(2, r[2, 0])
    q.submit_many(np.array([2, 0, 2]), np.stack([r[2, 1], r[0, 0], r[2, 2]]))
    buf = np.zeros((S, BLOCK, D), np.float32)
    touched, counts, n = q.take_block(buf, BLOCK)
    assert (touched, counts, n) == ([0, 2], [1, 3], 4)
    np.testing.assert_array_equal(buf[2, :3], r[2, :3])   # FIFO order
    np.testing.assert_array_equal(buf[0, 0], r[0, 0])
    assert q.backlog == 0 and q.live_users() == []


def test_take_block_base_offsets_write_past_existing_rows():
    q = AdmissionQueue(S, D)
    r = _rows(4, seed=8)
    q.submit_many(np.array([1, 1, 1, 3]),
                  np.stack([r[1, 0], r[1, 1], r[1, 2], r[3, 0]]))
    buf = np.zeros((S, BLOCK, D), np.float32)
    base = np.zeros((S,), np.int64)
    base[1] = 2                                   # user 1 already has 2 rows
    base[3] = BLOCK                               # user 3's slot is full
    touched, counts, n = q.take_block(buf, BLOCK, base=base)
    assert (touched, counts, n) == ([1], [2], 2)
    np.testing.assert_array_equal(buf[1, 2], r[1, 0])
    np.testing.assert_array_equal(buf[1, 3], r[1, 1])
    assert np.all(buf[3] == 0)                    # full slot untouched
    assert q.backlog == 2                         # r[1,2] and r[3,0] remain
    assert q.live_users() == [1, 3]               # incremental set correct


def test_push_front_without_headroom_preserves_fifo():
    """push_front when the pool has no consumed prefix to reuse (the
    reallocation path) must still put the rows ahead of queued ones."""
    q = AdmissionQueue(S, D)
    r = _rows(6, seed=9)
    q.submit_many(np.full((3,), 1, np.int64), r[1, :3])
    buf = np.zeros((S, BLOCK, D), np.float32)
    q.take_block(buf, BLOCK)                      # pool compacts to start=0
    q.submit(1, r[1, 3])                          # one queued row
    q.push_front(1, [r[1, 0], r[1, 1]])           # unwind two rows
    users, rows = q.snapshot()
    np.testing.assert_array_equal(users, [1, 1, 1])
    np.testing.assert_array_equal(rows, np.stack([r[1, 0], r[1, 1], r[1, 3]]))


def test_queues_property_is_a_fifo_view():
    q = AdmissionQueue(S, D)
    r = _rows(3, seed=10)
    q.submit_many(np.array([2, 0, 2]), np.stack([r[2, 0], r[0, 0], r[2, 1]]))
    qs = q.queues
    assert [len(x) for x in qs] == [1, 0, 2, 0]
    np.testing.assert_array_equal(np.stack(list(qs[2])), r[2, :2])
    qs[2].clear()                                 # mutating the view...
    assert q.backlog == 3                         # ...does not touch the pool
