"""On-chip smoke run of the DS-FD fleet engine (``SketchFleetEngine``).

    python chip_smoke.py             # one TPU chip: phases A, B and C
    python chip_smoke.py --chips 4   # four TPU chips: the sharded phase only

Everything goes through the engine's user-facing calls (``submit_many``,
``step``, ``query_user``, ``query_cohort``, ``query_interval``,
``anomalies``, ``checkpoint`` / ``from_checkpoint``) and is checked
against plain numpy references.

* Phase A-full — the main path at a real size: ``"dsfd"`` at the engine
  defaults (d=64, ε=1/8, window=1024, block=8, async ingest) over 32,768
  per-user streams on one chip, for two ticks.  Each sampled user's
  ``query_user`` must be within 4ε relative covariance error of the exact
  float64 window Gram; a ``query_cohort`` across the whole stream range
  must equal a from-scratch midpoint merge fold of the same users' states
  (``repro.testing.cohort_fold``) and be within 4ε of their exact union
  Gram.  Its tick time is why the phases below run at ``TICK_STREAMS``
  streams; the cut and its reason are printed before them.
* Phase A — the same for two windows (256 ticks), so snapshots expire and
  the main and aux sketches swap.
* Phase B — the fused Pallas kernel: phase A's configuration with
  ``mode="krylov", use_pallas=True`` for one window.  The compiled fleet
  program must contain a ``tpu_custom_call``; the same 4ε checks apply.
* Phase C — the other planes: scoring at the engine's default
  thresholds flags exactly the one injected anomalous user;
  ``query_interval`` over retired ticks equals a from-scratch fold of the
  raw rows through the documented dyadic schedule
  (``repro.testing.IntervalOracle``) and is within 4ε of the interval's
  exact Gram; ``checkpoint`` → ``from_checkpoint`` answers ``query_user``
  / ``query_cohort`` bit-identically.
* ``--chips 4`` — phase A's configuration sharded over four chips.
  A4-full runs 4 × 32,768 streams for two ticks with A-full's checks (the
  cohort spans all four devices) and peak bytes for each chip.  Then, at
  a cut size, two windows beside a one-chip engine fed the same rows for
  streams [0, S/4): ``query_user`` must be bit-identical.  A-full and
  A4-full both print a digest of the same 64 users' answers in
  [0, 32,768), so the two runs' records show whether one chip and four
  agree at full size.

Rows are seeded: each user has a dominant direction holding 3/4 of every
row's energy (it changes every window-length epoch), plus isotropic
noise, and every row has unit norm.  The users' directions share a
fleet-wide component, so a cohort's union has a dominant direction too.
An all-zero or stale answer then breaks every 4ε bound, and each check
prints the error such an answer would have beside the bound.

Lines before the last are the record: compile seconds, persistent-cache
hits, steady seconds per tick, rows per second, device bytes in use and
peak, and every measured error beside its bound.  The last line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": N}}``.
The script exits non-zero, without that line, when JAX finds no TPU,
when ``REPRO_KERNEL_LOWERING`` would move the kernel off the chip, or
when any check fails.  It starts no child process.

The compile cache lives where ``JAX_COMPILATION_CACHE_DIR`` says, and
otherwise in ``<repo>/.jax_cache``.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import shutil
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent

STREAMS = 32_768            # one-chip fleet size (compiled program ~12.3 GB)
# The fleet update cost 1.8 ms per stream per tick on a v5e at a few
# hundred streams and 2.7 ms at 32,768 (measured while a snapshot dump
# was a loop of ring appends), so the phases that run for windows are
# cut to a stream count whose ticks fit the 20-minute limit of a run
# with room to spare.  Widths are never cut.
TICK_STREAMS = 256          # phases A, B, C (C was planned at 4,096)
FOUR_CHIP_STREAMS = 64      # per chip, for --chips 4's windowed pass
FULL_TICKS = 2              # ticks of the full-size run
A_TICKS = 256               # two windows
D, EPS, WINDOW, BLOCK = 64, 1 / 8, 1024, 8      # the engine defaults
SAMPLED = 64                # users checked per phase
DOMINANT = 0.75             # share of each row's energy on the user's direction
SHARED = 0.8                # share of that direction on the fleet-wide one
VARIANTS = 8                # distinct noise slabs per epoch


class SmokeFailure(RuntimeError):
    """A check of the smoke run failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def report(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


# ---------------------------------------------------------------------------
# Seeded traffic
# ---------------------------------------------------------------------------


class Traffic:
    """Seeded unit-norm rows for every stream, one ``(S, block, d)`` slab
    per engine tick.

    Row ``j`` of user ``u`` in tick ``k`` is ``√a·dir[e, u] + √(1−a)·n``
    renormalized, where ``e = k // epoch`` picks the user's dominant
    direction and ``n`` is isotropic noise drawn from one of ``VARIANTS``
    seeded slabs (``k % VARIANTS``), so building a slab costs one mix of
    cached arrays instead of fresh random draws for every row.
    ``dir[e, u]`` is ``√b·c[e] + √(1−b)·g[e, u]`` renormalized: a
    fleet-wide direction ``c`` and the user's own ``g``.  Every draw is
    row-major over streams, so a smaller fleet's traffic is a prefix of a
    larger one's."""

    def __init__(self, streams: int, *, seed: int, epoch: int):
        self.S, self.epoch, self.seed = int(streams), int(epoch), int(seed)
        self._e = None              # epoch whose directions are cached
        self._dirs = None
        self._slabs: dict = {}      # noise variant -> that epoch's slab

    def slab(self, tick: int):
        import numpy as np

        e, v = tick // self.epoch, tick % VARIANTS
        if e != self._e:
            def unit(x):
                return x / np.linalg.norm(x, axis=-1, keepdims=True)

            g = unit(np.random.default_rng((self.seed, 1, e)).standard_normal(
                (self.S, D), dtype=np.float32))
            c = unit(np.random.default_rng((self.seed, 5, e)).standard_normal(
                D, dtype=np.float32))
            dirs = (np.float32(np.sqrt(SHARED)) * c
                    + np.float32(np.sqrt(1.0 - SHARED)) * g)
            self._e, self._dirs, self._slabs = e, unit(dirs), {}
        rows = self._slabs.get(v)
        if rows is None:
            noise = np.random.default_rng((self.seed, 2, v)).standard_normal(
                (self.S, BLOCK, D), dtype=np.float32) / np.float32(np.sqrt(D))
            rows = (np.float32(np.sqrt(DOMINANT)) * self._dirs[:, None, :]
                    + np.float32(np.sqrt(1.0 - DOMINANT)) * noise)
            rows /= np.linalg.norm(rows, axis=2, keepdims=True)
            self._slabs[v] = rows
        return rows


def sample_users(streams: int, seed: int, n: int = SAMPLED):
    """``n`` distinct seeded users spread over ``[0, streams)``: one from
    each of ``n`` equal strata."""
    import numpy as np

    rng = np.random.default_rng((seed, 3))
    edges = np.linspace(0, streams, n + 1).astype(np.int64)
    return np.array([rng.integers(lo, hi) for lo, hi in
                     zip(edges[:-1], edges[1:])], np.int64)


# ---------------------------------------------------------------------------
# Independent references
# ---------------------------------------------------------------------------


def rel_cova_error(gram, B) -> float:
    """‖G − BᵀB‖₂ / tr(G), in float64 (``core/errors.py``'s metric)."""
    import numpy as np

    B = np.asarray(B, np.float64)
    return float(np.linalg.norm(gram - B.T @ B, 2) / np.trace(gram))


def zero_answer_error(gram) -> float:
    """The relative covariance error an all-zero sketch would have."""
    import numpy as np

    return float(np.linalg.norm(gram, 2) / np.trace(gram))


# ---------------------------------------------------------------------------
# Measurement helpers
# ---------------------------------------------------------------------------


class CompileLog:
    """Backend compile seconds and persistent-cache hits/misses, from
    JAX's monitoring events, read as deltas around each phase."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        event = "/jax/core/compile/backend_compile_duration"

        def on_duration(name, secs, **_):
            if name == event:
                self.seconds += secs

        def on_event(name, **_):
            if name == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif name == "/jax/compilation_cache/cache_misses":
                self.misses += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snapshot(self):
        return self.seconds, self.hits, self.misses

    def since(self, snap) -> dict:
        s, h, m = snap
        return {"compile_s": self.seconds - s, "cache_hits": self.hits - h,
                "cache_misses": self.misses - m}


def memory(devices) -> dict:
    stats = [d.memory_stats() or {} for d in devices]
    return {"bytes_in_use": [s.get("bytes_in_use") for s in stats],
            "peak_bytes_in_use": [s.get("peak_bytes_in_use") for s in stats]}


def make_mesh(devices):
    from repro.launch.mesh import make_mesh as mesh

    return mesh((len(devices),), ("streams",), devices=devices)


def drive(eng, traffic, ticks: int, keep_users, label: str, *,
          on_tick=None, streams=None):
    """Feed ``ticks`` full slabs through ``submit_many`` + ``step``.

    Returns (first-tick seconds, steady seconds per tick, the kept users'
    rows as an array ``(len(keep_users), ticks·block, d)``).  The first
    tick holds the compile; the steady time spans ticks 1.. and ends at
    ``jax.block_until_ready(eng.state)``.  ``streams`` feeds only the
    first ``streams`` rows of each slab (a smaller engine fed a prefix
    of the same traffic).  Progress lines after ticks 1 and 33 let a run
    that is cut short still show its tick time."""
    import jax
    import numpy as np

    S = eng.S if streams is None else int(streams)
    users = np.repeat(np.arange(S, dtype=np.int32), BLOCK)
    kept = []
    t0 = t_steady = time.perf_counter()
    t_first = 0.0
    for k in range(ticks):
        if k == 1:
            jax.block_until_ready(eng.state)
            t_steady = time.perf_counter()
            t_first = t_steady - t0
            report("progress", of=label, tick=k, first_tick_s=t_first)
        elif k == 33:
            jax.block_until_ready(eng.state)
            report("progress", of=label, tick=k, steady_s_per_tick=(
                time.perf_counter() - t_steady) / 32)
        slab = traffic.slab(k)[:S]
        if on_tick is not None:
            slab = on_tick(k, slab)
        kept.append(slab[keep_users])
        accepted = eng.submit_many(users, slab.reshape(-1, D))
        check(bool(accepted.all()), f"tick {k}: submit_many deferred rows")
        check(eng.step() == S * BLOCK, f"tick {k}: partial slab")
    jax.block_until_ready(eng.state)
    t_end = time.perf_counter()
    if ticks == 1:
        t_first, steady = t_end - t0, float("nan")
    else:
        steady = (t_end - t_steady) / (ticks - 1)
    return t_first, steady, np.concatenate(kept, axis=1)


def user_checks(eng, users, rows, label: str) -> dict:
    """4ε check of ``query_user`` against the exact float64 window Gram
    for each sampled user; the all-zero answer's error must exceed it."""
    import numpy as np

    from repro.core.errors import window_gram_np

    bound = 4.0 * EPS
    errs, zeros = [], []
    for i, u in enumerate(users):
        gram = window_gram_np(rows[i].astype(np.float64), eng.t, WINDOW)
        errs.append(rel_cova_error(gram, eng.query_user(int(u))))
        zeros.append(zero_answer_error(gram))
    worst = int(np.argmax(errs))
    out = {"users": len(users), "bound": bound, "max_err": max(errs),
           "mean_err": float(np.mean(errs)), "worst_user": int(users[worst]),
           "min_zero_answer_err": min(zeros)}
    check(max(errs) <= bound,
          f"{label}: user {users[worst]} error {max(errs)} > 4ε = {bound}")
    check(min(zeros) > bound,
          f"{label}: an all-zero answer would pass the bound "
          f"({min(zeros)} <= {bound}) — the check could not fail")
    return out


def cohort_check(eng, users, rows, label: str) -> dict:
    """``query_cohort`` over the sampled users equals the from-scratch
    midpoint fold, compressed the same way, to float32 tolerance, and is
    within 4ε of the exact union Gram; the all-zero answer's error must
    exceed that bound."""
    import numpy as np

    from repro.core.errors import window_gram_np
    from repro.sketch.query import Cohort
    from repro.testing import cohort_fold

    cohort = Cohort.of(sorted(int(u) for u in users))
    t0 = time.perf_counter()
    got = eng.query_cohort(cohort)
    t_query = time.perf_counter() - t0
    ref = cohort_fold(eng.base, eng.state, eng.S, cohort.ranges, eng.t)
    want = np.asarray(eng.base.query(ref, eng.t))
    g_got = got.astype(np.float64).T @ got
    g_want = want.astype(np.float64).T @ want
    scale = float(np.abs(g_want).max())
    diff = float(np.abs(g_got - g_want).max()) / scale
    check(diff <= 1e-5, f"{label}: query_cohort differs from the midpoint "
                        f"fold by {diff} (relative, > 1e-5)")
    union = sum(window_gram_np(r.astype(np.float64), eng.t, WINDOW)
                for r in rows)
    bound = 4.0 * EPS
    err, zero = rel_cova_error(union, got), zero_answer_error(union)
    check(err <= bound, f"{label}: query_cohort error {err} against the "
                        f"exact union Gram > 4ε = {bound}")
    check(zero > bound, f"{label}: an all-zero cohort answer would pass the "
                        f"bound ({zero} <= {bound}) — the check could not "
                        "fail")
    return {"cohort_users": len(cohort), "cohort_query_s": t_query,
            "cohort_vs_fold_rel_diff": diff,
            "cohort_bitwise_equal": bool(np.array_equal(got, want)),
            "cohort_union_err": err, "cohort_union_zero_answer_err": zero}


def digest(answers) -> str:
    """SHA-256 of answers' bytes, to compare runs bit for bit."""
    import hashlib

    import numpy as np

    h = hashlib.sha256()
    for a in answers:
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


def timing(S: int, first: float, steady: float) -> dict:
    return {"first_tick_s": first, "steady_s_per_tick": steady,
            "rows_per_s": S * BLOCK / steady}


# ---------------------------------------------------------------------------
# Phases — each takes the stream count and the devices, so it can be
# rehearsed on the CPU at a tiny size
# ---------------------------------------------------------------------------


def phase_a(streams: int, devices, log: CompileLog, *, ticks: int = A_TICKS,
            seed: int = 0, label: str = "A", keep_answers=()):
    """The main path: engine defaults, async ingest.  Returns the record
    and ``query_user`` of each ``keep_answers`` user; the record holds
    their digest."""
    from repro.serve.engine import SketchFleetEngine

    snap = log.snapshot()
    eng = SketchFleetEngine("dsfd", d=D, streams=streams, eps=EPS,
                            window=WINDOW, block=BLOCK, mesh=make_mesh(devices))
    users = sample_users(streams, seed)
    traffic = Traffic(streams, seed=seed, epoch=WINDOW // BLOCK)
    first, steady, rows = drive(eng, traffic, ticks, users, label)
    rec = {"streams": streams, "devices": len(devices), "ticks": ticks,
           "t": eng.t, **timing(streams, first, steady), **log.since(snap),
           **memory(devices)}
    rec.update(user_checks(eng, users, rows, label))
    rec.update(cohort_check(eng, users, rows, label))
    answers = [eng.query_user(int(u)) for u in keep_answers]
    if answers:
        rec["kept_users"] = len(answers)
        rec["kept_answers_sha256"] = digest(answers)
    report(label, **rec)
    del eng
    return rec, answers


def phase_b(streams: int, devices, log: CompileLog, *, ticks: int = 128,
            seed: int = 1) -> dict:
    """The fused Pallas kernel on the fleet path (krylov mode)."""
    import jax
    import numpy as np

    from repro.serve.engine import SketchFleetEngine

    snap = log.snapshot()
    eng = SketchFleetEngine("dsfd", d=D, streams=streams, eps=EPS,
                            window=WINDOW, block=BLOCK, mesh=make_mesh(devices),
                            mode="krylov", use_pallas=True)
    slab = jax.device_put(np.zeros((streams, BLOCK, D), np.float32),
                          eng.fleet.meta["slab_sharding"])
    ts = np.arange(1, BLOCK + 1, dtype=np.int32)
    lowered = jax.jit(eng.fleet.update_block).lower(eng.state, slab, ts)
    in_lowered = "tpu_custom_call" in lowered.as_text()
    in_compiled = "tpu_custom_call" in lowered.compile().as_text()
    del slab
    check(in_lowered and in_compiled,
          f"B: fleet program has no tpu_custom_call (lowered: {in_lowered}, "
          f"compiled: {in_compiled}) — the kernel did not reach the chip")
    users = sample_users(streams, seed)
    traffic = Traffic(streams, seed=seed, epoch=WINDOW // BLOCK)
    first, steady, rows = drive(eng, traffic, ticks, users, "B")
    rec = {"streams": streams, "ticks": ticks, "t": eng.t,
           "tpu_custom_call": {"lowered": in_lowered,
                               "compiled": in_compiled},
           **timing(streams, first, steady), **log.since(snap),
           **memory(devices)}
    rec.update(user_checks(eng, users, rows, "B"))
    report("B", **rec)
    del eng
    return rec


def phase_c(streams: int, devices, log: CompileLog, *, seed: int = 2,
            retired: int = 32, spike_tick: int = 100) -> dict:
    """Scoring, history and checkpoints at a smaller fleet.  Scoring runs
    at the engine's default thresholds."""
    import numpy as np

    from repro.core.errors import window_gram_np
    from repro.serve.engine import SketchFleetEngine
    from repro.sketch.query import Cohort
    from repro.testing import IntervalOracle

    snap = log.snapshot()
    mesh = make_mesh(devices)
    # ticks: one window plus `retired` timestamps retired into history
    ticks = (WINDOW + retired) // BLOCK
    eng = SketchFleetEngine("dsfd", d=D, streams=streams, eps=EPS,
                            window=WINDOW, block=BLOCK, mesh=mesh,
                            score=True, history=True)
    rng = np.random.default_rng((seed, 4))
    spiked = int(rng.integers(0, streams))
    spike = rng.standard_normal(D).astype(np.float32)
    spike *= 100.0 / np.linalg.norm(spike)           # ‖x‖² = 10⁴ ≫ 1

    def inject(k, slab):
        if k != spike_tick:
            return slab
        slab = slab.copy()
        slab[spiked, 0] = spike
        return slab

    # the interval cohort: two ranges, one of them a lone stream
    lo = int(rng.integers(0, streams - 8))
    solo = int(rng.integers(0, streams))
    cohort = Cohort.range(lo, lo + 3) | Cohort.of(solo)
    keep = np.array(cohort.indices(), np.int64)
    # one epoch for the whole run: a new dominant direction would be a
    # genuine (and correctly scored) change for every user at once
    traffic = Traffic(streams, seed=seed, epoch=ticks + 1)
    first, steady, rows = drive(eng, traffic, ticks, keep, "C",
                                on_tick=inject)
    rec = {"streams": streams, "ticks": ticks, "t": eng.t,
           **timing(streams, first, steady)}

    flagged = [int(u) for u in eng.anomalies()]
    rec["anomalies"] = {"injected": spiked, "flagged": flagged}
    check(flagged == [spiked],
          f"C: anomalies() flagged {flagged}, expected exactly [{spiked}]")

    t1, t2 = 3, 31
    check(t2 - 1 <= eng.t - WINDOW, "C: interval not yet retired")
    t0 = time.perf_counter()
    got = eng.query_interval(cohort, t1, t2)
    rec["interval_query_s"] = time.perf_counter() - t0
    oracle = IntervalOracle({int(s): rows[i] for i, s in enumerate(keep)},
                            int(eng.base.meta["ell"]))
    want = oracle.interval(t1, t2, streams, cohort.ranges)
    g_got = got.astype(np.float64).T @ got
    g_want = want.astype(np.float64).T @ want
    diff = float(np.abs(g_got - g_want).max() / np.abs(g_want).max())
    exact = sum(window_gram_np(r.astype(np.float64), t2 - 1, t2 - t1)
                for r in rows)
    bound = 4.0 * EPS
    err, zero = rel_cova_error(exact, got), zero_answer_error(exact)
    rec["interval"] = {"t1": t1, "t2": t2, "cohort": len(cohort),
                       "vs_oracle_rel_diff": diff,
                       "bitwise_equal": bool(np.array_equal(got, want)),
                       "bound": bound, "exact_err": err,
                       "zero_answer_err": zero}
    check(diff <= 1e-5, f"C: query_interval differs from the oracle by "
                        f"{diff} (relative, > 1e-5)")
    check(err <= bound, f"C: query_interval error {err} against the exact "
                        f"interval Gram > 4ε = {bound}")
    check(zero > bound, f"C: an all-zero interval answer would pass the bound "
                        f"({zero} <= {bound}) — the check could not fail")

    probe = sample_users(streams, seed, n=8)
    before_users = [eng.query_user(int(u)) for u in probe]
    before_cohort = eng.query_cohort(Cohort.of(sorted(int(u) for u in probe)))
    ck = REPO / ".smoke_ckpt"
    shutil.rmtree(ck, ignore_errors=True)
    try:
        t0 = time.perf_counter()
        eng.checkpoint(str(ck))
        t_save = time.perf_counter() - t0
        del eng
        gc.collect()
        t0 = time.perf_counter()
        rest = SketchFleetEngine.from_checkpoint(str(ck), mesh)
        t_restore = time.perf_counter() - t0
    finally:
        shutil.rmtree(ck, ignore_errors=True)
    same_users = all(np.array_equal(rest.query_user(int(u)), b)
                     for u, b in zip(probe, before_users))
    same_cohort = np.array_equal(
        rest.query_cohort(Cohort.of(sorted(int(u) for u in probe))),
        before_cohort)
    rec["checkpoint"] = {"save_s": t_save, "restore_s": t_restore,
                         "query_user_bit_identical": same_users,
                         "query_cohort_bit_identical": bool(same_cohort)}
    check(same_users and same_cohort,
          "C: answers after from_checkpoint differ from before checkpoint")
    rec.update(log.since(snap))
    rec.update(memory(devices))
    report("C", **rec)
    del rest
    return rec


def phase_four(streams_per_chip: int, devices, log: CompileLog, *,
               ticks: int = A_TICKS, seed: int = 0) -> dict:
    """Phase A's configuration sharded over four chips at
    ``streams_per_chip`` a chip, plus the same rows for streams [0, S/4)
    through a one-chip engine."""
    import numpy as np

    from repro.serve.engine import SketchFleetEngine

    S = streams_per_chip * len(devices)
    users = sample_users(S, seed)
    local = users[users < streams_per_chip]
    check(local.size > 0, "A4: no sampled user in [0, S/4)")
    rec, four = phase_a(S, devices, log, ticks=ticks, seed=seed,
                        label="A4", keep_answers=local)
    gc.collect()
    snap = log.snapshot()
    eng = SketchFleetEngine("dsfd", d=D, streams=streams_per_chip, eps=EPS,
                            window=WINDOW, block=BLOCK,
                            mesh=make_mesh(devices[:1]))
    traffic = Traffic(S, seed=seed, epoch=WINDOW // BLOCK)
    first, steady, _ = drive(eng, traffic, ticks, local, "A4-one-chip",
                             streams=streams_per_chip)
    one = [eng.query_user(int(u)) for u in local]
    del eng
    same = all(np.array_equal(a, b) for a, b in zip(four, one))
    out = {"users": [int(u) for u in local],
           "query_user_bit_identical": same,
           "one_chip_engine": {"streams": streams_per_chip,
                               **timing(streams_per_chip, first, steady)},
           **log.since(snap)}
    report("A4-vs-one-chip", **out)
    check(same, "A4: query_user on four chips differs from one chip for "
                "streams in [0, S/4)")
    return {**rec, "vs_one_chip": out}


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------


def run_phases(devices, log: CompileLog) -> None:
    """The phases of one run: A-full, the cut, A, B and C on one device;
    A4-full, the cut and the sharded phase on four."""
    full_label = "A4-full" if len(devices) == 4 else "A-full"
    # the same users, in [0, STREAMS), on one chip and on four
    full, _ = phase_a(STREAMS * len(devices), devices, log, ticks=FULL_TICKS,
                      label=full_label,
                      keep_answers=sample_users(STREAMS, 0))
    gc.collect()
    tick = full["steady_s_per_tick"]
    reason = (f"one tick of {STREAMS} streams a chip took {tick:.1f} s, so "
              f"phase A's {A_TICKS} ticks alone would take "
              f"{A_TICKS * tick / 60:.0f} min at that size, past a run's "
              "20-minute limit; d, ε, window and block are unchanged")
    if len(devices) == 4:
        report("cut", streams_per_chip=FOUR_CHIP_STREAMS, planned=STREAMS,
               reason=reason)
        phase_four(FOUR_CHIP_STREAMS, devices, log)
        return
    report("cut", streams=TICK_STREAMS,
           planned={"A": STREAMS, "B": STREAMS, "C": 4_096}, reason=reason)
    for phase in (phase_a, phase_b, phase_c):
        phase(TICK_STREAMS, devices, log)
        gc.collect()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: phases A, B, C on one chip; 4: phase A "
                         "sharded over four chips, compared with one")
    args = ap.parse_args(argv)

    lowering = os.environ.get("REPRO_KERNEL_LOWERING", "auto").strip().lower()
    if lowering not in ("", "auto", "pallas"):
        print(f"chip_smoke: REPRO_KERNEL_LOWERING={lowering!r} would run the "
              "kernels off the chip; unset it or use 'auto' / 'pallas'",
              file=sys.stderr)
        return 2

    sys.path.insert(0, str(REPO / "src"))
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(REPO / ".jax_cache"))
    cache_dir = jax.config.jax_compilation_cache_dir

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: no TPU found — JAX platform is {platform!r} "
              f"({len(devices)} device(s)); this run needs a TPU chip",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, JAX found {len(devices)}", file=sys.stderr)
        return 2
    devices = devices[:args.chips]

    from repro.kernels.dispatch import kernel_lowering

    log = CompileLog()
    report("setup", jax=jax.__version__, kind=devices[0].device_kind,
           devices=len(devices), kernel_lowering=kernel_lowering(),
           compile_cache_dir=cache_dir)
    t_all = time.perf_counter()
    run_phases(devices, log)
    report("total", seconds=time.perf_counter() - t_all,
           compile_s=log.seconds, cache_hits=log.hits,
           cache_misses=log.misses,
           cache="hit" if log.hits and not log.misses else
                 "partial" if log.hits else "cold")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
