"""Quickstart: the paper's algorithm in five minutes — through the unified
``SlidingSketch`` API.

Every sketch variant (DS-FD, Seq-DS-FD, Time-DS-FD, and the LM-FD / DI-FD /
SWR / SWOR baselines) lives behind one protocol: ``make_sketch(name, ...)``
returns ``init / update / update_block / query_rows / query / space``.
This script streams a synthetic dataset through DS-FD and checks the
Theorem 3.1 guarantee, does the same for the unnormalized stream with
Seq-DS-FD (Theorem 4.1), then vmaps one jitted update over 64 independent
streams — the serving-scale path.

Run:  PYTHONPATH=src:. python examples/quickstart.py   (from the repo root)
"""

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.errors import cova_error
from repro.sketch.api import make_sketch, vmap_streams
from benchmarks.common import WindowOracle, run_sketch, spec_err

# --- Problem 1.1: sequence-based, row-normalized --------------------------
n, d, N, eps = 6000, 32, 1500, 1 / 8
rng = np.random.default_rng(0)
A = rng.normal(size=(n, d)).astype(np.float32)
A[:, :4] *= 4.0                       # a few strong directions
A /= np.linalg.norm(A, axis=1, keepdims=True)

sk = make_sketch("dsfd", d=d, eps=eps, window=N, mode="fast")
queries, peak, _ = run_sketch("dsfd", A, eps=eps, window=N,
                              query_every=N // 2)

print(f"DS-FD  (ℓ={sk.meta['ell']}, window N={N}, θ=εN={eps*N:.0f}, "
      f"peak rows={peak})")
for t in sorted(queries):
    if t < N:
        continue
    B = queries[t]
    AW = A[t - N:t]
    err = float(cova_error(jnp.asarray(AW), jnp.asarray(B)))
    print(f"  t={t:5d}  cova-err={err:8.2f}  bound 4εN={4*eps*N:.0f}  "
          f"rel={err/np.sum(AW*AW):.4f}")
    assert err <= 4 * eps * N

# --- Problem 1.2: unnormalized rows, Seq-DS-FD -----------------------------
R = 64.0
Au = A * np.sqrt(rng.uniform(1, R, size=(n, 1))).astype(np.float32)
queries, max_rows, _ = run_sketch("seq-dsfd", Au, eps=eps, window=N, R=R,
                                  query_every=N // 2)
oracle = WindowOracle(Au, N)
print(f"\nSeq-DS-FD (R={R:.0f}, L={int(np.ceil(np.log2(R)))+1} layers, "
      f"max rows stored={max_rows})")
for t, B in sorted(queries.items()):
    if t < N:
        continue
    G = oracle.grams_at([t])[t]
    fro2 = oracle.fro2_at(t)
    print(f"  t={t:5d}  rel-err={spec_err(G, B)/fro2:.4f}  (β·ε=0.5)")
    assert spec_err(G, B) <= 4.0 * eps * fro2

# --- Serving scale: 64 independent streams, one fused program --------------
S, n_s, N_s = 64, 512, 128
sk_s = make_sketch("dsfd", d=d, eps=eps, window=N_s)
fleet = vmap_streams(sk_s, S)                 # S per-user sketches
streams = rng.normal(size=(S, n_s, d)).astype(np.float32)
streams /= np.linalg.norm(streams, axis=2, keepdims=True)
ts = jnp.arange(1, n_s + 1, dtype=jnp.int32)

state = fleet.init()
state = fleet.update_block(state, jnp.asarray(streams), ts)   # one XLA program
B_all = np.asarray(fleet.query(state, n_s))                   # (S, 2ℓ, d)

worst = 0.0
for s in range(S):
    AW = streams[s, n_s - N_s:n_s]
    worst = max(worst, float(cova_error(jnp.asarray(AW),
                                        jnp.asarray(B_all[s]))))
print(f"\nvmap_streams: {S} streams × {n_s} rows in one jitted update_block; "
      f"worst cova-err={worst:.2f} ≤ 4εN={4*eps*N_s:.0f}")
assert worst <= 4 * eps * N_s

# --- Aggregate analytics: the query plane (cohorts + cached merge trees) ---
from repro.sketch.api import ALL, Cohort, agg_tree, query_cohort

# ONE global-window sketch over every stream.  The first call materializes
# the fleet's AggTree (S-1 partial merges, cached); ``merge_streams`` is
# now a deprecated alias for exactly this.
g = query_cohort(fleet, state, ALL, n_s)
union = streams[:, n_s - N_s:].reshape(-1, d)
g_err = float(cova_error(jnp.asarray(union), jnp.asarray(sk_s.query(g, n_s))))
print(f"query_cohort(ALL): global sketch over all {S} windows; "
      f"cova-err={g_err:.2f} ≤ S·4εN={S*4*eps*N_s:.0f} (additive bound)")
assert g_err <= S * 4 * eps * N_s

# Cohorts compose by union; warm queries reuse the cached partial merges,
# so answering "error of cohort X over its last-W rows" between ingest
# steps costs O(log S) node merges instead of an O(S) re-reduction.
cohort = Cohort.range(0, 16) | Cohort.of(40, 41)
tree = agg_tree(fleet)
m0 = tree.merges
g_c = query_cohort(fleet, state, cohort, n_s)
union_c = streams[list(cohort.indices(S)), n_s - N_s:].reshape(-1, d)
c_err = float(cova_error(jnp.asarray(union_c),
                         jnp.asarray(sk_s.query(g_c, n_s))))
print(f"query_cohort({cohort}): {len(cohort)} streams, "
      f"{tree.merges - m0} node merges (≤ 2·log2 S = "
      f"{2 * int(np.log2(S))}); cova-err={c_err:.2f} ≤ "
      f"{len(cohort) * 4 * eps * N_s:.0f}")
assert c_err <= len(cohort) * 4 * eps * N_s

# --- Serving ingest: the async admission pipeline --------------------------
# SketchFleetEngine admits rows through a bounded, validating queue and
# (by default) the double-buffered async pipeline: while the device
# consumes tick k's (S, block, d) slab, tick k+1's slab is packed into a
# spare host buffer and prefetched onto the fleet mesh — bit-identical to
# synchronous ingest, just faster.  Idle step() calls are clock-neutral.
from repro.serve.engine import SketchFleetEngine

eng = SketchFleetEngine("dsfd", d=d, streams=S, eps=eps, window=N_s,
                        block=8, queue_capacity=S * n_s)
for i in range(64):                            # a burst of per-user rows
    for u in range(S):
        accepted = eng.submit(u, streams[u, i])
        assert accepted                        # False would mean deferred
                                               # (backpressure at capacity)
ticks = eng.run()                              # drains; raises
                                               # IngestBacklogError if the
                                               # tick budget runs out
t_idle = eng.t
eng.step()                                     # idle poll: clock-neutral
assert eng.t == t_idle                         # (no silent window expiry)
B_u = eng.query_user(3)                        # one user's (2ℓ, d) window
B_g = eng.query_cohort(Cohort.range(0, 16))    # cohort, cached AggTree
print(f"\nSketchFleetEngine: drained {eng.rows_ingested} rows in {ticks} "
      f"ticks through the async pipeline (staged+prefetched slabs); "
      f"query_user/query_cohort shapes {B_u.shape}/{B_g.shape}")

# --- Fused Pallas fleet tick + batched admission ---------------------------
# mode="krylov" dumps via Gram → power iteration → rank-1 downdate; with
# use_pallas=True that whole dump step is ONE fused kernel (downdate +
# re-Gram + re-power over the (m, d) buffer), and under vmap_streams /
# shard_streams the pallas_call batching rule prepends the stream axis to
# the kernel grid — a fleet tick is a single launch over the (S, m, d)
# slab.  Off-TPU the same call sites lower to the XLA ref path (export
# REPRO_KERNEL_LOWERING=interpret to execute the kernel bodies anywhere);
# repro.kernels.kernel_lowering() reports which lowering you got.
# ``submit_many`` is the matching admission path: one vectorized copy
# into the queue's row pool instead of a Python loop of submit() calls.
from repro.kernels import kernel_lowering

S_k, n_k = 8, 16
eng_k = SketchFleetEngine("dsfd", d=d, streams=S_k, eps=eps, window=N_s,
                          block=8, mode="krylov", use_pallas=True)
users = np.repeat(np.arange(S_k), n_k)        # row owners, user-major
rows = streams[:S_k, :n_k].reshape(-1, d)     # their rows, same order
accepted = eng_k.submit_many(users, rows)     # one vectorized admission
assert bool(accepted.all())                   # prefix-accept mask
ticks_k = eng_k.run()
print(f"fused krylov fleet ({kernel_lowering()} lowering): {S_k} streams × "
      f"{n_k} rows admitted in one submit_many, drained in {ticks_k} "
      f"single-launch ticks; query shape {eng_k.query_user(0).shape}")

# --- Anomaly scoring: flag bad streams at ingest ---------------------------
# score=True turns every tick into a detector: the incoming slab is scored
# against the PRE-update window basis (a burst cannot vouch for itself) —
# residual mass ‖x‖² − ‖x·Vᵀ‖² per row — and a per-stream EWMA flags users
# whose tick peak exceeds mean + z·σ after warmup.  score_rows /
# score_cohort answer on-demand probes against one user's basis or a
# merged cohort basis served from the cached AggTree.
S_a, n_a, bad_user = 16, 96, 5
eng_a = SketchFleetEngine("dsfd", d=d, streams=S_a, eps=eps, window=N_s,
                          block=8, score=True, score_zscore=4.0)
rng_a = np.random.default_rng(7)
axes = np.linalg.qr(rng_a.normal(size=(d, 2)))[0].T    # shared 2-dim habit
for i in range(n_a):
    coef = rng_a.normal(size=(S_a, 2)).astype(np.float32)
    slab = coef @ axes + 0.03 * rng_a.normal(size=(S_a, d))
    if i >= n_a - 6:                           # one user leaves the subspace
        slab[bad_user] = 8.0 * rng_a.normal(size=(d,))
    for u in range(S_a):
        eng_a.submit(u, slab[u].astype(np.float32))
    eng_a.step()
flagged = eng_a.anomalies()
assert bad_user in flagged
probe = (3.0 * rng_a.normal(size=(4, d))).astype(np.float32)
s_u = eng_a.score_rows(probe, user=0)          # vs user 0's window basis
s_c = eng_a.score_cohort(probe, Cohort.range(0, 8))   # vs a merged cohort
print(f"\nscoring plane: ingest flagged streams {flagged.tolist()} "
      f"(injected: {bad_user}); off-subspace probes score "
      f"{float(np.median(s_c)):.1f} vs in-window rows ≈ 0")

# Adaptive rank: adapt_target= grows/shrinks each stream's ℓ online to
# hold a target relative covariance error, so a heterogeneous fleet
# spends rows only where streams are hard.  FleetSpace.ranks (and
# eng.ranks() on a scoring engine) expose the per-stream ℓ.
sk_ad = make_sketch("fd", d=d, eps=eps, window=N_s, adapt_target=0.05)
fleet_ad = vmap_streams(sk_ad, 4)
easy = streams[:4] @ axes.T @ axes             # 4 streams flattened to rank 2
st_ad = fleet_ad.update_block(
    fleet_ad.init(), jnp.asarray(easy, jnp.float32), ts)
sp_ad = fleet_ad.space(st_ad)
print(f"adaptive rank: rank-2 streams settle at ℓ={np.asarray(sp_ad.ranks)} "
      f"(ℓ_max={sk_ad.meta['ell']}), {int(sp_ad.total)} rows total")

# --- Time travel: the persistent history plane -----------------------------
# history=True stops the window from *forgetting*: content that slides out
# is retired into a time-dyadic index of compressed (2ℓ, d) snapshots —
# hot nodes in an in-memory LRU, the rest spilled write-once through
# train/checkpoint.py into marker-protected dirs (retention will never
# prune them).  query_interval(users, t1, t2) then answers ANY fully
# retired historical interval in O(log(t2−t1)) node merges, bit-identical
# to re-compressing the raw rows through the same dyadic schedule, and
# the whole index rides engine checkpoints.
import tempfile

S_h, W_h, n_h = 8, 16, 48
hist_root = tempfile.mkdtemp(prefix="quickstart-history-")
eng_h = SketchFleetEngine("dsfd", d=d, streams=S_h, eps=eps, window=W_h,
                          block=4, history=True, history_hot_nodes=8,
                          history_dir=f"{hist_root}/spill")
users_h = np.repeat(np.arange(S_h), n_h)
assert eng_h.submit_many(users_h, streams[:S_h, :n_h].reshape(-1, d)).all()
eng_h.run()                                   # window slides: rows with
                                              # ts ≤ t−W retire as they expire
t1, t2 = 5, eng_h.history.retired_through + 1  # any retired [t1, t2)
H = eng_h.query_interval(None, t1, t2)         # whole-fleet historical
Hc = eng_h.query_interval(range(0, 4), t1, t2)  # cohort-scoped
eng_h.checkpoint(f"{hist_root}/ck")            # history index rides along
eng_r = SketchFleetEngine.from_checkpoint(f"{hist_root}/ck")
assert np.array_equal(eng_r.query_interval(None, t1, t2), H)
print(f"\nhistory plane: t={eng_h.t}, window W={W_h} → intervals up to "
      f"ts<{t2} queryable; [{t1}, {t2}) answered in "
      f"{eng_h.history.store.faults} cold faults, shape {H.shape}; "
      f"restored engine answers bit-identically")

# --- Multi-host fleets: partitioned along the AggTree ----------------------
# FleetTopology gives each process a contiguous stream range that is a
# canonical node of the global segment tree, so a local AggTree answers
# its subtree bit-identically and only the O(log S) top spine crosses
# processes (as compressed (2ℓ, d) node states over the jax.distributed
# KV service).  Ingest routes by ownership; checkpoints are one shard
# per process and restore on any process count.  This block spawns a
# real 2-process CPU pair and checks both halves bit for bit against the
# fleet above — so it runs only when this process is on the CPU too: on
# an accelerator the reference answer comes from the chip, which this
# process holds, and CPU children could not match it bit for bit.
import os
import socket
import subprocess
import sys
import tempfile

_WORKER = """
import sys
pid, port = int(sys.argv[1]), sys.argv[2]
import numpy as np, jax
jax.distributed.initialize(coordinator_address="127.0.0.1:" + port,
                           num_processes=2, process_id=pid)
from repro.parallel.topology import FleetTopology
from repro.sketch.api import ALL, make_sketch, shard_streams

S, n, d, N, eps = 64, 512, 32, 128, 1 / 8
rng = np.random.default_rng(0)
_ = rng.normal(size=(6000, d))                  # keep the rng in step
_ = rng.uniform(1, 64.0, size=(6000, 1))
streams = rng.normal(size=(S, n, d)).astype(np.float32)
streams /= np.linalg.norm(streams, axis=2, keepdims=True)

sk = make_sketch("dsfd", d=d, eps=eps, window=N)
topo = FleetTopology(S)                         # range from the runtime
fleet = shard_streams(sk, S, topology=topo)     # local [lo, hi) shard
ts = np.arange(1, n + 1, dtype=np.int32)
state = fleet.update_block(fleet.init(), streams[topo.lo:topo.hi], ts)
g = fleet.query_cohort(state, ALL, n)           # collective global answer
np.save(sys.argv[3] + f"/g{pid}.npy",
        np.asarray(sk.query(g, n)))
print(f"process {pid} owns [{topo.lo}, {topo.hi}) of {S}")
"""

if jax.default_backend() != "cpu":
    print("\n2-process fleet: skipped — this process is on "
          f"{jax.default_backend()!r}; the CPU pair is compared with a CPU "
          "answer only")
elif os.environ.get("QUICKSTART_MULTIHOST", "1") != "0":
    sock = socket.socket()
    sock.bind(("127.0.0.1", 0))
    port = str(sock.getsockname()[1])
    sock.close()
    tmp = tempfile.mkdtemp(prefix="quickstart-multihost-")
    procs = [subprocess.Popen([sys.executable, "-c", _WORKER,
                               str(p), port, tmp],
                              env=dict(os.environ, JAX_PLATFORM_NAME="cpu"))
             for p in range(2)]
    assert all(p.wait(timeout=540) == 0 for p in procs)
    halves = [np.load(os.path.join(tmp, f"g{p}.npy")) for p in range(2)]
    want = np.asarray(sk_s.query(g, n_s))       # the single-process answer
    for p, got in enumerate(halves):
        np.testing.assert_array_equal(want, got)
    print(f"\n2-process fleet: both halves answered query_cohort(ALL) "
          f"bit-identically to the single-process fleet {want.shape}")

print("\nall guarantees hold ✓")
