"""DS-FD — Dump-Snapshot FrequentDirections over sliding windows (the paper's
core contribution, Algorithms 1-4 + the Fast/Krylov variants of §3.1).

Implementation notes (TPU/JAX adaptation — see DESIGN.md §3):

* The paper's Python object queues become fixed-capacity **ring buffers** so
  the whole update is a static-shape pure function; expiry is timestamp
  masking; a dump writes its rows into the ring in one masked store.
* One engine implements all cadences:
    - ``mode="exact"``  — SVD every step (Algorithm 2 cadence).
    - ``mode="fast"``   — SVD when the 2ℓ buffer fills (shrink) or when the
      running upper bound ``σ̂₁² ≥ θ`` (lossless rotate; Algorithm 3's trigger,
      line 16).  Deterministic.
    - ``mode="krylov"`` — like ``fast`` but the θ-triggered path extracts the
      top direction with Gram + power iteration + rank-1 downdate
      (probabilistic Fast-DS-FD, §3.1; maps onto the Pallas kernels in
      ``repro.kernels``).
* *Restart every N steps* is generalized to an **energy-based swap**: the
  auxiliary sketch is promoted to primary once it has absorbed
  ``swap_energy = ℓ·θ`` of squared norm (and a fresh auxiliary starts).  For
  the normalized problem (θ = εN, ‖a‖²=1) this is exactly the paper's
  swap-every-N: each sketch lives 2N steps — N as auxiliary + N as primary —
  so the retiring primary has absorbed 2N; for Seq-DS-FD layer j
  (θⱼ = 2ʲεN) the retiring primary has absorbed 2^{j+1}N, reproducing the
  paper's "swap once Σ‖aᵢ‖² surpasses 2^{j+1}N".
* Coverage bookkeeping: each sketch tracks ``cov_start`` — the earliest
  timestamp such that queue ∪ residual represents [cov_start, now].  Expiring
  or ring-evicting a snapshot with dump-time t_e advances it to t_e+1.  The
  Seq/Time query picks the lowest layer with ``cov_start ≤ T−N+1``
  (Algorithm 7 line 1).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.fd import (fd_absorb, fd_compress, fd_init, fd_rotate,
                           fd_shrink)

_NEG = jnp.int32(-(2**30))


# ---------------------------------------------------------------------------
# Configuration (static) and state (pytree)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class DSFDConfig:
    """Static configuration for one DS-FD sketch pair.

    d:        row dimension.
    ell:      sketch rows ℓ = min(⌈1/ε⌉, d).
    window:   sliding window length N (timestamps).
    cap:      snapshot ring capacity.  Theorem 3.1 proves ≤ 2/ε live
              snapshots (normalized); Theorem 4.1 caps at 2(1+4/β)/ε.
    mode:     'exact' | 'fast' | 'krylov'.
    power_iters: power-iteration sweeps for mode='krylov'.
    use_pallas:  route krylov linear algebra through the Pallas kernels.
    """

    d: int
    ell: int
    window: int
    cap: int
    mode: str = "fast"
    power_iters: int = 24
    use_pallas: bool = False

    @property
    def m(self) -> int:  # buffer rows
        return 2 * self.ell


def make_config(d: int, eps: float, window: int, *, mode: str = "fast",
                beta: float = 4.0, use_pallas: bool = False) -> DSFDConfig:
    ell = int(min(max(round(1.0 / eps), 1), d))
    cap = int(2 * (1.0 + 4.0 / beta) / eps) + 4
    return DSFDConfig(d=d, ell=ell, window=int(window), cap=cap, mode=mode,
                      use_pallas=use_pallas)


class SketchState(NamedTuple):
    """One FD sketch + its snapshot ring (the paper's (Ĉ, S) pair)."""

    buf: jax.Array        # (m, d) residual rows
    nbuf: jax.Array       # int32 occupied rows
    sig1: jax.Array       # f32 upper bound on σ₁²(buf)
    energy: jax.Array     # f32 Σ‖a‖² absorbed since init (non-bypassed)
    start_t: jax.Array    # int32 first timestamp this sketch saw
    last_t: jax.Array     # int32 dump time of the most recent snapshot
    cov_start: jax.Array  # int32 coverage start (see module docstring)
    snap_v: jax.Array     # (cap, d) snapshot vectors σ·v
    snap_s: jax.Array     # (cap,) coverage-start timestamps
    snap_t: jax.Array     # (cap,) dump timestamps
    snap_valid: jax.Array  # (cap,) bool
    snap_next: jax.Array  # int32 ring write cursor


class DSFDState(NamedTuple):
    main: SketchState
    aux: SketchState


def _sketch_init(cfg: DSFDConfig, t0) -> SketchState:
    t0 = jnp.asarray(t0, jnp.int32)
    return SketchState(
        buf=jnp.zeros((cfg.m, cfg.d), jnp.float32),
        nbuf=jnp.zeros((), jnp.int32),
        sig1=jnp.zeros((), jnp.float32),
        energy=jnp.zeros((), jnp.float32),
        start_t=t0,
        last_t=t0 - 1,
        cov_start=t0,
        snap_v=jnp.zeros((cfg.cap, cfg.d), jnp.float32),
        snap_s=jnp.full((cfg.cap,), _NEG, jnp.int32),
        snap_t=jnp.full((cfg.cap,), _NEG, jnp.int32),
        snap_valid=jnp.zeros((cfg.cap,), bool),
        snap_next=jnp.zeros((), jnp.int32),
    )


def dsfd_init(cfg: DSFDConfig, t0: int = 1) -> DSFDState:
    return DSFDState(main=_sketch_init(cfg, t0), aux=_sketch_init(cfg, t0))


# ---------------------------------------------------------------------------
# Snapshot ring helpers
# ---------------------------------------------------------------------------


def _expire(sk: SketchState, now, window) -> SketchState:
    """Drop snapshots with t + N ≤ now (Algorithm 2 lines 6-7)."""
    dead = sk.snap_valid & (sk.snap_t + window <= now)
    new_valid = sk.snap_valid & ~dead
    t_dead = jnp.max(jnp.where(dead, sk.snap_t, _NEG))
    cov = jnp.maximum(sk.cov_start, jnp.where(jnp.any(dead), t_dead + 1, _NEG))
    return sk._replace(snap_valid=new_valid, cov_start=cov)


def _ring_append(sk: SketchState, v, s, t) -> SketchState:
    """Append one snapshot; evicting the slot it lands on if still valid."""
    slot = jnp.mod(sk.snap_next, sk.snap_v.shape[0])
    evicted = sk.snap_valid[slot]
    cov = jnp.maximum(sk.cov_start,
                      jnp.where(evicted, sk.snap_t[slot] + 1, _NEG))
    return sk._replace(
        snap_v=jax.lax.dynamic_update_index_in_dim(sk.snap_v, v, slot, 0),
        snap_s=sk.snap_s.at[slot].set(jnp.asarray(s, jnp.int32)),
        snap_t=sk.snap_t.at[slot].set(jnp.asarray(t, jnp.int32)),
        snap_valid=sk.snap_valid.at[slot].set(True),
        snap_next=sk.snap_next + 1,
        cov_start=cov,
        last_t=jnp.asarray(t, jnp.int32),
    )


def _dump_sorted_rows(sk: SketchState, rows, nrows, now, theta) -> SketchState:
    """Given SVD-sorted rows, dump every row with ‖row‖² ≥ θ into the ring
    (Algorithm 2 lines 9-11), then compact the remaining rows to the top.

    The ndump appends are one masked store: dump j lands on slot
    (snap_next + j) mod cap, and ndump ≤ m ≤ cap writes no slot twice, so
    evictions are read from the ring as it stood before the store."""
    m = rows.shape[0]
    cap = sk.snap_v.shape[0]
    if m > cap:
        raise ValueError(f"snapshot ring of {cap} slots cannot take a dump "
                         f"of up to {m} rows")
    with jax.named_scope("dsfd.dump"):
        norms = jnp.sum(rows * rows, axis=1)
        # sorted ⇒ prefix
        ndump = jnp.sum((norms >= theta).astype(jnp.int32))

        # j[c]: the index of the dump that lands on slot c
        j = jnp.mod(jnp.arange(cap) - sk.snap_next, cap)
        write = j < ndump
        evicted = write & sk.snap_valid
        cov = jnp.maximum(sk.cov_start,
                          jnp.max(jnp.where(evicted, sk.snap_t + 1, _NEG)))
        sk = sk._replace(
            snap_v=jnp.where(write[:, None], rows[jnp.minimum(j, m - 1)],
                             sk.snap_v),
            snap_s=jnp.where(write, jnp.where(j == 0, sk.last_t + 1, now),
                             sk.snap_s),
            snap_t=jnp.where(write, now, sk.snap_t),
            snap_valid=sk.snap_valid | write,
            snap_next=sk.snap_next + ndump,
            cov_start=cov,
            last_t=jnp.where(ndump > 0, now, sk.last_t),
        )

        kept = jnp.roll(rows, -ndump, axis=0)
        nkeep = jnp.maximum(nrows - ndump, 0)
        kept = jnp.where(jnp.arange(m)[:, None] < nkeep, kept, 0.0)
        sig1 = jnp.sum(kept[0] * kept[0])
        return sk._replace(buf=kept, nbuf=nkeep.astype(jnp.int32),
                           sig1=sig1)


# ---------------------------------------------------------------------------
# Krylov (power-iteration) dump path — probabilistic Fast-DS-FD
# ---------------------------------------------------------------------------


def _power_topvec(K: jax.Array, iters: int, use_pallas: bool) -> Tuple[jax.Array, jax.Array]:
    """Top eigenpair (λ, u) of the small PSD Gram matrix K (m×m)."""
    if use_pallas:
        from repro.kernels.power_iter.ops import power_iter as _pi
        return _pi(K, iters=iters)
    m = K.shape[0]
    u = jnp.full((m,), 1.0 / jnp.sqrt(m), K.dtype)

    def body(_, u):
        w = K @ u
        return w / jnp.maximum(jnp.linalg.norm(w), 1e-30)

    u = jax.lax.fori_loop(0, iters, body, u)
    lam = u @ (K @ u)
    return lam, u


def _gram(buf: jax.Array, use_pallas: bool) -> jax.Array:
    if use_pallas:
        from repro.kernels.gram.ops import gram as _gram_k
        return _gram_k(buf)
    return buf @ buf.T


def _rank1_downdate(buf: jax.Array, v: jax.Array, use_pallas: bool) -> jax.Array:
    if use_pallas:
        from repro.kernels.rank1_downdate.ops import rank1_downdate as _rd
        return _rd(buf, v)
    return buf - (buf @ v)[:, None] * v[None, :]


def _krylov_dumps(cfg: DSFDConfig, sk: SketchState, now, theta) -> SketchState:
    """While σ₁²(buf) ≥ θ: extract v₁ = u₁ᵀD/σ₁, snapshot σ₁·v₁, downdate
    (Algorithm 3 lines 14-22, with power iteration per §3.1).

    With ``use_pallas`` the whole dump step — v-extraction, snapshot,
    downdate, Gram, power iteration — is ONE fused kernel launch
    (``repro.kernels.fused_tick``).  Written unbatched, the pallas vmap
    batching rule turns the fleet tick under ``vmap_streams`` /
    ``shard_streams`` into a single launch over the (S, m, d) slab."""

    def cond(carry):
        sk, lam, _u, it = carry
        return (lam >= theta) & (it < cfg.m)

    if cfg.use_pallas:
        from repro.kernels.fused_tick.ops import fused_krylov_step, gram_power

        def body(carry):
            sk, lam, u, it = carry
            snap, buf, lam2, u2 = fused_krylov_step(sk.buf, lam, u,
                                                    iters=cfg.power_iters)
            s = jnp.where(it == 0, sk.last_t + 1, now)
            sk = _ring_append(sk, snap, s, now)
            sk = sk._replace(buf=buf, sig1=lam2)
            return sk, lam2, u2, it + 1

        lam, u = gram_power(sk.buf, iters=cfg.power_iters)
    else:
        def body(carry):
            sk, lam, u, it = carry
            sigma = jnp.sqrt(jnp.maximum(lam, 1e-30))
            v = (u @ sk.buf) / sigma                  # right singular vector
            v = v / jnp.maximum(jnp.linalg.norm(v), 1e-30)
            snap = sigma * v
            s = jnp.where(it == 0, sk.last_t + 1, now)
            sk = _ring_append(sk, snap, s, now)
            buf = _rank1_downdate(sk.buf, v, cfg.use_pallas)
            K = _gram(buf, cfg.use_pallas)
            lam, u = _power_topvec(K, cfg.power_iters, cfg.use_pallas)
            sk = sk._replace(buf=buf, sig1=lam)
            return sk, lam, u, it + 1

        K = _gram(sk.buf, cfg.use_pallas)
        lam, u = _power_topvec(K, cfg.power_iters, cfg.use_pallas)
    sk = sk._replace(sig1=lam)
    sk, lam, _, _ = jax.lax.while_loop(
        cond, body, (sk, lam, u, jnp.zeros((), jnp.int32)))
    return sk


# ---------------------------------------------------------------------------
# Per-sketch absorb
# ---------------------------------------------------------------------------


def _absorb(cfg: DSFDConfig, sk: SketchState, row, now, theta) -> SketchState:
    """Insert one row, then merge/dump per the configured cadence.

    Named scopes (``dsfd.absorb`` around the whole, inside it
    ``dsfd.insert``, ``dsfd.shrink``, ``dsfd.rotate``, ``dsfd.dump`` and
    ``dsfd.krylov``) land in the compiled program's ``op_name`` metadata,
    so a device trace's ops can be attributed to the phase they run.
    Under ``vmap`` every ``cond`` here becomes a ``select``: both branches
    run for every row, and the selects themselves are ``dsfd.absorb``'s."""
    with jax.named_scope("dsfd.absorb"):
        with jax.named_scope("dsfd.insert"):
            buf = jax.lax.dynamic_update_index_in_dim(sk.buf, row, sk.nbuf,
                                                      0)
            e = jnp.sum(row * row)
            sk = sk._replace(buf=buf, nbuf=sk.nbuf + 1, sig1=sk.sig1 + e,
                             energy=sk.energy + e)

        full = sk.nbuf >= cfg.m
        hot = sk.sig1 >= theta

        def svd_merge(sk):
            # Buffer full → FD shrink (+ dump check on the sorted rows).
            with jax.named_scope("dsfd.shrink"):
                rows, _, _ = fd_shrink(sk.buf, cfg.ell)
            return _dump_sorted_rows(
                sk, rows, jnp.asarray(cfg.ell - 1, jnp.int32), now, theta)

        def rotate_dump(sk):
            # θ-trigger between merges → lossless rotate + dump (no shrink).
            with jax.named_scope("dsfd.rotate"):
                rows, _ = fd_rotate(sk.buf)
                nrows = jnp.minimum(sk.nbuf, min(cfg.m, cfg.d))
            return _dump_sorted_rows(sk, rows, nrows, now, theta)

        def krylov_dump(sk):
            with jax.named_scope("dsfd.krylov"):
                return _krylov_dumps(cfg, sk, now, theta)

        if cfg.mode == "exact":
            # SVD every step: rotate+dump, then shrink only if truly full.
            sk = rotate_dump(sk)
            sk = jax.lax.cond(sk.nbuf >= cfg.m, svd_merge, lambda s: s, sk)
            return sk

        hot_path = krylov_dump if cfg.mode == "krylov" else rotate_dump
        sk = jax.lax.cond(
            full, svd_merge,
            lambda s: jax.lax.cond(hot, hot_path, lambda x: x, s), sk)
        return sk


# ---------------------------------------------------------------------------
# Public update / query (plain DS-FD, Problem 1.1)
# ---------------------------------------------------------------------------


def dsfd_update(cfg: DSFDConfig, state: DSFDState, row: jax.Array, now,
                theta: Optional[jax.Array] = None,
                swap_energy: Optional[jax.Array] = None,
                bypass: bool = False) -> DSFDState:
    """One sliding-window update (Algorithm 2 / 3).

    ``theta`` defaults to εN = N/ℓ (Problem 1.1).  ``bypass`` enables the
    Seq-DS-FD heavy-row shortcut (Algorithm 6 lines 4-6): rows with
    ‖a‖² ≥ θ go straight into both snapshot queues, under the
    ``dsfd.bypass`` named scope.
    """
    now = jnp.asarray(now, jnp.int32)
    theta = jnp.asarray(
        cfg.window / cfg.ell if theta is None else theta, jnp.float32)
    swap_energy = jnp.asarray(
        1.0 * cfg.ell * theta if swap_energy is None else swap_energy,
        jnp.float32)

    with jax.named_scope("dsfd.expire"):
        main = _expire(state.main, now, cfg.window)
        aux = _expire(state.aux, now, cfg.window)

    # Restart-every-N generalized: promote the auxiliary once it has absorbed
    # swap_energy = ℓθ (== N steps in the normalized model; the retiring
    # primary has then absorbed 2ℓθ = its 2N-step lifetime).
    def do_swap(ma):
        main, aux = ma
        return aux, _sketch_init(cfg, now)

    with jax.named_scope("dsfd.swap"):
        main, aux = jax.lax.cond(
            aux.energy >= swap_energy, do_swap, lambda ma: ma, (main, aux))

    e = jnp.sum(row * row)

    def light(ma):
        main, aux = ma
        return (_absorb(cfg, main, row, now, theta),
                _absorb(cfg, aux, row, now, theta))

    def idle(ma):  # time-based idle tick (‖a‖² = 0): expiry/swap only
        return ma

    # the selects that merge these branches under vmap are absorb work
    with jax.named_scope("dsfd.absorb"):
        if bypass:
            def heavy(ma):
                main, aux = ma
                with jax.named_scope("dsfd.bypass"):
                    return (_ring_append(main, row, main.last_t + 1, now),
                            _ring_append(aux, row, aux.last_t + 1, now))

            main, aux = jax.lax.cond(
                e >= theta, heavy,
                lambda ma: jax.lax.cond(e > 0.0, light, idle, ma),
                (main, aux))
        else:
            main, aux = jax.lax.cond(e > 0.0, light, idle, (main, aux))
    return DSFDState(main=main, aux=aux)


def dsfd_query_rows(cfg: DSFDConfig, state: DSFDState,
                    now=None) -> jax.Array:
    """Fixed-shape (cap + m, d) stack of live snapshots + residual rows.

    Invalid slots are zero rows (they do not perturb BᵀB).  This is the
    un-compressed B_W; ``dsfd_query`` additionally FD-compresses to 2ℓ rows
    (Algorithm 4 returns FD_ℓ(B, Ĉ)).  Passing ``now`` re-applies expiry for
    queries issued between updates (time-based streams)."""
    sk = state.main
    valid = sk.snap_valid
    if now is not None:
        valid = valid & (sk.snap_t + cfg.window > jnp.asarray(now, jnp.int32))
    snaps = jnp.where(valid[:, None], sk.snap_v, 0.0)
    return jnp.concatenate([snaps, sk.buf], axis=0)


def dsfd_query(cfg: DSFDConfig, state: DSFDState) -> jax.Array:
    return fd_compress(dsfd_query_rows(cfg, state), cfg.ell)


def dsfd_score(cfg: DSFDConfig, state: DSFDState, X: jax.Array,
               now=None) -> jax.Array:
    """Residual anomaly score of each row of ``X`` against the windowed
    sketch: energy outside the span of the live snapshot ∪ residual rows
    (``‖x‖² − ‖x Vᵀ‖²``, clamped ≥ 0).  The FD guarantee bounds how much
    in-window structure that span can miss, so a large score is a row the
    current window genuinely cannot explain — the per-row event/anomaly
    signal of the paper's motivating applications.  Pass ``now`` to
    re-apply expiry first (same contract as ``dsfd_query_rows``)."""
    from repro.sketch.basis import residual_scores

    return residual_scores(dsfd_query_rows(cfg, state, now=now), X)


def dsfd_merge(cfg: DSFDConfig, s1: DSFDState, s2: DSFDState,
               now=None) -> DSFDState:
    """Merge two DS-FD sketches into one (FD mergeability, Liberty 2013).

    The live rows of each side — snapshots ∪ residual, i.e. exactly
    ``dsfd_query_rows`` — are unioned and FD-re-compressed to 2ℓ rows via
    ``fd_absorb``, giving the additive covariance-error bound

        err(merged) ≤ err(s1) + err(s2) + ‖B₁;B₂‖_F²/ℓ .

    The merged state is a valid ``DSFDState`` (it keeps answering queries
    and absorbing rows), but its snapshot rings restart empty, so rows
    already folded into the residual can no longer expire individually —
    merge is the *aggregation* primitive (cross-shard / cross-user fleet
    queries), not a substitute for streaming both inputs into one sketch.
    ``now`` re-applies expiry to both sides before the union (pass the
    query time for time-based streams).
    """
    rows = jnp.concatenate([dsfd_query_rows(cfg, s1, now=now),
                            dsfd_query_rows(cfg, s2, now=now)], axis=0)
    fd = fd_absorb(fd_init(cfg.ell, cfg.d), rows, ell=cfg.ell)
    m1, m2 = s1.main, s2.main
    merged = _sketch_init(cfg, jnp.minimum(m1.start_t, m2.start_t))
    merged = merged._replace(
        buf=fd.buf,
        nbuf=fd.nbuf,
        # Frobenius mass is a safe σ₁² upper bound for the trigger logic.
        sig1=jnp.sum(fd.buf * fd.buf),
        energy=m1.energy + m2.energy,
        last_t=jnp.maximum(m1.last_t, m2.last_t),
        # coverage is the INTERSECTION of the two sides: the union of rows
        # represents [t, now] only where both inputs do (max, not min —
        # min would let Algorithm 7 select a merged layer that is missing
        # one side's already-evicted early-window rows).
        cov_start=jnp.maximum(m1.cov_start, m2.cov_start),
    )
    t_next = jnp.maximum(m1.last_t, m2.last_t) + 1
    return DSFDState(main=merged, aux=_sketch_init(cfg, t_next))


# ---------------------------------------------------------------------------
# Stream runner (scan) — used by tests and benchmarks
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("cfg", "query_every"))
def dsfd_run_stream(cfg: DSFDConfig, rows: jax.Array, query_every: int = 0):
    """Scan a whole stream through DS-FD.  If query_every > 0, emit the
    stacked B_W rows every ``query_every`` steps (for error evaluation)."""

    def step(state, inp):
        t, row = inp
        state = dsfd_update(cfg, state, row, t)
        if query_every:
            out = jax.lax.cond(
                jnp.mod(t, query_every) == 0,
                lambda s: dsfd_query_rows(cfg, s),
                lambda s: jnp.zeros((cfg.cap + cfg.m, cfg.d), jnp.float32),
                state)
        else:
            out = None
        return state, out

    n = rows.shape[0]
    ts = jnp.arange(1, n + 1, dtype=jnp.int32)
    state = dsfd_init(cfg)
    return jax.lax.scan(step, state, (ts, rows))
