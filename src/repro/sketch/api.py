"""Unified ``SlidingSketch`` API — one protocol + registry for every sketch
variant in the repo (the paper's algorithms and the baselines it compares
against).

Every sketch answers the same question — approximate ``A_WᵀA_W`` over a
sliding window — so every sketch exposes the same optax-style bundle of
pure functions:

=================  =========================================================
protocol method    paper mapping
=================  =========================================================
``init(t0=1)``     fresh state (Algorithm 1 initialisation / ring buffers)
``update(s,a,t)``  one-row sliding-window update — Algorithm 2 (exact
                   cadence), Algorithm 3 (Fast-DS-FD trigger), Algorithm 6
                   (layered dispatch with heavy-row bypass)
``update_block``   ``(s, rows, ts) → s``: absorb a whole ``(B, d)`` block
                   via one internal ``lax.scan``, jit-compiled once — the
                   deployment cadence (not in the paper; semantics are
                   exactly B repeated ``update`` calls)
``query_rows``     ``(s, t) → B_W`` stacked live snapshot + residual rows —
                   Algorithm 4 line 1 / Algorithm 7 lines 1-2 (layer select
                   then stack)
``query``          ``(s, t) → FD_ℓ(B_W)`` compressed ``2ℓ×d`` sketch —
                   Algorithm 4's return / Algorithm 7 line 3
``space(s)``       live stored-row count — the quantity plotted in the
                   paper's space figures (Figures 4-9, Theorems 3.2/4.1/5.1)
``merge(s1,s2)``   combine two sketches of the same variant into one whose
                   query covers both inputs (FD mergeability, Liberty 2013:
                   the live snapshot/residual rows are unioned and
                   re-compressed to 2ℓ via ``fd_absorb``, giving the
                   additive bound err ≤ err₁ + err₂ + ‖B₁;B₂‖_F²/ℓ).  Takes
                   an optional query time ``t`` to re-apply expiry first.
                   Host baselines use their native combine where one exists
                   (DI-FD: aligned dyadic intervals; SWR/SWOR: priority-key
                   union, requiring independently-*seeded* instances) and
                   raise a documented ``NotImplementedError`` otherwise
                   (LM-FD: energy-aligned blocks do not merge).
=================  =========================================================

JAX-backed variants (``"fd"``, ``"dsfd"``, ``"seq-dsfd"``, ``"time-dsfd"``)
are pure functions over pytree states, so they compose with ``jax.jit`` /
``lax.scan`` / ``jax.vmap``.  The numpy baselines (``"lmfd"``, ``"difd"``,
``"swr"``, ``"swor"``) satisfy the same protocol through a host-side
adapter whose "state" is the mutable python object itself (returned back
from ``update`` so call sites are written identically; host ``merge`` may
likewise mutate and return its first argument).

Fleet scale: one lift turns a JAX-backed sketch into a fleet of S
independent per-user streams.  ``vmap_streams(sk, S)`` places it on one
device; ``shard_streams(sk, S, mesh)`` splits the streams over every
device of a mesh (S must divide by the device count), and with a
``topology`` this process holds only its own stream range.  Either way
``update_block`` is one jitted program per tick (``shard_map``'d when
there is a mesh), with no cross-device traffic on the hot path.
Aggregate queries go through the **query plane**
(``repro.sketch.query``): ``query_cohort(fleet, state, cohort, t)``
answers any union of stream ranges (a ``Cohort``) with ONE merged
base-variant sketch, served from the fleet's cached ``AggTree`` — a
segment tree of partial merges whose warm queries cost O(log S) node
merges instead of the O(S) from-scratch reduction.
``merge_streams(fleet, state, t)`` survives as a deprecated alias for
``query_cohort(fleet, state, ALL, t)``.

Registry::

    sk = make_sketch("dsfd", d=64, eps=1/8, window=1024, mode="fast")
    state = sk.init()
    state = sk.update_block(state, rows, ts)       # (B, d), (B,) int32
    B_W   = sk.query(state, t)                      # (2ℓ, d)

``make_sketch`` memoizes on its (hashable) arguments, so repeated
construction re-uses the same jitted ``update_block``.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.dsfd import (dsfd_init, dsfd_merge, dsfd_query_rows,
                             dsfd_score, dsfd_update, make_config)
from repro.core.fd import (adaptive_fd_init, adaptive_fd_merge,
                           adaptive_fd_update, fd_compress, fd_init,
                           fd_merge, fd_update)
from repro.core.seq_dsfd import (layered_init, layered_merge,
                                 layered_query_rows, layered_update,
                                 make_seq_config, make_time_config)
from repro.sketch import capability
from repro.sketch.basis import residual_scores
from repro.sketch.query import ALL, AggTree, Cohort, as_cohort  # noqa: F401
from repro.sketch.query import full_reduce_streams              # noqa: F401
from repro.sketch.score import make_host_score, make_jax_score


class SlidingSketch(NamedTuple):
    """Bundle of pure functions implementing the sliding-sketch protocol.

    Fields ``init / update / update_block / query_rows / query / space /
    merge`` are the protocol (see module docstring); ``meta`` carries static
    facts about the instance (``d``, ``eps``, ``window``, ``ell``,
    ``backend``: ``"jax"`` | ``"host"``) for harnesses that need them.

    ``query_cohort(state, cohort, t)`` is the query-plane entry point —
    it answers aggregate queries over any :class:`repro.sketch.query.Cohort`
    of streams from the fleet's cached :class:`AggTree`.  Only fleets
    (``vmap_streams`` / ``shard_streams``) implement it; single sketches
    carry a raiser explaining how to get one.

    ``query_interval(state, t1, t2, cohort=ALL)`` is the time-travel
    entry point: the ``(2ℓ, d)`` sketch of everything the cohort ingested
    with timestamps in ``[t1, t2)``, served from the persistent history
    plane of *retired* (expired-from-window) content
    (``repro.sketch.history``).  Live only on fleets with a history plane
    attached (``SketchFleetEngine(..., history=True)`` or
    ``install_query_interval``).

    ``score(state, X, t=None)`` is the scoring plane: the residual
    anomaly score of each row of ``X`` against the windowed sketch basis
    (``repro.sketch.score``) — every registered variant carries it (JAX
    variants as one jitted program, host baselines through the numpy
    adapter), and fleets score whole ``(S, B, d)`` slabs in the same
    fused/SPMD program shape as their updates.

    ``ranks(state)`` reports the per-stream working rank — live only on
    adaptive-rank variants (``make_sketch("fd", ..., adapt_target=...)``).

    The optional fields (``query_cohort`` / ``query_interval`` / ``score``
    / ``ranks``) are *capabilities* (``repro.sketch.capability``): when an
    instance lacks one, the field holds a tagged raiser whose message is
    derived from the instance's context (single vs fleet, host vs JAX,
    history attached or not) — introspect with
    ``repro.sketch.capability.capabilities(sk)``.
    """

    name: str
    meta: Dict[str, Any]
    init: Callable[..., Any]
    update: Callable[[Any, Any, Any], Any]
    update_block: Callable[[Any, Any, Any], Any]
    query_rows: Callable[..., Any]
    query: Callable[..., Any]
    space: Callable[[Any], Any]
    merge: Callable[..., Any]
    query_cohort: Optional[Callable[..., Any]] = None
    query_interval: Optional[Callable[..., Any]] = None
    score: Optional[Callable[..., Any]] = None
    ranks: Optional[Callable[..., Any]] = None


class FleetSpace(NamedTuple):
    """Fleet space accounting: ``per_stream`` is the ``(S,)`` vector of
    per-stream live-row counts (what the pre-query-plane fleet ``space``
    returned), ``cache_rows`` the rows held by the fleet's materialized
    ``AggTree`` nodes, and ``total`` the fleet-wide footprint
    ``per_stream.sum() + cache_rows``.  ``ranks`` is the ``(S,)`` vector
    of per-stream working ranks when the base sketch is adaptive-rank
    (heterogeneous ℓ — the space the fleet *uses*, not a uniform bound),
    else ``None``."""

    per_stream: Any
    total: Any
    cache_rows: int
    ranks: Any = None


_REGISTRY: Dict[str, Callable[..., SlidingSketch]] = {}
_CACHE: Dict[Tuple, SlidingSketch] = {}


def register(name: str) -> Callable:
    """Register a builder ``fn(d, eps, window, **hyper) -> SlidingSketch``."""

    def deco(fn: Callable[..., SlidingSketch]) -> Callable[..., SlidingSketch]:
        _REGISTRY[name] = fn
        return fn

    return deco


def available_sketches() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def _copy_meta(sk: SlidingSketch) -> SlidingSketch:
    """Per-call defensive copy of ``meta`` — the memo cache must never hand
    out a dict one consumer can mutate into every future ``make_sketch``
    hit for that key.  Shallow at the top level (the jitted protocol
    functions stay shared — that is the point of the memo), with the
    ``spec`` section copied one level deeper since it is what fleet
    checkpoints serialize."""
    meta = dict(sk.meta)
    spec = meta.get("spec")
    if spec is not None:
        meta["spec"] = dict(spec, hyper=dict(spec.get("hyper", {})))
    return sk._replace(meta=meta)


def make_sketch(name: str, *, d: int, eps: float = 1 / 8,
                window: int = 1024, **hyper) -> SlidingSketch:
    """Construct a registered sketch variant behind the unified protocol.

    Memoized on (name, d, eps, window, hyper) when hashable, so the jitted
    ``update_block`` of JAX variants compiles once per configuration.  The
    returned ``meta`` dict is a per-call copy (mutating it cannot poison
    future hits) and carries ``meta["spec"]`` — the exact constructor
    arguments — which is what ``save_fleet`` persists so a checkpoint can
    rebuild the sketch from the registry alone.
    """
    if name not in _REGISTRY:
        raise KeyError(
            f"unknown sketch {name!r}; available: {available_sketches()}")
    try:
        key = (name, int(d), float(eps), int(window),
               tuple(sorted(hyper.items())))
        cached = _CACHE.get(key)
    except TypeError:           # unhashable hyperparameter → skip the cache
        key, cached = None, None
    if cached is not None:
        return _copy_meta(cached)
    sk = _REGISTRY[name](int(d), float(eps), int(window), **hyper)
    if sk.score is None:
        # every registered variant scores: JAX variants as one jitted
        # residual program over their own query_rows, host baselines
        # through the numpy SVD adapter
        if sk.meta.get("backend") == "jax":
            _qr = sk.query_rows
            sk = sk._replace(score=make_jax_score(
                lambda state, X, t: residual_scores(_qr(state, t), X)))
        else:
            sk = sk._replace(score=make_host_score(sk.query_rows))
    # fill every absent capability with a context-derived raiser (the
    # hand-rolled per-site raisers this replaces lived here and in the
    # fleet lifts; see repro.sketch.capability)
    sk = capability.install_missing(sk)
    sk.meta["spec"] = {"name": name, "d": int(d), "eps": float(eps),
                       "window": int(window), "hyper": dict(hyper)}
    if key is not None:
        _CACHE[key] = sk
    return _copy_meta(sk)


# ---------------------------------------------------------------------------
# JAX-backed variants
# ---------------------------------------------------------------------------


def _block_scan(update: Callable) -> Callable:
    """Lift a one-row ``update(state, row, t)`` into a jitted block absorb."""

    @jax.jit
    def update_block(state, rows, ts):
        ts = jnp.asarray(ts, jnp.int32)

        def step(st, inp):
            t, row = inp
            return update(st, row, t), None

        return jax.lax.scan(step, state, (ts, rows))[0]

    return update_block


@register("fd")
def _make_fd(d: int, eps: float, window: int, *,
             adapt_target: float | None = None, ell_min: int = 2,
             ell0: int | None = None, **_) -> SlidingSketch:
    """Plain FrequentDirections (Ghashami et al. 2016) — the whole-stream
    primitive, no expiry.  ``window`` is ignored; registered so consumers can
    opt out of sliding semantics without changing call sites.

    ``adapt_target`` opts into **adaptive rank** (the btx ``FreqDir``
    rank-adaption idea): instead of a fixed ℓ = 1/eps, the working rank
    grows/shrinks online toward the named relative covariance error
    (``shed / ‖A‖_F² → adapt_target``), bounded by ``[ell_min, 1/eps]``.
    The buffer keeps the static ``(2·ℓ_max, d)`` shape (jit/vmap/shard_map
    friendly); ``space`` reports the rows actually *occupied* and the
    ``ranks`` capability reports the current ℓ — on easy streams both drop
    well below the fixed-rank footprint.  ``ell0`` seeds the starting rank
    (default ``ell_min``: start cheap, grow only when the error demands)."""
    ell = int(min(max(round(1.0 / eps), 1), d))
    if adapt_target is None:

        def update(state, row, t):
            del t
            return fd_update(state, row, ell=ell)

        def merge(s1, s2, t=None):
            del t               # no expiry — whole-stream semantics
            return fd_merge(s1, s2, ell=ell)

        init = lambda t0=1: fd_init(ell, d)                  # noqa: E731
        meta = {"d": d, "eps": eps, "window": window, "ell": ell,
                "backend": "jax"}
        ranks = None
    else:
        target = float(adapt_target)
        lo = int(min(max(ell_min, 1), ell))
        start = lo if ell0 is None else int(min(max(ell0, lo), ell))
        kw = dict(target=target, ell_min=lo, ell_max=ell)

        def update(state, row, t):
            del t
            return adaptive_fd_update(state, row, **kw)

        def merge(s1, s2, t=None):
            del t
            return adaptive_fd_merge(s1, s2, **kw)

        init = lambda t0=1: adaptive_fd_init(ell, d, ell0=start)  # noqa: E731
        meta = {"d": d, "eps": eps, "window": window, "ell": ell,
                "backend": "jax",
                "adapt": {"target": target, "ell_min": lo,
                          "ell_max": ell, "ell0": start}}
        ranks = lambda state: state.ell                      # noqa: E731

    def query_rows(state, t=None):
        del t
        return state.buf

    def space(state):
        return state.nbuf

    return SlidingSketch(
        name="fd",
        meta=meta,
        init=init,
        update=update,
        update_block=_block_scan(update),
        query_rows=query_rows,
        query=query_rows,       # the FD buffer is already the 2ℓ×d sketch
        space=space,
        merge=merge,
        ranks=ranks,
    )


@register("dsfd")
def _make_dsfd(d: int, eps: float, window: int, *, mode: str = "fast",
               beta: float = 4.0, use_pallas: bool = False,
               **_) -> SlidingSketch:
    """DS-FD (Algorithms 2-4; ``mode`` picks the §3.1 cadence)."""
    cfg = make_config(d, eps, window, mode=mode, beta=beta,
                      use_pallas=use_pallas)

    def update(state, row, t):
        return dsfd_update(cfg, state, row, t)

    def query_rows(state, t=None):
        return dsfd_query_rows(cfg, state, now=t)

    def query(state, t=None):
        return fd_compress(query_rows(state, t), cfg.ell)

    def space(state):
        return (jnp.sum(state.main.snap_valid) + state.main.nbuf
                + jnp.sum(state.aux.snap_valid) + state.aux.nbuf)

    return SlidingSketch(
        name="dsfd",
        meta={"d": d, "eps": eps, "window": window, "ell": cfg.ell,
              "backend": "jax", "cfg": cfg},
        init=lambda t0=1: dsfd_init(cfg, t0),
        update=update,
        update_block=_block_scan(update),
        query_rows=query_rows,
        query=query,
        space=space,
        merge=lambda s1, s2, t=None: dsfd_merge(cfg, s1, s2, now=t),
        score=make_jax_score(
            lambda state, X, t: dsfd_score(cfg, state, X, now=t)),
    )


def _make_layered(name: str, cfg, d, eps, window) -> SlidingSketch:
    def update(state, row, t):
        return layered_update(cfg, state, row, t)

    def query_rows(state, t=None):
        if t is None:
            raise ValueError(
                f"{name} queries need an explicit query time t (layer "
                "selection is time-dependent, Algorithm 7 line 1)")
        return layered_query_rows(cfg, state, t)

    def query(state, t=None):
        return fd_compress(query_rows(state, t), cfg.base.ell)

    def space(state):
        return (jnp.sum(state.main.snap_valid) + jnp.sum(state.main.nbuf)
                + jnp.sum(state.aux.snap_valid) + jnp.sum(state.aux.nbuf))

    return SlidingSketch(
        name=name,
        meta={"d": d, "eps": eps, "window": window, "ell": cfg.base.ell,
              "backend": "jax", "cfg": cfg},
        init=lambda t0=1: layered_init(cfg, t0),
        update=update,
        update_block=_block_scan(update),
        query_rows=query_rows,
        query=query,
        space=space,
        merge=lambda s1, s2, t=None: layered_merge(cfg, s1, s2, now=t),
    )


@register("seq-dsfd")
def _make_seq_dsfd(d: int, eps: float, window: int, *, R: float = 64.0,
                   beta: float = 4.0, mode: str = "fast",
                   **_) -> SlidingSketch:
    """Seq-DS-FD (Algorithms 5-7): unnormalized rows ‖a‖² ∈ [1, R]."""
    cfg = make_seq_config(d, eps, window, R, beta=beta, mode=mode)
    return _make_layered("seq-dsfd", cfg, d, eps, window)


@register("time-dsfd")
def _make_time_dsfd(d: int, eps: float, window: int, *, R: float = 64.0,
                    beta: float = 4.0, mode: str = "fast",
                    **_) -> SlidingSketch:
    """Time-DS-FD (§5): time-based windows, idle ticks are zero rows."""
    cfg = make_time_config(d, eps, window, R, beta=beta, mode=mode)
    return _make_layered("time-dsfd", cfg, d, eps, window)


# ---------------------------------------------------------------------------
# Host-side (numpy) baselines behind the same protocol
# ---------------------------------------------------------------------------


def _host_sketch(name: str, ctor: Callable[[], Any],
                 meta: Dict[str, Any]) -> SlidingSketch:
    """Adapter: numpy ``.update()/.query()/.n_rows_stored`` classes → the
    protocol.  The state *is* the mutable object; ``update`` returns it so
    call sites read identically to the pure-functional variants."""

    def init(t0=1):
        del t0
        return ctor()

    def update(state, row, t):
        state.update(np.asarray(row), int(t))
        return state

    def update_block(state, rows, ts):
        rows = np.asarray(rows)
        ts = np.asarray(ts)
        for i in range(rows.shape[0]):
            state.update(rows[i], int(ts[i]))
        return state

    def query_rows(state, t=None):
        del t                       # host baselines track time internally
        return state.query()

    def space(state):
        return state.n_rows_stored

    def merge(s1, s2, t=None):
        """Native baseline combine (DI-FD / SWR / SWOR); LM-FD raises a
        documented ``NotImplementedError``.  Mutates and returns ``s1``."""
        del t                       # host baselines track time internally
        return s1.combine(s2)

    return SlidingSketch(
        name=name,
        meta=dict(meta, backend="host"),
        init=init,
        update=update,
        update_block=update_block,
        query_rows=query_rows,
        query=query_rows,           # baseline queries are already compressed
        space=space,
        merge=merge,
    )


@register("lmfd")
def _make_lmfd(d: int, eps: float, window: int, *,
               blocks_per_level: int | None = None, **_) -> SlidingSketch:
    """LM-FD — FD in the Exponential Histogram framework (§2.2)."""
    from repro.core.baselines import LMFD

    return _host_sketch(
        "lmfd",
        lambda: LMFD(d, eps, window, blocks_per_level=blocks_per_level),
        {"d": d, "eps": eps, "window": window,
         "ell": int(max(1, min(round(1.0 / eps), d)))})


@register("difd")
def _make_difd(d: int, eps: float, window: int, *, R: float = 1.0,
               **_) -> SlidingSketch:
    """DI-FD — FD over dyadic intervals (§2.2); sequence-based only."""
    from repro.core.baselines import DIFD

    return _host_sketch(
        "difd", lambda: DIFD(d, eps, window, R=R),
        {"d": d, "eps": eps, "window": window,
         "ell": int(max(1, min(round(1.0 / eps), d)))})


def _sampler_ell(eps: float, ell: int | None) -> int:
    return int(ell if ell is not None else min(max(4.0 / eps ** 2, 8), 4096))


@register("swr")
def _make_swr(d: int, eps: float, window: int, *, ell: int | None = None,
              seed: int = 0, **_) -> SlidingSketch:
    """SWR — sliding-window row sampling with replacement (§7 baselines)."""
    from repro.core.baselines import SWR

    k = _sampler_ell(eps, ell)
    return _host_sketch(
        "swr", lambda: SWR(d, ell=k, window=window, seed=seed),
        {"d": d, "eps": eps, "window": window, "ell": k})


@register("swor")
def _make_swor(d: int, eps: float, window: int, *, ell: int | None = None,
               seed: int = 0, **_) -> SlidingSketch:
    """SWOR — sampling without replacement (Efraimidis–Spirakis keys)."""
    from repro.core.baselines import SWOR

    k = _sampler_ell(eps, ell)
    return _host_sketch(
        "swor", lambda: SWOR(d, ell=k, window=window, seed=seed),
        {"d": d, "eps": eps, "window": window, "ell": k})


# ---------------------------------------------------------------------------
# The fleet lift (the serving-scale path)
# ---------------------------------------------------------------------------


def _require_jax(sk: SlidingSketch, who: str) -> None:
    if sk.meta.get("backend") != "jax":
        raise ValueError(
            f"{who} requires a JAX-backed sketch, got {sk.name!r} "
            f"(backend={sk.meta.get('backend')!r})")


def vmap_streams(sk: SlidingSketch, streams: int) -> SlidingSketch:
    """Lift a JAX-backed sketch to ``streams`` independent streams in one
    program on the default device (the lift with no placement).

    State leaves gain a leading ``(S, ...)`` axis; ``update`` takes
    ``(S, d)`` rows and ``(S,)`` timestamps; ``update_block`` takes
    ``(S, B, d)`` rows and ``(B,)`` or ``(S, B)`` timestamps and runs all
    streams in **one fused XLA program**.  ``query_rows`` / ``query``
    broadcast a scalar query time across streams.
    """
    _require_jax(sk, "vmap_streams")
    return _lift(sk, int(streams))


def shard_streams(sk: SlidingSketch, streams: int, mesh=None, *,
                  axis: str = "streams", topology=None) -> SlidingSketch:
    """Lift a JAX-backed sketch to a device-sharded fleet of ``streams``.

    Every device of ``mesh`` (default: a 1-D mesh over this process's
    local devices) owns ``streams / n_devices`` per-user sketches and runs
    the same vmapped block scan on them — one ``shard_map``'d SPMD program
    per ``update_block``, no cross-device traffic on the update path
    (streams are independent).  State leaves are sharded along their
    leading ``(S, ...)`` stream axis; ``init`` returns the state already
    placed.  Aggregate (cross-shard) queries go through
    :func:`query_cohort`, whose upper tree-merge rounds are where the
    collective traffic lives.

    ``streams`` must be a multiple of the mesh axis size.

    Multi-host: pass ``topology`` (a
    :class:`repro.parallel.topology.FleetTopology`) and this process
    holds only its OWN contiguous stream range — state leaves have
    leading axis ``topology.local_size``, laid out over ``mesh``, and
    ``update_block`` takes the local slab.  ``query_cohort`` still takes
    GLOBAL cohorts and is a collective answered through a
    :class:`~repro.parallel.topology.PartitionedAggTree` (owned subtrees
    served locally, only the O(log S) top spine crossing processes as
    compressed ``2ℓ×d`` node states, bit-identical to the unsplit
    fleet).  Without a topology, a multi-process runtime is rejected
    loudly — the implicit all-local-devices mesh would silently build a
    fleet whose global shape no process actually holds.
    """
    _require_jax(sk, "shard_streams")
    S = int(streams)
    if topology is not None and topology.S != S:
        raise ValueError(
            f"topology covers {topology.S} streams but shard_streams was "
            f"asked for {S} — build both from the same fleet size")
    if mesh is None:
        if topology is None and jax.process_count() > 1:
            raise ValueError(
                f"shard_streams(streams={S}) in a multi-process "
                f"runtime (process_count={jax.process_count()}) needs a "
                "topology: the default mesh covers only this process's "
                "local devices, so a global-shape fleet state would exist "
                "on no process.  Pass topology=FleetTopology(streams) "
                "(repro.parallel.topology) so each process owns a "
                "contiguous stream range, or pass an explicit mesh if you "
                "really mean a per-process private fleet.")
        from repro.launch.mesh import make_local_mesh
        mesh = make_local_mesh(axis)
    return _lift(sk, S, mesh=mesh, axis=axis, topology=topology)


def _lift(sk: SlidingSketch, S: int, *, mesh=None, axis: str = "streams",
          topology=None) -> SlidingSketch:
    """The one fleet lift behind :func:`vmap_streams` and
    :func:`shard_streams`.

    The placement is none (one program on the default device), a
    ``mesh`` (the streams split over its ``axis`` devices) or a
    ``topology`` over a mesh (this process's stream range only).  ``S``
    is the fleet's global stream count; state leaves hold the streams of
    this process.  ``update_block``, ``score`` and ``ranks`` become fleet
    programs through one helper; ``update``, ``query_rows``, ``query``,
    ``merge`` and ``space`` are plain ``vmap``s under every placement.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    n = S if topology is None else int(topology.local_size)
    meta = dict(sk.meta, streams=S, base=sk, agg_box={})
    sharding = None
    per_device = n
    name = f"vmap[{sk.name}x{S}]"
    if mesh is not None:
        ndev = int(mesh.shape[axis])
        if n % ndev:
            raise ValueError(f"streams={n} must divide over {ndev} devices")
        per_device = n // ndev
        sharding = NamedSharding(mesh, P(axis))
        meta.update(mesh=mesh, devices=ndev, axis=axis,
                    slab_sharding=sharding)
        name = f"shard[{sk.name}x{S}/{ndev}]"
    if topology is not None:
        lo, hi = topology.lo, topology.hi
        meta.update(topology=topology, local_streams=n, local_range=(lo, hi))
        name = f"topo[{sk.name}x{S}@{topology.pid}/{topology.P}:{lo}-{hi}]"

    def program(fn, in_axes):
        """``fn`` over one stream → its jitted fleet program: ``vmap``
        over the streams, ``shard_map`` over the mesh axis when there is
        one.  An argument of axis ``None`` is shared by every stream: it
        is replicated over the mesh and broadcast per stream inside."""
        v = jax.vmap(fn)

        def lifted(*args):
            return v(*(a if ax == 0
                       else jnp.broadcast_to(a, (per_device,) + a.shape)
                       for a, ax in zip(args, in_axes)))

        lifted.__name__ = fn.__name__   # the program is named after fn
        if mesh is not None:
            lifted = jax.shard_map(
                lifted, mesh=mesh, out_specs=P(axis), check_vma=False,
                in_specs=tuple(P(axis) if ax == 0 else P() for ax in in_axes))
        return jax.jit(lifted)

    def place(rows):
        """A host slab is placed along the streams; a device array (a
        slab prefetched with ``meta["slab_sharding"]``) passes through
        untouched, with no re-transfer."""
        if sharding is None or isinstance(rows, jax.Array):
            return rows
        return jax.device_put(np.asarray(rows), sharding)

    def init(t0=1):
        one = sk.init(t0)
        return jax.tree.map(
            lambda x: jnp.broadcast_to(x, (n,) + jnp.shape(x)), one)

    if sharding is not None:
        # built in place: each device materializes only its own stream
        # shard (an eager init would first hold the whole fleet on one)
        init = jax.jit(init, out_shardings=sharding)

    # shared (B,) tick stamps are replicated and broadcast per stream;
    # per-stream (S, B) stamps are sharded with the streams
    block_shared = program(sk.update_block, (0, 0, None))
    block = program(sk.update_block, (0, 0, 0))

    def pick(ts):
        return block_shared if len(ts.shape) == 1 else block

    def update_block(state, rows, ts):
        if not isinstance(ts, jax.Array):
            ts = np.asarray(ts, np.int32)
        return pick(ts)(state, place(rows), ts)

    # lowers the update program from shapes, dtypes and shardings alone
    update_block.lower = lambda state, rows, ts: pick(ts).lower(
        state, rows, ts)

    v_update = jax.vmap(sk.update)

    def update(state, rows, ts):
        ts = jnp.broadcast_to(jnp.asarray(ts, jnp.int32), (n,))
        return v_update(state, rows, ts)

    def query_rows(state, t=None):
        return jax.vmap(lambda s: sk.query_rows(s, t))(state)

    def query(state, t=None):
        return jax.vmap(lambda s: sk.query(s, t))(state)

    def merge(s1, s2, t=None):
        return jax.vmap(lambda a, b: sk.merge(a, b, t))(s1, s2)

    # the query plane: the fleet's tree, created lazily on first use
    # (see agg_tree), so fleets that never issue aggregate queries pay
    # nothing
    def query_cohort(state, cohort=ALL, t=None):
        return agg_tree(fleet).query(state, cohort, t)

    # the scoring plane: the raw per-stream residual program (tagged
    # ``_per_stream`` by repro.sketch.score) scores a whole slab in the
    # same program shape as the block update
    score = None
    raw = getattr(sk.score, "_per_stream", None)
    if raw is not None:
        def scoped(s, x, t):
            with jax.named_scope("fleet.score"):
                return raw(s, x, t)

        score_t = program(scoped, (0, 0, None))
        score_nt = program(lambda s, x: scoped(s, x, None), (0, 0))

        def score(state, rows, t=None):
            rows = place(rows)
            if t is None:
                return score_nt(state, rows)
            return score_t(state, rows, jnp.asarray(t, jnp.int32))

    ranks = (program(sk.ranks, (0,)) if capability.has(sk, "ranks")
             else None)
    v_space = jax.vmap(sk.space)

    def space(state):
        per = v_space(state)
        tree = meta["agg_box"].get("tree")
        cache_rows = 0 if tree is None else tree.space()
        return FleetSpace(per_stream=per,
                          total=jnp.sum(per) + cache_rows,
                          cache_rows=cache_rows,
                          ranks=None if ranks is None else ranks(state))

    fleet = capability.install_missing(SlidingSketch(
        name=name,
        meta=meta,
        init=init,
        update=update,
        update_block=update_block,
        query_rows=query_rows,
        query=query,
        space=space,
        merge=merge,
        query_cohort=query_cohort,
        score=score,
        ranks=ranks,
    ))
    return fleet


def query_cohort(fleet: SlidingSketch, state, cohort=ALL, t=None):
    """Aggregate query over a :class:`Cohort` of a fleet's streams.

    Returns ONE merged base-variant state covering the union of the
    cohort's per-stream windows at query time ``t`` — compress it with
    ``fleet.meta["base"].query(g, t)``.  Answers come from the fleet's
    cached :class:`AggTree` (segment tree of partial merges, pad-free for
    any fleet size): the first query over a region materializes its
    canonical nodes once; every later query over any overlapping cohort
    at the same clock reuses them, so a warm query costs O(log S) node
    merges instead of the O(S) from-scratch reduction.

    ``cohort`` composes via union: ``Cohort.range(0, 64) | Cohort.of(80)``.
    Pass :data:`ALL` (the default) for the whole-fleet aggregate.
    """
    if (not capability.has(fleet, "query_cohort")
            or fleet.meta.get("base") is None):
        raise ValueError(
            f"query_cohort needs a fleet from vmap_streams/shard_streams, "
            f"got {fleet.name!r}")
    return fleet.query_cohort(state, cohort, t)


def query_interval(fleet: SlidingSketch, state, t1, t2, cohort=ALL):
    """Time-travel query: ONE compressed ``(2ℓ, d)`` sketch of every row
    the ``cohort``'s streams ingested with timestamp in ``[t1, t2)``,
    answered from the fleet's persistent history plane of *retired*
    (expired-from-window) content — ``O(log(t2 − t1))`` dyadic node
    merges, under the FD mergeability additive-error guarantee.

    Needs a fleet with a plane attached (``SketchFleetEngine(...,
    history=True)`` or ``repro.sketch.history.install_query_interval``);
    anything else raises with receiver-correct directions (the capability
    raiser — a fleet is told how to attach a plane, a single sketch how
    to become a fleet first).  See ``repro.sketch.history`` for the
    canonical dyadic schedule the answer is pinned to.
    """
    fn = fleet.query_interval
    if fn is None:
        fn = capability.missing("query_interval", fleet)
    return fn(state, t1, t2, cohort)


def agg_tree(fleet: SlidingSketch) -> AggTree:
    """The fleet's shared query-plane tree (created lazily on first use) —
    for cache accounting, targeted ``advance``/``dirty`` invalidation, and
    checkpoint persistence of materialized nodes.  A plain fleet gets an
    :class:`AggTree`; a topology-sharded fleet gets its collective
    :class:`~repro.parallel.topology.PartitionedAggTree`."""
    box = fleet.meta.get("agg_box")
    if box is None:
        raise ValueError(
            f"agg_tree needs a fleet from vmap_streams/shard_streams, "
            f"got {fleet.name!r}")
    tree = box.get("tree")
    if tree is None:
        topo = fleet.meta.get("topology")
        if topo is not None:
            from repro.parallel.topology import PartitionedAggTree
            tree = box["tree"] = PartitionedAggTree(fleet.meta["base"],
                                                    topo)
        else:
            tree = box["tree"] = AggTree(fleet.meta["base"],
                                         int(fleet.meta["streams"]))
    return tree


def merge_streams(fleet: SlidingSketch, state, t=None):
    """Deprecated alias: the whole-fleet aggregate is now
    ``query_cohort(fleet, state, ALL, t)`` — same merged base-variant
    state, but served from the fleet's cached :class:`AggTree` (repeated
    calls between ingests are near-free) instead of an O(S) re-reduction
    per call.  The uncached from-scratch reduction survives as
    :func:`repro.sketch.query.full_reduce_streams` (the uncached
    reference).  Kept for import compatibility; new code should call
    :func:`query_cohort`.
    """
    import warnings

    warnings.warn(
        "merge_streams(fleet, state, t) is deprecated — call "
        "query_cohort(fleet, state, ALL, t) (same merged state, served "
        "from the fleet's cached AggTree); the uncached O(S) reduction "
        "lives on as repro.sketch.query.full_reduce_streams",
        DeprecationWarning, stacklevel=2)
    return query_cohort(fleet, state, ALL, t)


# ---------------------------------------------------------------------------
# Fleet persistence — mesh-aware checkpoint/restore over train/checkpoint.py
# ---------------------------------------------------------------------------


class FleetCheckpoint(NamedTuple):
    """What ``restore_fleet`` hands back: a rebuilt fleet (laid out on the
    *target* mesh), its restored state, the fleet clock at save time, any
    auxiliary host arrays saved alongside, and the raw manifest."""

    fleet: SlidingSketch
    state: Any
    t: int
    aux: Dict[str, np.ndarray]
    manifest: Dict[str, Any]


def save_fleet(path: str, fleet: SlidingSketch, state, t, *,
               aux: Dict[str, np.ndarray] | None = None,
               spec_extra: Dict[str, Any] | None = None,
               keep: int = 3) -> str:
    """Atomic mesh-agnostic checkpoint of a fleet's state at clock ``t``.

    The state pytree is pure data (FD-style sketches carry no closures),
    so the on-disk format is the shared ``train/checkpoint.py`` layout —
    one ``.npy`` per leaf behind an atomically-renamed manifest — with a
    ``sketch_spec`` manifest section recording everything needed to
    rebuild the fleet from the registry: the base sketch's ``make_sketch``
    name/kwargs, the fleet size, the mesh axis name, and the fleet clock.
    Leaves are gathered to full host arrays, which is the whole elastic
    story: :func:`restore_fleet` re-lays them out on whatever mesh the
    restoring process has.

    ``aux``: optional flat ``{name: array}`` of host-side extras persisted
    in the same atomic checkpoint (e.g. a serving engine's pending
    queues).  ``spec_extra``: optional JSON-serializable entries merged
    into the ``sketch_spec`` section.
    """
    import json

    from repro.train import checkpoint as ckpt

    base = fleet.meta.get("base")
    if base is None:
        raise ValueError(
            f"save_fleet needs a fleet from vmap_streams/shard_streams, "
            f"got {fleet.name!r}")
    spec = base.meta.get("spec")
    if spec is None:
        raise ValueError(
            f"fleet base {base.name!r} has no construction spec — build it "
            "via make_sketch() so the checkpoint can name it in the "
            "registry")
    mesh = fleet.meta.get("mesh")
    topo = fleet.meta.get("topology")
    aux = dict(aux or {})
    sketch_spec: Dict[str, Any] = {
        "sketch": spec,
        "streams": int(fleet.meta["streams"]),
        "sharded": mesh is not None,
        "mesh_axis": fleet.meta.get("axis"),
        "mesh_devices": (int(fleet.meta["devices"])
                         if mesh is not None else None),
        "t": int(t),
        "aux_keys": sorted(aux),
    }
    if topo is not None:
        # one self-describing shard manifest per process, side by side
        # under `path` — restore_fleet reassembles ANY process count from
        # whatever shards it finds (process-elastic, PR 3's device
        # elasticity one level up)
        sketch_spec["topology"] = topo.spec()
        sketch_spec["local_streams"] = int(topo.local_size)
        path = fleet_shard_dir(path, topo.lo, topo.hi)
    if spec_extra:
        sketch_spec.update(spec_extra)
    try:
        json.dumps(sketch_spec)
    except TypeError as e:
        raise ValueError(
            f"fleet checkpoint spec is not JSON-serializable ({e}); "
            "sketch hyperparameters and spec_extra must be plain "
            "scalars/strings") from e
    tree = {"aux": {k: np.asarray(aux[k]) for k in aux},
            "state": state}
    return ckpt.save(
        path, int(t), tree, sketch_spec=sketch_spec,
        mesh_shape=tuple(np.shape(mesh.devices)) if mesh is not None
        else None,
        keep=keep)


def fleet_shard_dir(path: str, lo: int, hi: int) -> str:
    """Per-process shard directory of a topology-partitioned checkpoint."""
    import os

    return os.path.join(str(path), f"shard-{int(lo):06d}-{int(hi):06d}")


def _fleet_shards(path: str):
    """``[(lo, hi, dir)]`` shard checkpoints under ``path`` (stream order),
    or ``[]`` when ``path`` is a plain single-manifest fleet checkpoint."""
    import os
    import re

    out = []
    try:
        entries = sorted(os.listdir(path))
    except (FileNotFoundError, NotADirectoryError):
        return out
    for name in entries:
        m = re.fullmatch(r"shard-(\d{6})-(\d{6})", name)
        if m and os.path.isdir(os.path.join(path, name)):
            out.append((int(m.group(1)), int(m.group(2)),
                        os.path.join(path, name)))
    return out


def restore_fleet(path: str, mesh=None, *, step: int | None = None,
                  topology=None) -> FleetCheckpoint:
    """Rebuild a fleet from a :func:`save_fleet` checkpoint — elastically.

    The base sketch is reconstructed from the registry using the
    ``sketch_spec`` manifest section, the fleet is re-laid-out with
    ``shard_streams`` over ``mesh`` (default: a fresh 1-D mesh over the
    *restoring* process's local devices, whose count need not match the
    saving one as long as it divides the fleet size), and every state
    leaf is ``device_put`` with the target mesh's shardings.  Restoring
    a ``vmap_streams`` (unsharded) checkpoint ignores ``mesh``.

    Process elasticity: the save-time and restore-time process counts
    are independent.  A topology fleet saves one self-describing shard
    manifest per process (``shard-LLLLLL-HHHHHH/`` under ``path``);
    ``restore_fleet`` assembles THIS caller's stream range from whatever
    layout it finds — plain checkpoint restored under a ``topology``
    slices the caller's range out; shard checkpoints restored without a
    topology gather back into one full fleet; shard checkpoints restored
    under a different process count slice-and-concatenate the
    overlapping shards.  Per-stream leaves are exact row slices, so
    every reassembly is bit-identical.  ``aux`` arrays ride along
    concatenated in stream order (they are row-aligned per shard, e.g.
    the engine's pending queues — consumers filter by ownership).

    Returns a :class:`FleetCheckpoint`; continuing the stream from
    ``.state`` at clock ``.t`` is numerically identical to never having
    stopped (the sketches are pure data and the clock is persisted).
    """
    from repro.train import checkpoint as ckpt

    shards = _fleet_shards(path)
    if not shards and topology is None:
        manifest = ckpt.read_manifest(path, step=step)
        ss = _fleet_spec_of(manifest, path)
        spec = ss["sketch"]
        sk = make_sketch(spec["name"], d=spec["d"], eps=spec["eps"],
                         window=spec["window"], **spec.get("hyper", {}))
        S = int(ss["streams"])
        shardings = None
        if ss.get("sharded"):
            from jax.sharding import NamedSharding, PartitionSpec as P

            axis = ss.get("mesh_axis") or "streams"
            fleet = shard_streams(sk, S, mesh, axis=axis)
            sharding = NamedSharding(fleet.meta["mesh"], P(axis))
        else:
            fleet, sharding = vmap_streams(sk, S), None
        state_like = jax.eval_shape(lambda: fleet.init())
        aux_keys = list(ss.get("aux_keys", []))
        tree_like = {"aux": {k: 0 for k in aux_keys}, "state": state_like}
        if sharding is not None:
            shardings = {"aux": {k: None for k in aux_keys},
                         "state": jax.tree.map(lambda _: sharding,
                                               state_like)}
        # pin the step resolved above — a concurrent saver landing a new
        # step between read_manifest and restore must not change which
        # checkpoint the leaves come from (the template tree was built
        # for THIS manifest)
        tree, manifest = ckpt.restore(path, tree_like,
                                      step=int(manifest["step"]),
                                      shardings=shardings,
                                      host_leaves=_is_aux_leaf)
        aux = {k: np.asarray(v) for k, v in tree["aux"].items()}
        return FleetCheckpoint(fleet, tree["state"], int(ss["t"]), aux,
                               manifest)
    return _restore_fleet_elastic(path, shards, mesh, step, topology)


def _is_aux_leaf(path: str) -> bool:
    """Manifest-path predicate for ``ckpt.restore(host_leaves=...)``: aux
    arrays are host-side extras (pending queues, the engine's float64 EWMA
    score accumulators) — they must come back at their on-disk dtype, not
    through a jnp round-trip that downcasts f64/i64 when x64 is off."""
    return path.startswith("['aux']")


def _fleet_spec_of(manifest, path) -> Dict[str, Any]:
    ss = manifest.get("sketch_spec")
    if not ss:
        raise ValueError(
            f"checkpoint under {path!r} has no sketch_spec manifest "
            "section — not a fleet checkpoint (train states restore via "
            "repro.train.checkpoint.restore)")
    return ss


def _restore_fleet_elastic(path, shards, mesh, step, topology
                           ) -> FleetCheckpoint:
    """Cross-process-count reassembly: slice the caller's stream range
    out of whatever shard layout ``path`` holds (see ``restore_fleet``)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.train import checkpoint as ckpt

    # -- source manifests ---------------------------------------------------
    if shards:
        sources = []
        for lo, hi, sdir in shards:
            manifest = ckpt.read_manifest(sdir, step=step)
            sources.append((lo, hi, sdir, manifest,
                            _fleet_spec_of(manifest, sdir)))
    else:
        manifest = ckpt.read_manifest(path, step=step)
        ss0 = _fleet_spec_of(manifest, path)
        sources = [(0, int(ss0["streams"]), path, manifest, ss0)]
    ss = sources[0][4]
    S, t = int(ss["streams"]), int(ss["t"])
    for lo, hi, sdir, _, ssi in sources:
        if ssi["sketch"] != ss["sketch"] or int(ssi["streams"]) != S:
            raise ValueError(
                f"shard {sdir!r} disagrees with its siblings on the fleet "
                "spec — shards of one checkpoint must come from one fleet")
        if int(ssi["t"]) != t:
            raise ValueError(
                f"shard {sdir!r} was saved at clock {ssi['t']} but its "
                f"siblings at {t} — processes must checkpoint the same "
                "tick (the engine checkpoint path is a collective)")
    spec = ss["sketch"]
    sk = make_sketch(spec["name"], d=spec["d"], eps=spec["eps"],
                     window=spec["window"], **spec.get("hyper", {}))
    axis = ss.get("mesh_axis") or "streams"

    # -- target fleet -------------------------------------------------------
    if topology is not None:
        if topology.S != S:
            raise ValueError(
                f"checkpoint holds {S} streams but the topology covers "
                f"{topology.S}")
        fleet = shard_streams(sk, S, mesh, axis=axis, topology=topology)
        tlo, thi = topology.lo, topology.hi
    else:
        fleet = shard_streams(sk, S, mesh, axis=axis)
        tlo, thi = 0, S

    # -- gather + slice the overlapping shards, stream order ----------------
    overlapping = [(lo, hi, sdir, m, ssi)
                   for lo, hi, sdir, m, ssi in sources
                   if lo < thi and hi > tlo]
    cover = tlo
    pieces, aux_pieces = [], []
    for lo, hi, sdir, m, ssi in sorted(overlapping):
        if lo > cover:
            break
        cover = max(cover, hi)
        src = vmap_streams(sk, hi - lo)
        state_like = jax.eval_shape(lambda: src.init())
        aux_keys = list(ssi.get("aux_keys", []))
        tree_like = {"aux": {k: 0 for k in aux_keys}, "state": state_like}
        tree, _ = ckpt.restore(sdir, tree_like, step=int(m["step"]),
                               host_leaves=_is_aux_leaf)
        a, b = max(tlo, lo) - lo, min(thi, hi) - lo
        pieces.append(jax.tree.map(lambda x: np.asarray(x)[a:b],
                                   tree["state"]))
        aux_pieces.append({k: np.asarray(v)
                           for k, v in tree["aux"].items()})
    if cover < thi:
        raise ValueError(
            f"checkpoint under {path!r} has no shard covering streams "
            f"[{cover}, {thi}) — incomplete save (a process died before "
            "its shard landed?)")
    state_np = jax.tree.map(
        lambda *xs: np.concatenate(xs, axis=0) if len(xs) > 1 else xs[0],
        *pieces)
    sharding = NamedSharding(fleet.meta["mesh"], P(axis))
    state = jax.tree.map(lambda x: jax.device_put(x, sharding), state_np)
    aux: Dict[str, np.ndarray] = {}
    for k in {k for p in aux_pieces for k in p}:
        vals = [p[k] for p in aux_pieces if k in p]
        aux[k] = vals[0] if len(vals) == 1 else np.concatenate(vals, axis=0)
    return FleetCheckpoint(fleet, state, t, aux, sources[0][3])
