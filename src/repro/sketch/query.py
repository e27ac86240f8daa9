"""Query plane for sketch fleets: cohort algebra + cached merge trees.

PR 1-3 made *ingest* scale — ``vmap_streams`` / ``shard_streams`` advance
thousands of per-user sliding-window sketches as one SPMD program — but
aggregate *queries* still tree-reduced the whole fleet from scratch on
every call.  The paper's mergeability result (DS-FD merges compose with
additive covariance error, §3; Liberty 2013) means an aggregate answer
over ANY subset of streams can be assembled from cached partial merges
instead, which is what this module provides:

``Cohort``
    A frozen, normalized set of stream indices — a union of half-open
    ``[lo, hi)`` ranges over the fleet's stream axis.  Build one with
    ``Cohort.of(3, 7, 8)``, ``Cohort.range(0, 64)``, or the ``ALL``
    singleton, and compose with ``|`` (union).  Cohorts are hashable
    values: the same cohort is the same cache key.

``AggTree``
    A segment tree of partial merges over the stream axis ``[0, S)``.
    Leaves are per-stream sketch states sliced out of the fleet state;
    each internal node ``[lo, hi)`` is the base variant's
    ``merge(node[lo, mid), node[mid, hi), t)`` with ``mid = (lo+hi)//2``
    — pad-free, so *any* fleet size works, not just powers of two.
    Internal nodes are materialized lazily and cached with the query
    time they were merged at; ``query(state, cohort, t)`` decomposes the
    cohort into at most ``2⌈log₂S⌉`` canonical nodes per contiguous run
    and left-folds them, so a warm query costs O(log S) cached-node
    merges while a cold full reduction costs S−1 (performed once, then
    amortized across every subsequent cohort).

    Correctness contract: ``query(state, cohort, t)`` is bit-identical
    to a from-scratch midpoint-split merge fold over the same streams at
    the same ``t`` (pinned by ``tests/sketch/test_query.py``).  Caching
    never changes answers:

    * a cached node is reused only when its time tag equals the query
      time (``merge`` re-applies expiry at ``t``, so results are only
      reusable at the ``t`` they were computed for), and
    * the tree tracks the identity of the fleet state it was built
      from — passing a *different* state object without announcing it
      via :meth:`AggTree.advance` resets the cache wholesale (sound,
      never stale).  Engines that know exactly which streams an ingest
      touched call ``advance(state, touched)`` instead, which dirties
      only the root-to-leaf paths of those streams.

The serving win: between ingest steps the fleet clock is constant, so
heavy aggregate-query traffic ("error of cohort X over its last-W
rows") hits warm nodes — repeated queries are near-free, and different
cohorts share every canonical node they have in common.  The cohort
structure is also what a multi-host fleet shards along (each host owns
a contiguous sub-tree; only the O(log S) top spine crosses hosts).

The merged state a query returns is a full base-variant state, so every
capability of the base sketch applies to it: ``base.query`` for the
compressed window, ``base.query_rows`` + ``topr_basis`` for a cohort
subspace, and ``base.score(merged, rows, t)`` for residual anomaly
scores of probe rows against the cohort's merged window basis (the
scoring plane, ``repro.sketch.score`` — served by
``SketchFleetEngine.score_cohort``).
"""

from __future__ import annotations

from typing import Any, Dict, Iterable, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

__all__ = ["ALL", "AggTree", "Cohort", "canonical_cover",
           "full_reduce_streams"]


def canonical_cover(lo: int, hi: int, qlo: int, qhi: int,
                    out: List[Tuple[int, int]]) -> None:
    """Canonical segment-tree cover of ``[qlo, qhi)`` within the
    midpoint-split node ``[lo, hi)`` — at most ``2⌈log₂S⌉`` canonical
    nodes, appended to ``out`` in stream order.

    This is THE decomposition both query planes share: ``AggTree`` uses
    it over a single fleet's ``[0, S)``, and the partitioned plane
    (``repro.parallel.topology``) uses the identical recursion over the
    global stream axis so every process derives the same spine nodes —
    a prerequisite for bit-identical cross-process answers.
    """
    if qlo <= lo and hi <= qhi:
        out.append((lo, hi))
        return
    mid = (lo + hi) // 2
    if qlo < mid:
        canonical_cover(lo, mid, qlo, min(qhi, mid), out)
    if qhi > mid:
        canonical_cover(mid, hi, max(qlo, mid), qhi, out)


# ---------------------------------------------------------------------------
# Cohort algebra
# ---------------------------------------------------------------------------


class Cohort:
    """A frozen, normalized union of half-open stream-index ranges.

    Normal form: ranges are sorted, disjoint, non-empty, and non-adjacent
    (touching ranges are coalesced), so two cohorts covering the same
    index set compare and hash equal — a ``Cohort`` is a *value*, usable
    directly as a cache key.  ``ALL`` is the distinguished whole-fleet
    cohort; its extent is resolved against the fleet size at query time.
    """

    __slots__ = ("_ranges",)

    def __init__(self, ranges: Iterable[Tuple[int, Optional[int]]] = ()):
        self._ranges = self._normalize(ranges)

    @staticmethod
    def _normalize(ranges) -> Tuple[Tuple[int, Optional[int]], ...]:
        concrete: List[Tuple[int, int]] = []
        unbounded_lo: Optional[int] = None        # smallest lo with hi=None
        for lo, hi in ranges:
            lo = int(lo)
            if lo < 0:
                raise ValueError(f"stream index {lo} is negative")
            if hi is None:
                unbounded_lo = lo if unbounded_lo is None \
                    else min(unbounded_lo, lo)
                continue
            hi = int(hi)
            if hi <= lo:
                raise ValueError(f"empty/inverted range [{lo}, {hi})")
            concrete.append((lo, hi))
        concrete.sort()
        merged: List[List[int]] = []
        for lo, hi in concrete:
            if merged and lo <= merged[-1][1]:    # overlap or adjacency
                merged[-1][1] = max(merged[-1][1], hi)
            else:
                merged.append([lo, hi])
        out: List[Tuple[int, Optional[int]]] = [(lo, hi)
                                                for lo, hi in merged]
        if unbounded_lo is not None:
            # an open-ended tail swallows every bounded range at/after it
            while out and out[-1][1] is not None \
                    and out[-1][1] >= unbounded_lo:
                unbounded_lo = min(unbounded_lo, out.pop()[0])
            out.append((unbounded_lo, None))
        return tuple(out)

    # -- constructors -------------------------------------------------------

    @classmethod
    def of(cls, *indices: int) -> "Cohort":
        """Cohort of explicit stream indices: ``Cohort.of(3, 7, 8, 9)``.
        A single iterable argument is also accepted."""
        if len(indices) == 1 and not isinstance(indices[0], (int, np.integer)):
            indices = tuple(indices[0])
        return cls((int(i), int(i) + 1) for i in indices)

    @classmethod
    def range(cls, lo: int, hi: int) -> "Cohort":
        """Contiguous cohort ``[lo, hi)`` over the stream axis."""
        return cls([(lo, hi)])

    # -- algebra ------------------------------------------------------------

    def __or__(self, other: "Cohort") -> "Cohort":
        if not isinstance(other, Cohort):
            return NotImplemented
        return Cohort(self._ranges + other._ranges)

    def union(self, other: "Cohort") -> "Cohort":
        return self | other

    # -- inspection ---------------------------------------------------------

    @property
    def ranges(self) -> Tuple[Tuple[int, Optional[int]], ...]:
        return self._ranges

    @property
    def is_all(self) -> bool:
        return self._ranges == ((0, None),)

    def resolve(self, streams: int) -> Tuple[Tuple[int, int], ...]:
        """Concrete ``(lo, hi)`` ranges for a fleet of ``streams`` streams
        (bounds-checked; open-ended tails close at ``streams``)."""
        S = int(streams)
        out = []
        for lo, hi in self._ranges:
            hi = S if hi is None else hi
            if hi > S or lo >= S:
                raise ValueError(
                    f"cohort range [{lo}, {hi}) exceeds fleet size {S}")
            out.append((lo, hi))
        if not out:
            raise ValueError("empty cohort")
        return tuple(out)

    def indices(self, streams: Optional[int] = None) -> Tuple[int, ...]:
        if streams is None and any(hi is None for _, hi in self._ranges):
            raise TypeError(
                "indices() of an unresolved ALL/open-ended cohort — pass "
                "the fleet size: cohort.indices(S)")
        ranges = self.resolve(streams) if streams is not None \
            else self._ranges
        return tuple(i for lo, hi in ranges for i in range(lo, hi))

    def __contains__(self, i: int) -> bool:
        return any(lo <= int(i) and (hi is None or int(i) < hi)
                   for lo, hi in self._ranges)

    def __len__(self) -> int:
        if any(hi is None for _, hi in self._ranges):
            raise TypeError("len() of an unresolved ALL-cohort; use "
                            "len(cohort.indices(S)) or resolve(S) first")
        return sum(hi - lo for lo, hi in self._ranges)

    def __bool__(self) -> bool:
        return bool(self._ranges)

    def __eq__(self, other) -> bool:
        return isinstance(other, Cohort) and self._ranges == other._ranges

    def __hash__(self) -> int:
        return hash(self._ranges)

    def __repr__(self) -> str:
        if self.is_all:
            return "Cohort.ALL"
        parts = ", ".join(f"[{lo}, {'S' if hi is None else hi})"
                          for lo, hi in self._ranges)
        return f"Cohort({parts})"


#: The whole-fleet cohort: ``query_cohort(fleet, state, ALL, t)`` is the
#: (cached) global aggregate — what ``merge_streams`` used to recompute
#: from scratch on every call.
ALL = Cohort([(0, None)])


def as_cohort(users) -> Cohort:
    """Coerce ``None`` / a Cohort / an int / an iterable of ints."""
    if users is None:
        return ALL
    if isinstance(users, Cohort):
        return users
    if isinstance(users, (int, np.integer)):
        return Cohort.of(int(users))
    return Cohort.of(users)


# ---------------------------------------------------------------------------
# AggTree — the cached merge tree
# ---------------------------------------------------------------------------


def _scoped_merge(base, a, b, t):
    """The node merge, its ops under the ``fleet.query`` named scope."""
    with jax.named_scope("fleet.query"):
        return base.merge(a, b, t)


class AggTree:
    """Segment tree of partial merges over a fleet's stream axis.

    ``base`` is the per-stream sketch (a JAX-backed ``SlidingSketch``);
    ``streams`` the fleet size S.  Node ``[lo, hi)`` holds the merged
    base-variant state of those streams at some query time; leaves are
    sliced out of the *current* fleet state on demand and are never
    cached (a slice is free, a merge is not).

    All node merges go through ONE jitted pairwise ``merge`` — every
    base-variant state has the same fixed shapes, so the whole tree
    (any node, any level) reuses a single compilation.
    """

    def __init__(self, base, streams: int):
        if base.meta.get("backend") != "jax":
            raise ValueError(
                f"AggTree needs a JAX-backed base sketch, got {base.name!r} "
                f"(backend={base.meta.get('backend')!r})")
        self.base = base
        self.S = int(streams)
        if self.S < 1:
            raise ValueError(f"fleet size {streams} < 1")
        self._jmerge = jax.jit(lambda a, b, t: _scoped_merge(base, a, b, t))
        # (lo, hi) -> (t_tag, merged base state)
        self._nodes: Dict[Tuple[int, int], Tuple[Optional[int], Any]] = {}
        # (resolved ranges, t_tag) -> composed result state
        self._results: Dict[Tuple, Any] = {}
        self._np_state = None                  # lazy host view of the state
        self._leaf_ids: Optional[Tuple[int, ...]] = None
        self._state_ref = None                 # keeps leaf ids un-recycled
        self._last_tkey = None                 # most recent query time tag
        self.merges = 0                        # cumulative node merges
        self.resets = 0                        # wholesale invalidations
        # cumulative nodes garbage-collected by advance()/dirty() — the
        # conservation counterpart of the history plane's retired_units
        # (tests pin evicted == retired on a shared clock sequence)
        self.evicted_nodes = 0

    # -- cache lifecycle ----------------------------------------------------

    def _ids(self, state) -> Tuple[int, ...]:
        return tuple(map(id, jax.tree.leaves(state)))

    def _adopt(self, state) -> None:
        # NO device→host copy here: adopting is called from the ingest hot
        # path (engine step advance) and must not block on async compute —
        # the host view is materialized lazily on first leaf access
        self._np_state = None
        self._leaf_ids = self._ids(state)
        self._state_ref = state

    def _host_state(self):
        if self._np_state is None:
            self._np_state = jax.tree.map(np.asarray, self._state_ref)
        return self._np_state

    def _sync(self, state) -> None:
        """Safety net: an unannounced state change invalidates everything
        (sound by construction — the tree can't know which streams moved)."""
        if self._leaf_ids != self._ids(state):
            if self._leaf_ids is not None:
                self.resets += 1
            self._nodes.clear()
            self._results.clear()
            self._adopt(state)

    def advance(self, state, touched: Optional[Iterable[int]] = None) -> None:
        """Announce a fleet-state transition from ingest.

        ``touched`` — the streams whose rows changed; only their
        root-to-leaf paths are dirtied (``None`` means "unknown": dirty
        everything).  Callers must query at the post-ingest clock (time
        only moves forward): node reuse is additionally guarded by the
        per-node time tag, so clock-driven expiry can never serve stale.

        Nodes whose time tag was already superseded before this ingest
        (tag ≠ the most recent query's) are garbage-collected here — under
        the forward-clock contract they can never be served again, and
        keeping them would only inflate ``space()`` and checkpoints.
        """
        self._results.clear()
        if touched is None:
            self.evicted_nodes += len(self._nodes)
            self._nodes.clear()
        else:
            self.dirty(touched)
            stale = [k for k, v in self._nodes.items()
                     if v[0] != self._last_tkey]
            for k in stale:
                del self._nodes[k]
            self.evicted_nodes += len(stale)
        self._adopt(state)

    def dirty(self, streams: Iterable[int]) -> int:
        """Evict every cached node whose range contains a touched stream
        (the root-to-leaf paths).  Returns the number of evicted nodes."""
        import bisect

        touched = sorted({int(s) for s in streams})
        if not touched:
            return 0
        # a node [lo, hi) is stale iff some touched index falls inside it
        evict = [k for k in self._nodes
                 if bisect.bisect_left(touched, k[0])
                 < bisect.bisect_left(touched, k[1])]
        for k in evict:
            del self._nodes[k]
        self._results.clear()
        self.evicted_nodes += len(evict)
        return len(evict)

    def reset(self) -> None:
        self._nodes.clear()
        self._results.clear()
        self.resets += 1

    # -- queries ------------------------------------------------------------

    def query(self, state, cohort=ALL, t=None):
        """Merged base-variant state over ``cohort`` at query time ``t``.

        Bit-identical to a from-scratch midpoint-split merge fold over
        the cohort's streams at the same ``t``; warm queries reuse every
        cached canonical node and cost only the O(log S) composition.
        """
        self._sync(state)
        cohort = as_cohort(cohort)
        ranges = cohort.resolve(self.S)
        tkey = None if t is None else int(t)
        self._last_tkey = tkey
        rkey = (ranges, tkey)
        hit = self._results.get(rkey)
        if hit is not None:
            return hit
        segs: List[Tuple[int, int]] = []
        for lo, hi in ranges:
            self._decompose(0, self.S, lo, hi, segs)
        acc = None
        for lo, hi in segs:
            node = self._node(lo, hi, t, tkey)
            acc = node if acc is None else self._merge2(acc, node, t)
        if len(self._results) >= 4096:         # bounded result memo
            self._results.clear()
        self._results[rkey] = acc
        return acc

    def build(self, state, t=None):
        """Warm-up: materialize every internal node (S−1 merges when cold).
        Equivalent to ``query(state, ALL, t)`` — returns the root state."""
        return self.query(state, ALL, t)

    def _decompose(self, lo: int, hi: int, qlo: int, qhi: int,
                   out: List[Tuple[int, int]]) -> None:
        """Canonical segment-tree cover of ``[qlo, qhi)`` within node
        ``[lo, hi)`` — at most ``2⌈log₂S⌉`` nodes, in stream order."""
        canonical_cover(lo, hi, qlo, qhi, out)

    def node(self, state, lo: int, hi: int, t=None):
        """Merged base-variant state of the single range ``[lo, hi)`` at
        query time ``t`` — the midpoint-split fold, cached like any other
        node.  The partitioned query plane uses this to materialize the
        canonical subtree nodes it owns (which it publishes cross-process
        as compressed ``2ℓ×d`` states) without re-deriving the fold."""
        lo, hi = int(lo), int(hi)
        if not (0 <= lo < hi <= self.S):
            raise ValueError(f"node range [{lo}, {hi}) outside fleet "
                             f"[0, {self.S})")
        self._sync(state)
        tkey = None if t is None else int(t)
        self._last_tkey = tkey
        return self._node(lo, hi, t, tkey)

    def _node(self, lo: int, hi: int, t, tkey):
        if hi - lo == 1:                       # leaf: a free slice, not cached
            return jax.tree.map(lambda x: x[lo], self._host_state())
        ent = self._nodes.get((lo, hi))
        if ent is not None and ent[0] == tkey:
            return ent[1]
        mid = (lo + hi) // 2
        merged = self._merge2(self._node(lo, mid, t, tkey),
                              self._node(mid, hi, t, tkey), t)
        self._nodes[(lo, hi)] = (tkey, merged)
        return merged

    def _merge2(self, a, b, t):
        self.merges += 1
        targ = None if t is None else jnp.asarray(int(t), jnp.int32)
        return self._jmerge(a, b, targ)

    # -- accounting ---------------------------------------------------------

    @property
    def cached_nodes(self) -> int:
        return len(self._nodes)

    def space(self) -> int:
        """Live rows held by cached internal nodes (the fleet-space term
        the pre-query-plane ``space`` ignored)."""
        return int(sum(int(self.base.space(s))
                       for _, s in self._nodes.values()))

    # -- persistence (engine checkpoints) -----------------------------------

    AUX_PREFIX = "aggnode"

    def compile_merge(self, state, t=None) -> None:
        """Trace/compile the shared pairwise merge without touching the
        node cache or the ``merges`` counter — benchmark warmup, so a cold
        ``build`` measures S−1 merges rather than merges + XLA compile."""
        self._sync(state)
        leaf = jax.tree.map(lambda x: x[0], self._host_state())
        targ = None if t is None else jnp.asarray(int(t), jnp.int32)
        jax.block_until_ready(self._jmerge(leaf, leaf, targ))

    def state_dict(self, t=...):
        """``(meta, arrays)`` for checkpointing the materialized nodes.

        ``meta`` is JSON-serializable (node ranges + time tags + leaf
        count); ``arrays`` is a flat ``{name: np.ndarray}`` suitable for
        the shared ``train/checkpoint.py`` one-``.npy``-per-leaf layout
        (``save_fleet``'s ``aux``).

        ``t``: persist only nodes whose time tag equals it — engines pass
        their clock so checkpoints never carry superseded nodes (which a
        forward-moving clock could never serve again).  Default: keep all.
        """
        nodes = sorted(self._nodes)
        if t is not ...:
            tkey = None if t is None else int(t)
            nodes = [k for k in nodes if self._nodes[k][0] == tkey]
        meta = {"streams": self.S,
                "nodes": [[lo, hi, self._nodes[(lo, hi)][0]]
                          for lo, hi in nodes],
                "n_leaves": None}
        arrays: Dict[str, np.ndarray] = {}
        for lo, hi in nodes:
            leaves = jax.tree.leaves(self._nodes[(lo, hi)][1])
            meta["n_leaves"] = len(leaves)
            for j, leaf in enumerate(leaves):
                arrays[f"{self.AUX_PREFIX}_{lo:06d}_{hi:06d}_{j:03d}"] = \
                    np.asarray(jax.device_get(leaf))
        return meta, arrays

    def load_state_dict(self, meta, arrays, state) -> bool:
        """Install checkpointed nodes against the restored fleet ``state``.

        Returns True on success.  Any mismatch — wrong fleet size, leaf
        count, missing arrays, or shape/dtype drift vs the base variant's
        state template — falls back to an empty cache (rebuild lazily on
        the next query) instead of failing the restore: the cache is an
        accelerator, never a correctness dependency.
        """
        self._nodes.clear()
        self._results.clear()
        self._adopt(state)
        if not meta:
            return False
        template = jax.eval_shape(lambda: self.base.init())
        t_leaves, treedef = jax.tree_util.tree_flatten(template)
        try:
            if int(meta["streams"]) != self.S \
                    or int(meta["n_leaves"]) != len(t_leaves):
                raise ValueError("fleet/template mismatch")
            for lo, hi, ttag in meta["nodes"]:
                lo, hi = int(lo), int(hi)
                if not (0 <= lo < hi <= self.S):
                    raise ValueError(f"node [{lo}, {hi}) out of range")
                leaves = []
                for j, tl in enumerate(t_leaves):
                    arr = arrays[
                        f"{self.AUX_PREFIX}_{lo:06d}_{hi:06d}_{j:03d}"]
                    if tuple(arr.shape) != tuple(tl.shape) \
                            or arr.dtype != tl.dtype:
                        raise ValueError(
                            f"leaf {j} of node [{lo}, {hi}): "
                            f"{arr.shape}/{arr.dtype} != "
                            f"{tl.shape}/{tl.dtype}")
                    leaves.append(jnp.asarray(arr))
                self._nodes[(lo, hi)] = (
                    None if ttag is None else int(ttag),
                    jax.tree_util.tree_unflatten(treedef, leaves))
        except (KeyError, TypeError, ValueError):
            self._nodes.clear()                # rebuild-on-mismatch fallback
            return False
        return True


# ---------------------------------------------------------------------------
# Uncached full reduction — the from-scratch baseline
# ---------------------------------------------------------------------------


def full_reduce_streams(fleet, state, t=None):
    """Tree-reduce a whole fleet to ONE global-window sketch, from scratch.

    This is the pre-query-plane ``merge_streams`` implementation —
    ⌈log₂S⌉ rounds of vmapped pairwise merges, an odd tail carried
    pad-free at every round, no caching.  Kept as the reference the
    cached tree is tested against and as an O(S) merge path that
    allocates no cache.
    Answers differ from ``query_cohort(ALL)`` only in merge association
    order (both obey the additive FD bound).
    """
    base = fleet.meta.get("base")
    if base is None:
        raise ValueError(
            f"full_reduce_streams needs a fleet from vmap_streams/"
            f"shard_streams, got {fleet.name!r}")
    n = int(fleet.meta["streams"])
    vmerge = jax.vmap(lambda a, b: base.merge(a, b, t))
    while n > 1:
        half = n // 2
        a = jax.tree.map(lambda x: x[:half], state)
        b = jax.tree.map(lambda x: x[half:2 * half], state)
        merged = vmerge(a, b)
        if n % 2:                   # odd stream count: carry the last one
            tail = jax.tree.map(lambda x: x[2 * half:n], state)
            state = jax.tree.map(
                lambda m, z: jnp.concatenate([m, z], axis=0), merged, tail)
            n = half + 1
        else:
            state, n = merged, half
    return jax.tree.map(lambda x: x[0], state)
