"""Batched serving engines.

Two serving paths live here:

* ``ServeEngine`` — fixed-slot continuous batching over the jit'd
  prefill/decode steps.  B slots run in lockstep (one decode_step per tick
  advances every active slot); finished or empty slots are refilled by
  prefilling the next queued request and splicing its caches into the batch
  at the slot index.  This is the vLLM-style "continuous batching lite"
  that a fixed-shape jit world supports: no recompilation at runtime —
  prefill is compiled per bucketed prompt length, decode once.

* ``SketchFleetEngine`` — the fleet-backed sketch serving path: S per-user
  sliding-window sketches advanced as ONE SPMD program
  (``shard_streams``), with per-user queries and cross-shard ``merge``
  aggregation for global-window queries.  Rows are admitted through the
  ingest subsystem (``repro.serve.ingest``): a bounded, validating
  admission queue feeding a double-buffered slab pipeline that packs and
  prefetches slab k+1 while the device consumes slab k.
"""

from __future__ import annotations

import dataclasses
import time
import warnings
from collections import deque
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import api
from repro.serve import trace
from repro.serve.serve_step import build_decode_step, build_prefill_step


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray                 # (len,) int32
    max_new: int = 16
    eos_id: Optional[int] = None
    # filled by the engine:
    out_tokens: Optional[List[int]] = None
    latency_s: float = 0.0
    t_submit: float = 0.0


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    slots: int = 4                     # decode batch width
    s_max: int = 256                   # cache capacity
    prefill_buckets: tuple = (32, 64, 128)
    temperature: float = 0.0


class ServeEngine:
    """Single-host engine over jit'd steps (the multi-pod serve path jits
    the same fns with mesh shardings — see launch/serve.py)."""

    def __init__(self, cfg: ModelConfig, params, ecfg: EngineConfig,
                 dtype=jnp.float32):
        self.cfg = cfg
        self.ecfg = ecfg
        self.params = params
        self.dtype = dtype
        self.queue: deque = deque()
        self.done: Dict[int, Request] = {}
        self.slot_req: List[Optional[Request]] = [None] * ecfg.slots
        self.slot_left: np.ndarray = np.zeros(ecfg.slots, np.int32)
        self.tokens = jnp.zeros((ecfg.slots, 1), jnp.int32)
        self.caches = api.init_cache(cfg, ecfg.slots, ecfg.s_max, dtype)
        self._decode = jax.jit(build_decode_step(
            cfg, temperature=ecfg.temperature), donate_argnums=(2,))
        self._prefill_b1 = jax.jit(build_prefill_step(cfg))
        self.ticks = 0

    # -- admission ---------------------------------------------------------

    def submit(self, req: Request) -> None:
        b_max = max(self.ecfg.prefill_buckets)
        if len(req.prompt) > b_max:
            raise ValueError(
                f"prompt of {len(req.prompt)} tokens exceeds the largest "
                f"prefill bucket ({b_max}); admitting it would silently "
                f"drop all but the last {b_max} tokens — chunk the prompt "
                "or enlarge EngineConfig.prefill_buckets")
        req.t_submit = time.perf_counter()   # latency base: monotonic
        req.out_tokens = []
        self.queue.append(req)

    def _bucket(self, n: int) -> int:
        for b in self.ecfg.prefill_buckets:
            if n <= b:
                return b
        # unreachable through submit(), which rejects over-long prompts
        raise ValueError(
            f"no prefill bucket holds {n} tokens "
            f"(buckets={self.ecfg.prefill_buckets})")

    def _admit(self, slot: int, req: Request) -> None:
        b = self._bucket(len(req.prompt))
        prompt = np.zeros((1, b), np.int32)
        prompt[0, -len(req.prompt):] = req.prompt
        tok, caches1 = self._prefill_b1(self.params,
                                        {"tokens": jnp.asarray(prompt)})
        # splice the single-request caches into slot `slot`
        self.caches = _splice_caches(self.cfg, self.caches, caches1, slot,
                                     self.ecfg.s_max)
        self.tokens = self.tokens.at[slot].set(tok[0])
        self.slot_req[slot] = req
        self.slot_left[slot] = req.max_new
        req.out_tokens.append(int(tok[0, 0]))

    # -- main loop ----------------------------------------------------------

    def step(self) -> None:
        """One engine tick: refill slots, one decode step, harvest."""
        for s in range(self.ecfg.slots):
            if self.slot_req[s] is None and self.queue:
                self._admit(s, self.queue.popleft())
        if all(r is None for r in self.slot_req):
            return
        self.tokens, self.caches = self._decode(self.params, self.tokens,
                                                self.caches)
        self.ticks += 1
        toks = np.asarray(self.tokens[:, 0])
        for s, req in enumerate(self.slot_req):
            if req is None:
                continue
            req.out_tokens.append(int(toks[s]))
            self.slot_left[s] -= 1
            hit_eos = req.eos_id is not None and toks[s] == req.eos_id
            if self.slot_left[s] <= 0 or hit_eos:
                req.latency_s = time.perf_counter() - req.t_submit
                self.done[req.uid] = req
                self.slot_req[s] = None

    def run(self, max_ticks: int = 10_000) -> Dict[int, Request]:
        # budget THIS call, not the engine's lifetime: self.ticks is
        # cumulative, so comparing it to max_ticks would make run() a
        # permanent no-op once a long-lived engine crosses the budget
        t0 = self.ticks
        while (self.queue or any(r is not None for r in self.slot_req)) \
                and self.ticks - t0 < max_ticks:
            self.step()
        left = len(self.queue) + sum(r is not None for r in self.slot_req)
        if left:
            warnings.warn(
                f"ServeEngine.run() exhausted max_ticks={max_ticks} with "
                f"{left} request(s) unfinished — `done` is incomplete",
                RuntimeWarning, stacklevel=2)
        return self.done


class SketchFleetEngine:
    """Fleet-backed sketch serving: S per-user sketches, one SPMD program.

    Ingestion is tick-batched to keep shapes static: ``submit(user, row)``
    admits rows through a validating, optionally capacity-bounded
    ``AdmissionQueue`` (``repro.serve.ingest``) — it returns ``True``
    (accepted) or ``False`` (deferred: queue at ``queue_capacity``);
    malformed input raises at admission.  ``submit_many(users, rows)`` is
    the batched fast path: one vectorized validation and ONE copy into
    the queue's row pool for a whole ``(n,) users / (n, d) rows`` batch
    (per-user FIFO order = batch order), returning an ``(n,)`` bool
    acceptance mask with prefix-accept semantics at capacity::

        users = np.repeat(np.arange(S), 4)          # 4 rows per user
        rows  = batch.reshape(-1, d)
        accepted = eng.submit_many(users, rows)     # one call, no loop
        eng.run()

    Each ``step()`` takes a fixed
    ``(S, block, d)`` slab from the ingest pipeline — users with nothing
    queued contribute zero rows, which the DS-FD family treats as idle
    ticks (expiry/swap advance, nothing is absorbed) — and advances every
    stream with one sharded ``update_block``.  With the default
    ``ingest="async"`` pipeline the slab for tick k+1 is packed into a
    spare host buffer and prefetched onto the fleet mesh *while the
    device consumes tick k's slab* (double buffering); ``ingest="sync"``
    keeps the legacy assemble-at-dispatch path.  Both are bit-identical
    for the same submit/step interleaving (the tick/clock contract in
    ``repro.serve.ingest``).

    The fleet runs one shared clock, so an idle user's window ages out in
    engine ticks, exactly the time-based semantics of §5 — but a tick in
    which NO user has pending rows is clock-neutral by default (a no-op:
    polling ``step()`` on an idle engine no longer silently expires live
    window content).  Wall-clock-driven time-based deployments that want
    idle ticks to age windows out opt in with ``step(advance_time=True)``.

    Ownership routing (multi-host fleets): pass ``topology`` (a
    :class:`repro.parallel.topology.FleetTopology`) and this engine holds
    only the contiguous stream range the topology assigns to this
    process.  ``submit``/``submit_many``/``query_user`` still speak
    GLOBAL user ids: owned ids are mapped onto the local shard, a
    non-owned id raises :class:`~repro.parallel.topology.OwnershipError`
    naming the owning process and its range (``submit_many`` admits
    nothing on a mixed batch) — the front-end routes the request to that
    process instead.  ``query_cohort``/``query_global`` are collectives:
    every process must issue the same query sequence between the same
    ticks (owned subtrees answer locally; only O(log S) compressed spine
    nodes cross processes — see ``repro.parallel.topology``).
    ``checkpoint`` writes this process's shard manifest; restoring with
    a different process count is supported (``from_checkpoint(...,
    topology=...)`` slices its range from whatever shards it finds).

    Queries (the query plane, ``repro.sketch.query``):
      * ``query_user(u)``    — that user's compressed (2ℓ, d) window sketch.
      * ``query_cohort(c)``  — ONE compressed sketch over any cohort of
        users (a ``Cohort``, an iterable of user ids, or ``None`` for the
        whole fleet), served from the engine's cached ``AggTree`` of
        partial merges: a warm cohort query costs O(log S) node merges,
        and ``step()`` dirties only the root-to-leaf paths of the streams
        it actually ingested rows for, so repeated aggregate queries
        between ticks are near-free.
      * ``query_global()``   — ``query_cohort(None)``: the whole-fleet
        aggregate (the old ``merge_streams`` re-reduction, now cached).
      * ``query_interval(users, t1, t2)`` — time travel over RETIRED
        history (``history=True``): any fully expired interval
        ``[t1, t2)``, answered in O(log(t2−t1)) merges from the
        persistent plane's tiered hot/cold dyadic index and carried
        through checkpoints (``repro.sketch.history``).
      * ``score_rows(rows, user)`` / ``score_cohort(rows, users)`` —
        residual anomaly scores of probe rows against one user's (or a
        cohort's merged) current window basis, via the fleet's ``score``
        capability.

    Anomaly flagging (``score=True``): every ingested slab is residual-
    scored against the pre-update window basis inside the ingesting tick
    (one extra jitted program on the same device state — no second
    transfer), and a per-user EWMA threshold
    (``repro.sketch.score.ScorePlane``; ``score_ema`` / ``score_zscore``
    / ``score_warmup``) flags users whose per-tick peak score spikes.
    Harvest with ``eng.anomalies()`` (``collective=True`` under a
    topology allgathers flagged GLOBAL ids on every process).  The
    plane's accumulators ride engine checkpoints and restore
    bit-identically, elastically across process counts.
    """

    def __init__(self, name: str = "dsfd", *, d: int, streams: int,
                 eps: float = 1 / 8, window: int = 1024, block: int = 8,
                 mesh=None, ingest: str = "async",
                 queue_capacity: Optional[int] = None, topology=None,
                 history: bool = False,
                 history_hot_nodes: Optional[int] = None,
                 history_dir: Optional[str] = None,
                 score: bool = False, score_ema: float = 0.05,
                 score_zscore: float = 4.0, score_warmup: int = 5,
                 **hyper):
        from repro.sketch.api import agg_tree, make_sketch, shard_streams

        self.base = make_sketch(name, d=d, eps=eps, window=window, **hyper)
        self.topology = topology
        self.fleet = shard_streams(self.base, streams, mesh,
                                   topology=topology)
        self.S, self.d, self.block = int(streams), int(d), int(block)
        self.window = int(window)
        self.S_local = (int(topology.local_size) if topology is not None
                        else self.S)
        self.state = self.fleet.init()
        self.t = 0                                  # fleet clock (ticks)
        self.rows_ingested = 0
        self._wire_ingest(ingest, queue_capacity)
        self._wire_trace()
        # the cohort-query cache, shared with the fleet's query_cohort path
        self.tree = agg_tree(self.fleet)
        # the persistent sketch plane: with history=True, window expiry
        # RETIRES content into a time-dyadic index (hot LRU of
        # `history_hot_nodes` nodes, cold spill under `history_dir`
        # through train/checkpoint.py) instead of discarding it —
        # query_interval(cohort, t1, t2) then answers any historical
        # interval.  Each tick pays one host copy of the slab for the
        # retirement path (opt-in; see benchmarks/fleet_throughput.py).
        self.history = None
        if history:
            from repro.sketch.history import (HistoryPlane,
                                              install_query_interval)

            ell = self.base.meta.get("ell")
            if ell is None:
                raise ValueError(
                    f"history=True needs a sketch variant exposing its FD "
                    f"width as meta['ell'] (a (2ℓ, d) buffer) — "
                    f"{name!r} does not")
            self.history = HistoryPlane(
                streams=self.S, d=self.d, ell=int(ell),
                window=self.window,
                hot_capacity=history_hot_nodes, spill_dir=history_dir,
                topology=topology)
            self.fleet = install_query_interval(self.fleet, self.history)
        # the scoring plane: with score=True every ingested slab is
        # residual-scored against the PRE-update window basis inside the
        # ingesting tick (one extra jitted program, no second transfer),
        # and per-user EWMA thresholds flag anomalous users online —
        # harvest them with eng.anomalies()
        self._wire_score(score, ema=score_ema, zscore=score_zscore,
                         warmup=score_warmup)

    def _wire_score(self, on: bool, *, ema: float, zscore: float,
                    warmup: int) -> None:
        """Build (or skip) the per-user EWMA scoring plane — also the
        restore path, so the plane always wraps S_local streams."""
        from repro.sketch import capability
        from repro.sketch.score import ScorePlane

        self.score_plane = None
        if not on:
            return
        if not capability.has(self.fleet, "score"):
            self.fleet.score()  # the capability raiser: names the fix
        self.score_plane = ScorePlane(self.S_local, ema=ema,
                                      zscore=zscore, warmup=warmup)

    def _wire_ingest(self, mode: str,
                     capacity: Optional[int]) -> None:
        """Build the admission queue + slab pipeline for this fleet
        (also the restore path: ``from_checkpoint`` rewires the same
        way, so pending rows always live in one structure)."""
        from repro.serve.ingest import AdmissionQueue, make_pipeline

        sharding = self.fleet.meta["slab_sharding"]
        put = lambda slab: jax.device_put(slab, sharding)    # noqa: E731
        self.ingest = mode
        self.queue = AdmissionQueue(self.S_local, self.d, capacity=capacity)
        self.pipe = make_pipeline(mode, self.queue, block=self.block,
                                  put=put)
        self._zero_slab = None         # lazy zero slab for idle ticks

    def _wire_trace(self) -> None:
        """The tick log and the counters' baseline (also the restore
        path; see ``ticks`` and ``counters``)."""
        self.log = trace.TickLog(self.S_local * self.block)
        self._census = None            # None: the fleet as initialized
        lower = self.fleet.update_block.lower
        avals = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=x.sharding), self.state)
        slab = jax.ShapeDtypeStruct(
            (self.S_local, self.block, self.d), jnp.float32,
            sharding=self.fleet.meta["slab_sharding"])
        ts = jax.ShapeDtypeStruct((self.block,), jnp.int32)
        lower_update = lambda: lower(avals, slab, ts)        # noqa: E731
        self._lower_update = lower_update
        # the log may outlive the engine: hold no reference to it
        self.log.update_hlo = lambda: lower_update().compile().as_text()

    @property
    def _pending(self) -> List[deque]:
        """Back-compat snapshot of every admitted-but-not-ingested row
        per user — rows staged in the async pipeline come first (they
        dispatch next), then the queued rows behind them.  Read-only:
        mutate through ``submit``/``step``, not this."""
        qs = [deque(q) for q in self.queue.queues]
        for u, rows in self.pipe.staged_snapshot():
            qs[u].extendleft(reversed(rows))
        return qs

    # -- persistence --------------------------------------------------------

    def checkpoint(self, path: str, *, keep: int = 3) -> str:
        """Atomic engine checkpoint: the sharded fleet state, the fleet
        clock, and every not-yet-ingested pending row.

        The window is defined by the clock, so the clock is part of the
        state: a restore that did not realign ``t`` would silently expire
        (or resurrect) every user's window.  Rows staged by the async
        pipeline are first unwound back to the queue front
        (``flush_to_queue``), then the queue is packed into two flat
        arrays (FIFO order per user is preserved because users are
        walked in order) — the one-``.npy``-per-leaf checkpoint format
        is pipeline-agnostic and identical to the pre-ingest-subsystem
        layout.  The ``AggTree``'s materialized nodes ride in
        the same atomic checkpoint (node arrays as extra aux leaves, node
        ranges + time tags in the JSON spec), so a restored engine's first
        aggregate queries hit a warm cache; a node-layout mismatch at
        restore time falls back to rebuilding the cache lazily.
        """
        from repro.sketch.api import save_fleet

        self.pipe.flush_to_queue()
        users, rows = self.queue.snapshot()
        if self.topology is not None:
            # pending ids are persisted GLOBAL: the restoring process
            # count (and hence the local index mapping) is not ours to
            # assume — from_checkpoint filters by its own ownership
            users = (users + np.int32(self.topology.lo)).astype(np.int32)
        aux = {"pending_user": users, "pending_rows": rows}
        if self.topology is None:
            tree_meta, tree_arrays = self.tree.state_dict(t=self.t)
            aux.update(tree_arrays)
        else:
            # the partitioned plane restarts cold: its node cache is
            # scoped by transport version (a restart resets every
            # process's version in lockstep) and rebuilds in O(local)
            tree_meta = None
        # the history plane rides in the same atomic checkpoint: hot node
        # snapshots + pending raw units as aux leaves, the index metadata
        # (node keys, emptiness, cold set, spill dir path) in the JSON
        # spec — the spill dir itself stays on disk and IS part of the
        # persisted state (cold nodes are faulted from it after restore)
        hist_meta = None
        if self.history is not None:
            hist_meta, hist_arrays = self.history.state_dict()
            aux.update(hist_arrays)
        # the scoring plane's EWMA accumulators ride as aux leaves keyed
        # by this process's GLOBAL stream range — a restore with a
        # different process count reassembles its own slice from whatever
        # ranges the save-time shards wrote (cf. the pending-id story)
        score_meta = None
        if self.score_plane is not None:
            lo = 0 if self.topology is None else int(self.topology.lo)
            for k, v in self.score_plane.state_dict().items():
                aux[f"{k}_{lo:08d}_{lo + self.S_local:08d}"] = v
            score_meta = self.score_plane.spec()
        # rows_ingested rides in the JSON spec (arbitrary-precision int —
        # an array leaf would be silently downcast by x64-disabled jax)
        return save_fleet(path, self.fleet, self.state, self.t, aux=aux,
                          spec_extra={"engine": {
                              "block": self.block,
                              "rows_ingested": int(self.rows_ingested),
                              "ingest": self.ingest,
                              "queue_capacity": self.queue.capacity,
                              "agg_tree": tree_meta,
                              "history": hist_meta,
                              "score": score_meta}},
                          keep=keep)

    @classmethod
    def from_checkpoint(cls, path: str, mesh=None, *,
                        step: Optional[int] = None,
                        topology=None) -> "SketchFleetEngine":
        """Rebuild an engine from :meth:`checkpoint` — elastically.

        The sketch comes back from the registry via the checkpoint's
        ``sketch_spec``; the fleet state is laid out on ``mesh`` (default:
        all local devices — the restore-time device count may differ from
        the save-time one as long as it divides the fleet size).  Clock,
        ingested-row counter, and pending per-user queues are realigned so
        subsequent ``step``/``query_user``/``query_cohort``/
        ``query_global`` calls are numerically identical to an
        uninterrupted run.  Materialized ``AggTree`` nodes saved by
        :meth:`checkpoint` are re-installed so the first aggregate
        queries after a restore are warm; any mismatch (older checkpoint
        format, config drift) silently falls back to a cold cache — the
        cache is an accelerator, never a correctness dependency.

        Process elasticity: pass ``topology`` to restore one process's
        shard of a multi-host engine — the save-time process count is
        irrelevant (a plain checkpoint is sliced, shard checkpoints are
        sliced-and-concatenated; see :func:`restore_fleet`).  Pending
        rows are persisted with GLOBAL user ids, so each restoring
        process keeps exactly the ones it now owns — nothing is lost or
        duplicated across the fleet.  ``rows_ingested`` counts the whole
        fleet's rows as of the save regardless of who saved.
        """
        from repro.sketch.api import agg_tree, restore_fleet

        fc = restore_fleet(path, mesh, step=step, topology=topology)
        ss = fc.manifest["sketch_spec"]
        espec = ss.get("engine")
        if espec is None:
            raise ValueError(
                f"checkpoint under {path!r} is a bare fleet (no engine "
                "section) — restore it with repro.sketch.api.restore_fleet")
        spec = ss["sketch"]
        # assemble around the restored fleet/state directly — running
        # __init__ would rebuild the fleet and materialize a full
        # throwaway init() state on devices at exactly the restore moment
        eng = cls.__new__(cls)
        eng.base = fc.fleet.meta["base"]
        eng.fleet = fc.fleet
        eng.topology = topology
        eng.S = int(ss["streams"])
        eng.S_local = (int(topology.local_size) if topology is not None
                       else eng.S)
        eng.d = int(spec["d"])
        eng.block = int(espec["block"])
        eng.state = fc.state
        eng.t = int(fc.t)
        eng.rows_ingested = int(espec.get("rows_ingested", 0))
        # pre-ingest-subsystem checkpoints carry no ingest section:
        # default to the async pipeline, unbounded queue (bit-identical
        # either way — the pipeline is not part of the persisted state)
        eng._wire_ingest(espec.get("ingest", "async"),
                         espec.get("queue_capacity"))
        eng._wire_trace()
        if trace.has_census(eng.state):
            eng._census = trace.census(eng.state)
        users, rows = fc.aux["pending_user"], fc.aux["pending_rows"]
        if topology is not None:
            # shard checkpoints carry GLOBAL pending ids (possibly from a
            # different process count): keep the ones this process now
            # owns; sibling processes pick up the rest
            users = np.asarray(users, np.int32).reshape(-1)
            owned = (users >= topology.lo) & (users < topology.hi)
            users = users[owned] - np.int32(topology.lo)
            rows = np.asarray(rows)[owned]
        eng.queue.load(users, rows)
        eng.tree = agg_tree(eng.fleet)
        if topology is None:
            eng.tree.load_state_dict(espec.get("agg_tree"), fc.aux,
                                     eng.state)
        eng.window = int(spec["window"])
        eng.history = None
        hmeta = espec.get("history")
        if hmeta is not None:
            from repro.sketch.history import (HistoryPlane,
                                              install_query_interval)

            # same-partition restore only (from_state_dict raises on a
            # mismatch): retired snapshots are per-owned-stream arrays,
            # and silently resharding history would answer intervals
            # from the wrong streams
            eng.history = HistoryPlane.from_state_dict(hmeta, fc.aux,
                                                       topology=topology)
            eng.fleet = install_query_interval(eng.fleet, eng.history)
        smeta = espec.get("score")
        eng._wire_score(smeta is not None, **(smeta or
                                             dict(ema=0.0, zscore=0.0,
                                                  warmup=0)))
        if smeta is not None:
            lo = 0 if topology is None else int(topology.lo)
            arrays = _score_aux_slice(fc.aux, lo, lo + eng.S_local)
            if arrays is not None:
                eng.score_plane.load_state_dict(arrays)
        return eng

    # -- admission ---------------------------------------------------------

    def _route(self, user) -> int:
        """Ownership routing: map a GLOBAL user id onto this process's
        local shard (the identity for single-host engines).  Non-owned
        ids raise ``OwnershipError`` naming the owner — the caller
        should route the request to that process."""
        if isinstance(user, bool) or not isinstance(user, (int, np.integer)):
            raise ValueError(
                f"user id must be an integer, got {type(user).__name__} "
                f"({user!r})")
        u = int(user)
        if not 0 <= u < self.S:
            raise ValueError(
                f"user id {u} outside the fleet's [0, {self.S}) stream "
                "range")
        return u if self.topology is None else self.topology.to_local(u)

    def submit(self, user: int, row: np.ndarray) -> bool:
        """Admit one row for ``user`` (a GLOBAL id); validated at
        admission (clear ``ValueError`` instead of a late XLA shape
        error; ``OwnershipError`` when a topology routes ``user`` to a
        different process).  Returns ``True`` (accepted) or ``False``
        (deferred — the queue is at ``queue_capacity``; drain with
        ``step``/``run`` and resubmit)."""
        if self.topology is not None:
            user = self._route(user)
        return self.queue.submit(user, row)

    def submit_many(self, users, rows) -> np.ndarray:
        """Batched admission: ``users`` (n,) int GLOBAL ids, ``rows``
        (n, d) float32 — one vectorized validation + one copy into the
        queue's row pool, no per-row Python (see the class docstring).
        Returns an (n,) bool mask of accepted rows; at
        ``queue_capacity`` the longest fitting prefix is admitted
        (resubmit the ``~mask`` suffix after a drain).  Malformed input
        raises ``ValueError`` with nothing admitted; under a topology a
        batch containing any non-owned id raises ``OwnershipError``
        with nothing admitted (split batches by owner upstream)."""
        if self.topology is not None:
            ua = np.asarray(users)
            if ua.ndim != 1 or (ua.size
                                and not np.issubdtype(ua.dtype, np.integer)):
                raise ValueError(
                    f"users must be a 1-D integer array, got shape "
                    f"{ua.shape} dtype {ua.dtype}")
            if ua.size:
                bad = (ua < 0) | (ua >= self.S)
                if bad.any():
                    raise ValueError(
                        f"user id {int(ua[bad][0])} outside the fleet's "
                        f"[0, {self.S}) stream range")
                owned = (ua >= self.topology.lo) & (ua < self.topology.hi)
                if not owned.all():
                    self.topology.to_local(int(ua[~owned][0]))  # raises
            users = (ua - self.topology.lo).astype(ua.dtype, copy=False)
        return self.queue.submit_many(users, rows)

    @property
    def backlog(self) -> int:
        """Admitted-but-not-ingested rows: queued + staged in the async
        pipeline's prefetched slab."""
        return self.queue.backlog + self.pipe.staged_rows

    # -- main loop ---------------------------------------------------------

    def step(self, *, advance_time: bool = False) -> int:
        """One engine tick: take the next ≤ ``block``-rows-per-user slab
        from the ingest pipeline, advance the whole fleet in one sharded
        program call, and dirty only the touched streams' root-to-leaf
        paths in the cohort-query cache (untouched subtrees stay
        materialized; clock-driven expiry is handled by the per-node
        time tags).  Returns the number of rows ingested this tick.

        A tick where NO user has pending rows is clock-neutral (a
        no-op) unless ``advance_time=True`` — polling an idle engine
        must not silently expire live window content; wall-clock-driven
        time-based windows opt in to idle aging explicitly.

        Every call, an idle poll too, is one record of :meth:`ticks`,
        its phases timed as the spans of ``repro.serve.trace``.
        """
        log = self.log
        with log.step(self.t):
            with log.span(trace.NEXT_SLAB):
                slab, touched, counts, nrows = self.pipe.next_slab()
            log.taken(nrows, len(touched))
            if nrows == 0 and not advance_time:
                return 0                   # idle: nothing is dispatched
            if nrows == 0:
                if self._zero_slab is None:
                    self._zero_slab = np.zeros(
                        (self.S_local, self.block, self.d), np.float32)
                slab = self._zero_slab
            dev_scores = None
            if self.score_plane is not None and nrows:
                # score the slab against the PRE-update window basis at
                # the current clock: "is this row explained by what the
                # window already holds?" — scoring post-update would let
                # a burst vouch for itself
                with log.span(trace.SCORE):
                    dev_scores = self.fleet.score(self.state, slab, self.t)
            prev = self.state
            with log.span(trace.DISPATCH):
                # host stamps: the fleet replicates them onto its own mesh
                ts = np.arange(self.t + 1, self.t + self.block + 1,
                               dtype=np.int32)
                self.state = self.fleet.update_block(prev, slab, ts)
            self.t += self.block
            self.rows_ingested += nrows
            with log.span(trace.TREE_ADVANCE):
                self.tree.advance(self.state, touched)
            if dev_scores is not None:
                with log.span(trace.SCORE_OBSERVE):
                    cnt = np.zeros((self.S_local,), np.int64)
                    cnt[touched] = counts
                    self.score_plane.observe(np.asarray(dev_scores), cnt)
            if self.history is not None:
                # the persistent plane is host-side: observe the slab's
                # raw units (one host copy — the opt-in cost of history),
                # then retire exactly the units this clock advance
                # expired.  Idle advance_time ticks land here too (their
                # zero slab retires as empty nodes); clock-neutral idle
                # polls returned above.
                with log.span(trace.HISTORY):
                    self.history.observe_block(
                        np.asarray(slab), first_ts=self.t - self.block + 1)
                    self.history.retire_through(self.t - self.window)
            # double buffering: pack + prefetch the NEXT slab while the
            # device consumes the one just dispatched (no-op for sync)
            with log.span(trace.PREFETCH):
                self.pipe.after_dispatch()
            # ...but no further ahead: wait for the previous tick, so at
            # most this one is in flight.  Unbounded, the host would queue
            # a slab and a fleet state of device memory for every tick it
            # ran ahead.
            with log.span(trace.WAIT):
                jax.block_until_ready(prev)
            return nrows

    def run(self, max_ticks: int = 10_000, *,
            on_budget: str = "raise") -> int:
        """Drain every pending row; returns engine ticks consumed.

        If ``max_ticks`` is exhausted with rows still pending the drain
        did NOT complete: raises :class:`IngestBacklogError` (carrying
        ``.remaining``) by default, or warns and returns the ticks spent
        with ``on_budget="warn"`` (check ``self.backlog``)."""
        from repro.serve.ingest import IngestBacklogError

        if on_budget not in ("raise", "warn"):
            raise ValueError(
                f"on_budget must be 'raise' or 'warn', got {on_budget!r}")
        ticks = 0
        while self.backlog and ticks < max_ticks:
            self.step()
            ticks += 1
        if self.backlog:
            msg = (f"run() exhausted max_ticks={max_ticks} with "
                   f"{self.backlog} row(s) still pending — the drain did "
                   "NOT complete")
            if on_budget == "raise":
                raise IngestBacklogError(msg, self.backlog)
            warnings.warn(msg, RuntimeWarning, stacklevel=2)
        return ticks

    # -- what the engine records about itself --------------------------------

    def ticks(self) -> np.ndarray:
        """The engine's record of its last ticks (up to 4,096), oldest
        first: a structured array of ``repro.serve.trace.RECORD``.

        One record per ``step()`` call, idle polls included: ``step``
        (the call's count) and ``clock`` (the engine clock before it),
        ``start``/``end`` of the whole call, ``<phase>_start`` and
        ``<phase>_end`` on ``time.perf_counter`` for each of
        ``repro.serve.trace.PHASES`` (NaN where the tick did not run the
        phase), ``rows`` taken, ``users`` touched and ``occupancy`` =
        rows ÷ (streams × block).  For example, the host's own time in a
        tick is ``end - start - (wait_end - wait_start)``; the time to
        enqueue its update is ``dispatch_end - start``.  Recording costs
        a few clock reads a tick; the same spans appear in a JAX
        profiler trace as ``engine.*`` annotations while one runs."""
        return self.log.records()

    def counters(self) -> Dict[str, Optional[int]]:
        """Device counters since the previous call (or since the engine
        was built or restored), from one small program over the fleet
        state; nothing is counted on the tick path.

        ``dumped``: snapshots dumped into the rings, main and aux summed
        over the fleet; ``left``: snapshots that left a ring by expiry or
        eviction; ``swaps``: main/aux swaps (see
        ``repro.serve.trace.census_diff`` for when a read must come at
        least once per swap period to be exact); these are ``None`` for a
        variant without snapshot rings.  ``peak_bytes_in_use``: the
        largest device's peak (``None`` where the runtime keeps no
        statistics), beside ``update_*_bytes`` from the update program's
        ``memory_analysis()``."""
        out: Dict[str, Optional[int]] = dict.fromkeys(
            ("dumped", "left", "swaps"))
        if trace.has_census(self.state):
            now = trace.census(self.state)
            before = (trace.initial_census(self.state)
                      if self._census is None else self._census)
            out.update({k: int(v) for k, v in
                        trace.census_diff(before, now).items()})
            self._census = now
        devices = self.fleet.meta["slab_sharding"].device_set
        stats = [d.memory_stats() for d in devices]
        out["peak_bytes_in_use"] = (
            max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
            if all(stats) else None)
        mem = self.compiled_update().memory_analysis()
        for k in ("argument", "output", "temp", "generated_code"):
            v = getattr(mem, f"{k}_size_in_bytes", None)
            out[f"update_{k}_bytes"] = None if v is None else int(v)
        return out

    def compiled_update(self):
        """The fleet update program as compiled for this engine's shapes
        (a ``jax.stages.Compiled``, from the compile cache once a tick
        has run): ``as_text()`` carries the ``dsfd.*`` named scopes in
        its ``op_name`` metadata, ``memory_analysis()`` its bytes."""
        return self._lower_update().compile()

    # -- queries -----------------------------------------------------------

    def query_user(self, user: int) -> np.ndarray:
        if self.topology is not None:
            user = self._route(user)
        one = jax.tree.map(lambda x: x[user], self.state)
        return np.asarray(self.base.query(one, self.t))

    def query_cohort(self, users=None) -> np.ndarray:
        """ONE compressed (2ℓ, d) sketch over a cohort of users' windows.

        ``users``: a :class:`repro.sketch.query.Cohort`, an int, an
        iterable of user ids, or ``None`` for the whole fleet.  Served
        from the engine's cached ``AggTree``: the first query over a
        region pays its node merges once, repeated/overlapping cohort
        queries between ticks reuse them (O(log S) merges warm).
        """
        from repro.sketch.query import as_cohort

        g = self.tree.query(self.state, as_cohort(users), self.t)
        return np.asarray(self.base.query(g, self.t))

    def query_global(self) -> np.ndarray:
        return self.query_cohort(None)

    def query_interval(self, users, t1: int, t2: int) -> np.ndarray:
        """Time-travel query: ONE compressed ``(2ℓ, d)`` sketch of every
        row the cohort's users ingested with timestamp in ``[t1, t2)``,
        answered from the persistent history plane of RETIRED window
        content (``repro.sketch.history``) — O(log(t2−t1)) dyadic node
        merges, hot nodes served from memory, cold ones faulted in from
        the spill tier.  ``users`` as in :meth:`query_cohort` (``None``
        for the whole fleet).  Needs ``history=True``; only intervals
        that have fully expired from the live window are addressable
        (``t2 − 1 <= t − window``) — live content is ``query_cohort``'s
        job.  Collective under a topology, like ``query_cohort``.

        Without ``history=True`` the fleet's capability raiser fires —
        its message explains how to build an engine that records history
        (``repro.sketch.capability``)."""
        from repro.sketch.query import as_cohort

        # delegation, not a hand-rolled guard: history-less fleets carry
        # a context-derived raiser installed by the capability protocol
        return self.fleet.query_interval(self.state, t1, t2,
                                         as_cohort(users))

    # -- the scoring plane ---------------------------------------------------

    def score_rows(self, rows, user: Optional[int] = None) -> np.ndarray:
        """Residual anomaly scores of ``rows`` (n, d) against one user's
        current window basis (or the whole-fleet aggregate when ``user``
        is None — equivalent to ``score_cohort(rows)``)."""
        if user is None:
            return self.score_cohort(rows)
        u = self._route(user)
        one = jax.tree.map(lambda x: x[u], self.state)
        return np.asarray(self.base.score(one, jnp.asarray(
            rows, jnp.float32), self.t))

    def score_cohort(self, rows, users=None) -> np.ndarray:
        """Residual anomaly scores of ``rows`` (n, d) against the merged
        window basis of a cohort (``users`` as in :meth:`query_cohort`;
        ``None`` = whole fleet) — the cached ``AggTree`` serves the
        merged state, the base variant's ``score`` capability does the
        residual."""
        from repro.sketch.query import as_cohort

        g = self.tree.query(self.state, as_cohort(users), self.t)
        return np.asarray(self.base.score(g, jnp.asarray(
            rows, jnp.float32), self.t))

    def anomalies(self, *, reset: bool = False,
                  collective: bool = False) -> np.ndarray:
        """GLOBAL user ids currently flagged anomalous by the per-user
        EWMA thresholds (``score=True`` engines; see
        ``repro.sketch.score.ScorePlane``).  ``reset=True`` clears the
        flags after reading.  Under a topology each process knows only
        its owned streams; ``collective=True`` allgathers every process's
        flagged ids into the same globally-sorted array on all processes
        (a collective — call it from every process)."""
        if self.score_plane is None:
            raise ValueError(
                "this engine scores nothing — build it with "
                "SketchFleetEngine(..., score=True[, score_zscore=..., "
                "score_warmup=...]) to run the per-user EWMA scoring "
                "plane at ingest")
        local = self.score_plane.anomalies(reset=reset)
        if self.topology is not None:
            local = local + np.int64(self.topology.lo)
            if collective:
                gathered = self.topology.allgather_array(
                    "anomalies", np.asarray(local, np.int64))
                local = np.sort(np.concatenate(gathered))
        return np.asarray(local, np.int64)

    def ranks(self) -> np.ndarray:
        """Per-stream working rank ℓ of this process's streams (adaptive
        variants only — ``make_sketch('fd', ..., adapt_target=...)``);
        fires the capability raiser otherwise."""
        return np.asarray(self.fleet.ranks(self.state))

    def space(self) -> Dict[str, int]:
        """Fleet-wide live-row accounting: per-stream total + cached
        ``AggTree`` node rows (see ``FleetSpace`` in ``sketch/api.py``)."""
        fs = self.fleet.space(self.state)
        out = {"per_stream_total": int(np.asarray(fs.per_stream).sum()),
               "cache_rows": int(fs.cache_rows),
               "total": int(fs.total)}
        if fs.ranks is not None:
            out["ranks_total"] = int(np.asarray(fs.ranks).sum())
        return out


def _score_aux_slice(aux: Dict[str, np.ndarray], lo: int,
                     hi: int) -> Optional[Dict[str, np.ndarray]]:
    """Reassemble a restoring process's ``[lo, hi)`` slice of the scoring
    plane's EWMA accumulators from checkpoint aux leaves keyed
    ``score_*_{save_lo:08d}_{save_hi:08d}`` — the save-time process count
    (and hence the key ranges) may differ from ours.  Streams no saved
    range covers restart cold (count 0); returns ``None`` when no score
    leaves exist at all (a pre-scoring checkpoint)."""
    from repro.sketch.score import ScorePlane

    out: Dict[str, np.ndarray] = {}
    found = False
    for base in ScorePlane.KEYS:
        acc = None
        for k, v in aux.items():
            if not k.startswith(base + "_"):
                continue
            try:
                klo, khi = (int(p) for p in k[len(base) + 1:].split("_"))
            except ValueError:
                continue
            a, b = max(lo, klo), min(hi, khi)
            if a >= b:
                continue
            v = np.asarray(v)
            if acc is None:
                acc = np.zeros((hi - lo,), v.dtype)
            acc[a - lo:b - lo] = v[a - klo:b - klo]
            found = True
        if acc is not None:
            out[base] = acc
    if not found:
        return None
    # a key entirely outside every saved range still needs cold arrays
    cold = ScorePlane(hi - lo).state_dict()
    for base in ScorePlane.KEYS:
        out.setdefault(base, cold[base])
    return out


def _splice_caches(cfg: ModelConfig, big, one, slot: int, s_max: int):
    """Insert a batch-1 prefill cache into batch slot `slot` of the engine
    cache, left-aligned into the s_max-long buffers where seq-shaped.

    Left alignment is the decode-step convention: valid cache entries
    occupy positions ``[0, length)`` and ``kv_cache_append`` writes the
    next token at index ``length`` (``decode_attention`` masks
    ``kpos < length``), so a b-token prefill cache lands at ``[0, b)``
    with zero-padding *after* it and ``length = b`` picks up exactly where
    prefill stopped.  Right-aligning the data into ``[s_max-b, s_max)``
    would desynchronize it from the write position.  (The token-level
    right-alignment of a short prompt *within* its prefill bucket in
    ``_admit`` is a separate, upstream padding choice.)"""

    def leaf(b, o):
        if b.ndim == 0 or o.shape[0] != b.shape[0]:
            return b
        # layer-stacked leaves: dim0 = layers, dim1 = batch
        if b.ndim >= 2 and o.shape[1] == 1 and b.shape[2:] != o.shape[2:]:
            # seq-capacity mismatch (prefill len < s_max): left-align —
            # pad zeros AFTER the cache so entry i stays at position i
            pad = [(0, 0)] * o.ndim
            pad[2] = (0, b.shape[2] - o.shape[2]) if b.ndim > 2 else (0, 0)
            o = jnp.pad(o, pad)
        if b.ndim >= 2 and o.shape[1] == 1:
            return b.at[:, slot:slot + 1].set(o.astype(b.dtype))
        if b.ndim == 1:                          # per-layer lengths
            return o
        return b

    return jax.tree.map(leaf, big, one)
