"""Seq-DS-FD — unnormalized rows ‖a‖² ∈ [1, R] (Problem 1.2, §4).

``L+1 = ⌈log₂R⌉+1`` parallel DS-FD layers, dump thresholds θⱼ = 2ʲ·εN,
heavy rows (‖a‖² ≥ θⱼ) bypass straight into layer j's snapshot queues
(Algorithm 6), snapshot count capped at 2(1+4/β)/ε per layer, and the query
picks the lowest layer whose retained snapshots still span the window
(Algorithm 7).  The layer stack is a single vmapped DS-FD state, so the whole
structure updates in one fused XLA program.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core.dsfd import (DSFDConfig, DSFDState, dsfd_init, dsfd_merge,
                             dsfd_query_rows, dsfd_update)
from repro.core.fd import fd_compress


@dataclasses.dataclass(frozen=True)
class LayeredConfig:
    """Static config for a stack of DS-FD layers (Seq- or Time-DS-FD)."""

    base: DSFDConfig
    thetas: Tuple[float, ...]       # dump threshold per layer (ascending)
    swap_energies: Tuple[float, ...]

    @property
    def levels(self) -> int:
        return len(self.thetas)


def make_seq_config(d: int, eps: float, window: int, R: float, *,
                    beta: float = 4.0, mode: str = "fast") -> LayeredConfig:
    """Problem 1.2: θⱼ = 2ʲ εN for j = 0..⌈log₂R⌉ (Algorithm 5)."""
    L = max(int(math.ceil(math.log2(max(R, 1.0)))), 0)
    ell = int(min(max(round(1.0 / eps), 1), d))
    cap = int(2 * (1.0 + 4.0 / beta) / eps) + 4
    base = DSFDConfig(d=d, ell=ell, window=int(window), cap=cap, mode=mode)
    thetas = tuple((2.0 ** j) * eps * window for j in range(L + 1))
    swaps = tuple(ell * th for th in thetas)   # aux promotes at ℓθ absorbed
    return LayeredConfig(base=base, thetas=thetas, swap_energies=swaps)


def make_time_config(d: int, eps: float, window: int, R: float, *,
                     beta: float = 4.0, mode: str = "fast") -> LayeredConfig:
    """Problems 1.3/1.4 (§5): θⱼ = 2ʲ for j = 0..⌈log₂(εNR)⌉."""
    L = max(int(math.ceil(math.log2(max(eps * window * max(R, 1.0), 2.0)))), 1)
    ell = int(min(max(round(1.0 / eps), 1), d))
    cap = int(2 * (1.0 + 4.0 / beta) / eps) + 4
    base = DSFDConfig(d=d, ell=ell, window=int(window), cap=cap, mode=mode)
    thetas = tuple(2.0 ** j for j in range(L + 1))
    swaps = tuple(ell * th for th in thetas)
    return LayeredConfig(base=base, thetas=thetas, swap_energies=swaps)


def layered_init(cfg: LayeredConfig, t0: int = 1):
    one = dsfd_init(cfg.base, t0)
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x, (cfg.levels,) + x.shape), one)


def layered_update(cfg: LayeredConfig, state, row: jax.Array, now):
    """Feed one row to every layer (Algorithm 6).  Zero rows (idle time-based
    ticks) only advance expiry/swap logic."""
    thetas = jnp.asarray(cfg.thetas, jnp.float32)
    swaps = jnp.asarray(cfg.swap_energies, jnp.float32)

    def per_layer(st: DSFDState, th, sw):
        return dsfd_update(cfg.base, st, row, now, theta=th, swap_energy=sw,
                           bypass=True)

    return jax.vmap(per_layer)(state, thetas, swaps)


def layered_covered(cfg: LayeredConfig, state, now) -> jax.Array:
    """Per-layer bool: does (queue ∪ residual) span the window
    [max(now−N+1, 1), now]?  Timestamps start at 1, so before a window has
    filled it is the whole stream so far, which a layer that has lost
    nothing since the stream's start (``cov_start`` 1) covers."""
    now = jnp.asarray(now, jnp.int32)
    return state.main.cov_start <= jnp.maximum(now - cfg.base.window + 1, 1)


def layered_select(cfg: LayeredConfig, state, now) -> jax.Array:
    """Index of the lowest covered layer (Algorithm 7 line 1); the top
    layer when none covers."""
    with jax.named_scope("dsfd.select"):
        cov = layered_covered(cfg, state, now)
        idx = jnp.arange(cfg.levels)
        return jnp.min(jnp.where(cov, idx, cfg.levels - 1))


def layered_query_rows(cfg: LayeredConfig, state, now) -> jax.Array:
    """Stacked B_W rows ((cap+m, d)) from the selected layer."""
    j = layered_select(cfg, state, now)
    layer = jax.tree.map(lambda x: x[j], state)
    return dsfd_query_rows(cfg.base, layer, now=now)


def layered_query(cfg: LayeredConfig, state, now) -> jax.Array:
    return fd_compress(layered_query_rows(cfg, state, now), cfg.base.ell)


def layered_merge(cfg: LayeredConfig, s1, s2, now=None):
    """Merge two layered (Seq-/Time-DS-FD) states layer-by-layer.

    Layer j of both inputs runs the same threshold θⱼ, so the DS-FD merge
    (snapshot ∪ residual union, re-compressed to 2ℓ) applies per layer and
    the Algorithm 7 layer selection still works on the merged stack — the
    merged ``cov_start`` per layer is the max (intersection) of the two
    sides, so a layer only claims to cover the window when both inputs do.
    """
    return jax.vmap(lambda a, b: dsfd_merge(cfg.base, a, b, now))(s1, s2)


@functools.partial(jax.jit, static_argnames=("cfg", "query_every"))
def layered_run_stream(cfg: LayeredConfig, rows: jax.Array,
                       ts: jax.Array, query_every: int = 0):
    """Scan a stream (with explicit int32 timestamps ``ts``, supporting
    time-based streams: repeated or skipped timestamps are both legal)."""

    def step(state, inp):
        t, row = inp
        state = layered_update(cfg, state, row, t)
        if query_every:
            out = jax.lax.cond(
                jnp.mod(t, query_every) == 0,
                lambda s: layered_query_rows(cfg, s, t),
                lambda s: jnp.zeros((cfg.base.cap + cfg.base.m, cfg.base.d),
                                    jnp.float32),
                state)
        else:
            out = None
        return state, out

    state = layered_init(cfg)
    return jax.lax.scan(step, state, (ts.astype(jnp.int32), rows))
