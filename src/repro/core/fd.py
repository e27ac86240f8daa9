"""FrequentDirections (Liberty 2013; Ghashami et al. 2016) — jittable, scan-friendly.

This is the streaming primitive the paper builds on.  The sketch is a fixed
``(2ℓ, d)`` row buffer; rows ``[0, nbuf)`` hold data.  Incoming rows are written
into free slots (FastFD buffering); when the buffer fills, a single SVD
*shrink* subtracts ``σ_ℓ²`` from every squared singular value, zeroing at
least ``ℓ+1`` rows.  Guarantee (with ``ε = 1/ℓ``)::

    ‖AᵀA − BᵀB‖₂ ≤ ‖A‖_F² / ℓ        and        BᵀB ⪯ AᵀA .

Everything here is a pure function on a NamedTuple state so it composes with
``jax.jit`` / ``lax.scan`` / ``jax.vmap`` / ``shard_map``.  Shapes are static.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp


class FDState(NamedTuple):
    """FrequentDirections sketch state.

    buf:   (m, d) row buffer, m = 2ℓ.  Rows ≥ nbuf are zero.
    nbuf:  int32 — number of occupied rows.
    shed:  f32 — cumulative Σ σ_ℓ² discarded by shrinks (diagnostic; the FD
           error bound says ``shed ≤ (‖A‖_F² − ‖B‖_F²)/ℓ``).
    """

    buf: jax.Array
    nbuf: jax.Array
    shed: jax.Array


def fd_init(ell: int, d: int, dtype=jnp.float32) -> FDState:
    ell = int(min(ell, d))
    m = 2 * ell
    return FDState(
        buf=jnp.zeros((m, d), dtype),
        nbuf=jnp.zeros((), jnp.int32),
        shed=jnp.zeros((), dtype),
    )


def _svd_rows(buf: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Rows σᵢ·vᵢᵀ of the buffer, sorted by σ² descending and padded to
    ``buf.shape``, and σ² (clamped at 0).

    FD needs only the spectrum and the rotated rows, so they come from one
    ``eigh`` of the smaller Gram matrix, chosen by the static shape: for
    m ≤ d, K = BBᵀ = UΛUᵀ and the rows are UᵀB; for d < m, K = BᵀB = VΛVᵀ
    and the rows are √λ·Vᵀ.  Either way rowsᵀrows = BᵀB.  A general SVD
    would lower on the TPU to a QR and polar-decomposition loops around
    an ``eigh`` of the same size.  The matmuls run at full float32
    precision: the TPU's default would take bf16 passes."""
    m, d = buf.shape
    hi = jax.lax.Precision.HIGHEST
    if m <= d:
        lam, u = jnp.linalg.eigh(jnp.matmul(buf, buf.T, precision=hi))
        s2 = jnp.maximum(lam[::-1], 0.0)                 # eigh: ascending
        rows = jnp.matmul(u[:, ::-1].T, buf, precision=hi)
    else:
        lam, v = jnp.linalg.eigh(jnp.matmul(buf.T, buf, precision=hi))
        s2 = jnp.pad(jnp.maximum(lam[::-1], 0.0), (0, m - d))
        rows = jnp.sqrt(s2)[:, None] * jnp.pad(v[:, ::-1].T,
                                               ((0, m - d), (0, 0)))
    # eigh fixes each vector only up to sign, and its pick can flip with
    # the last bits of K, so that two programs over one stream would part;
    # the entry of largest magnitude of every row is made positive
    flip = -jnp.min(rows, axis=1) > jnp.max(rows, axis=1)
    return jnp.where(flip[:, None], -rows, rows), s2


def fd_rotate(buf: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Lossless re-orthogonalization: rows become σᵢ·vᵢᵀ sorted by σ desc.

    Returns (rows, σ²).  ``rowsᵀ rows == bufᵀ buf`` exactly (up to fp error).
    """
    return _svd_rows(buf)


def fd_shrink(buf: jax.Array, ell: int) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The FD shrink: subtract σ_ℓ² from every σ², re-materialize rows.

    Returns (rows, σ²_after, σ_ℓ²_discarded).  At least rows ℓ-1.. are zero.
    """
    rows, s2 = _svd_rows(buf)
    delta = s2[ell - 1]
    s2n = jnp.maximum(s2 - delta, 0.0)
    # rows are σ·vᵀ; rescale each row by sqrt(new σ² / old σ²).
    scale = jnp.sqrt(s2n / jnp.maximum(s2, 1e-30))
    return rows * scale[:, None], s2n, delta


def fd_update(state: FDState, row: jax.Array, *, ell: int) -> FDState:
    """Absorb one row (FastFD cadence: shrink only when the buffer fills).

    The ``fd.insert`` and ``fd.shrink`` named scopes mark its two phases
    in the compiled program's ``op_name`` metadata."""
    m = state.buf.shape[0]
    with jax.named_scope("fd.insert"):
        buf = jax.lax.dynamic_update_index_in_dim(state.buf, row, state.nbuf,
                                                  0)
        nbuf = state.nbuf + 1

    def do_shrink(args):
        buf, nbuf, shed = args
        with jax.named_scope("fd.shrink"):
            rows, _, delta = fd_shrink(buf, ell)
        return rows, jnp.asarray(ell - 1, jnp.int32), shed + delta

    def no_shrink(args):
        return args

    buf, nbuf, shed = jax.lax.cond(
        nbuf >= m, do_shrink, no_shrink, (buf, nbuf, state.shed))
    return FDState(buf, nbuf, shed)


def fd_absorb(state: FDState, rows: jax.Array, *, ell: int) -> FDState:
    """Absorb a block of rows via scan (rows with all-zero entries are skipped
    logically — they do not change BᵀB, so inserting them is harmless, but we
    still skip to preserve buffer occupancy)."""

    def step(st, r):
        is_zero = jnp.sum(r * r) <= 0.0
        st2 = fd_update(st, r, ell=ell)
        st = jax.tree.map(lambda a, b: jnp.where(is_zero, a, b), st, st2)
        return st, None

    state, _ = jax.lax.scan(step, state, rows)
    return state


@functools.partial(jax.jit, static_argnames=("ell",))
def fd_compress(mat: jax.Array, ell: int) -> jax.Array:
    """Compress an (n, d) matrix to a (2ℓ, d) FD sketch buffer (≤ ℓ-1 + tail
    nonzero rows).  Used by queries to merge snapshots with the residual;
    its program's ops carry the ``fd.compress`` named scope."""
    with jax.named_scope("fd.compress"):
        st = fd_init(ell, mat.shape[1], mat.dtype)
        st = fd_absorb(st, mat, ell=ell)
        return st.buf


def fd_query(state: FDState) -> jax.Array:
    """The sketch matrix B (fixed shape (2ℓ, d); trailing rows zero)."""
    return state.buf


def fd_merge(a: FDState, b: FDState, *, ell: int) -> FDState:
    """Merge two FD sketches (FD is mergeable: absorb b's rows into a)."""
    return fd_absorb(a, b.buf, ell=ell)


# ---------------------------------------------------------------------------
# Adaptive-rank FrequentDirections — grow/shrink ℓ toward a target residual
# error (the btx FreqDir rank-adaption idea: the user names the relative
# reconstruction error they can live with; the sketch adjusts its own rank
# to meet it as data streams in).
# ---------------------------------------------------------------------------


class AdaptiveFDState(NamedTuple):
    """FD state with an online working rank.

    buf:    (2·ℓ_max, d) row buffer — physical capacity is the rank *cap*,
            so states of every working rank share one static shape (jit /
            vmap / shard_map friendly); only rows [0, nbuf) are live.
    nbuf:   int32 — occupied rows.
    shed:   f32 — cumulative Σ σ_ℓ² discarded by shrinks.  The FD bound
            ``‖AᵀA − BᵀB‖₂ ≤ shed`` holds at every working rank.
    ell:    int32 — current working rank ℓ ∈ [ℓ_min, ℓ_max] (traced; the
            shrink indexes σ²_ℓ dynamically).
    energy: f32 — cumulative ‖A‖_F² of everything absorbed.
    shed_mark / energy_mark: f32 — ``shed``/``energy`` captured at the
            last rank change.  ``(shed − shed_mark) / (energy −
            energy_mark)`` is the relative error rate the CURRENT rank is
            incurring — the controller's signal.  (The cumulative ratio
            ``shed/energy`` is a stale signal: error already shed at a
            too-small rank cannot be undone by growing, so steering on it
            marches ℓ to ℓ_max long after the level stopped shedding.)
    """

    buf: jax.Array
    nbuf: jax.Array
    shed: jax.Array
    ell: jax.Array
    energy: jax.Array
    shed_mark: jax.Array
    energy_mark: jax.Array


def adaptive_fd_init(ell_max: int, d: int, *, ell0: int | None = None,
                     dtype=jnp.float32) -> AdaptiveFDState:
    ell_max = int(min(ell_max, d))
    ell0 = ell_max if ell0 is None else int(min(max(ell0, 1), ell_max))
    return AdaptiveFDState(
        buf=jnp.zeros((2 * ell_max, d), dtype),
        nbuf=jnp.zeros((), jnp.int32),
        shed=jnp.zeros((), dtype),
        ell=jnp.asarray(ell0, jnp.int32),
        energy=jnp.zeros((), dtype),
        shed_mark=jnp.zeros((), dtype),
        energy_mark=jnp.zeros((), dtype),
    )


def adaptive_fd_update(state: AdaptiveFDState, row: jax.Array, *,
                       target: float, ell_min: int,
                       ell_max: int) -> AdaptiveFDState:
    """Absorb one row; at each shrink, re-aim ℓ at the error target.

    Shrinks trigger at ``nbuf ≥ 2ℓ`` (the working rank's own cadence, not
    the physical capacity — a small-ℓ state shrinks early and cheaply).
    After the shrink the error rate incurred AT the current rank —
    ``(shed − shed_mark) / (energy − energy_mark)``, i.e. since the last
    rank change — is compared to ``target``: above it ℓ grows by one
    (more directions kept, less shed per shrink); below half of it ℓ
    shrinks by one — but only when the look-ahead agrees: the σ² of the
    direction rank ℓ−1 would start discarding (``s2[ℓ−2]``, read off the
    SVD the shrink already paid for) must itself be inside the half-
    target budget.  Without the look-ahead the controller ping-pongs:
    a level that sheds nothing invites a down-probe, the probe level
    sheds a full σ²_{ℓ−1} before the rate signal reacts, and on
    low-rank streams that single probe shrink can cost a large slice of
    the window energy.  The half-target dead zone plus the per-level
    measurement then keep ℓ hovering at the smallest rank that meets
    the target instead of ratcheting on stale cumulative error.
    All-zero rows are skipped (they change neither BᵀB nor the error).
    """
    is_zero = jnp.sum(row * row) <= 0.0
    buf = jax.lax.dynamic_update_index_in_dim(state.buf, row, state.nbuf, 0)
    nbuf = state.nbuf + 1
    energy = state.energy + jnp.sum(row * row).astype(state.energy.dtype)

    def do_shrink(args):
        buf, nbuf, shed, ell, smark, emark = args
        rows, s2n, delta = fd_shrink(buf, ell)
        shed = shed + delta
        span = jnp.maximum(energy - emark, 1e-30)
        err = (shed - smark) / span
        # what would rank ℓ−1 discard next time?  (pre-subtraction σ² at
        # index ℓ−2; with ℓ at ℓ_min the clip below voids the read)
        probe_cost = s2n[ell - 2] + delta
        down_ok = (err < 0.5 * target) \
            & (probe_cost <= 0.5 * target * span)
        new_ell = jnp.clip(ell
                           + (err > target).astype(jnp.int32)
                           - down_ok.astype(jnp.int32),
                           ell_min, ell_max)
        changed = new_ell != ell
        smark = jnp.where(changed, shed, smark)
        emark = jnp.where(changed, energy, emark)
        # occupancy = the rows the shrink actually left alive (sorted, so
        # a prefix).  Deriving it from the NEW ell would, on a rank
        # decrease, point the next insert AT a live row — silently
        # deleting unaccounted energy and voiding the ≤-shed bound.
        nlive = jnp.sum(s2n > 0.0).astype(jnp.int32)
        return rows, nlive, shed, new_ell, smark, emark

    def no_shrink(args):
        return args

    buf, nbuf, shed, ell, smark, emark = jax.lax.cond(
        nbuf >= 2 * state.ell, do_shrink, no_shrink,
        (buf, nbuf, state.shed, state.ell, state.shed_mark,
         state.energy_mark))
    st2 = AdaptiveFDState(buf, nbuf, shed, ell, energy, smark, emark)
    return jax.tree.map(lambda a, b: jnp.where(is_zero, a, b), state, st2)


def adaptive_fd_absorb(state: AdaptiveFDState, rows: jax.Array, *,
                       target: float, ell_min: int,
                       ell_max: int) -> AdaptiveFDState:
    def step(st, r):
        return adaptive_fd_update(st, r, target=target, ell_min=ell_min,
                                  ell_max=ell_max), None

    state, _ = jax.lax.scan(step, state, rows)
    return state


def adaptive_fd_merge(a: AdaptiveFDState, b: AdaptiveFDState, *,
                      target: float, ell_min: int,
                      ell_max: int) -> AdaptiveFDState:
    """Merge by absorbing b's buffer rows, then restore the *stream*
    accounting: energy/shed must cover both input streams, not count
    b's (already-shed-reduced) buffer content as fresh energy."""
    st = adaptive_fd_absorb(a, b.buf, target=target, ell_min=ell_min,
                            ell_max=ell_max)
    absorbed = jnp.sum(b.buf * b.buf).astype(st.energy.dtype)
    energy = st.energy - absorbed + b.energy
    shed = st.shed + b.shed
    # a merge splices two error histories: restart the current-rank
    # measurement window at the merged totals
    return st._replace(energy=energy, shed=shed,
                       shed_mark=shed, energy_mark=energy)
