"""``sketchy_dsfd`` — Sketchy-style (Feinberg et al. 2024, cited as [16] in
the paper) low-rank adaptive preconditioning where the per-layer gradient
covariance estimate comes from a *sliding-window* DS-FD sketch instead of a
full-stream FD: stale curvature is forgotten, which is exactly the paper's
contribution applied to second-moment estimation.

Per 2-D+ parameter (rows n, cols d):

    sketch S_t  ← DS-FD over FD-compressed rows of g_t  (window W steps)
    (λ_i, v_i)  ← top-r eigenpairs of the windowed covariance Σ_W gᵀg
    precond(g)  = (g V) diag(1/√(λ·s + ρ)) Vᵀ + (g − (g V) Vᵀ)/√ρ

i.e. Sketchy's "low-rank + isotropic tail" inverse root.  1-D params fall
back to Adam-style diagonal second moments.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, NamedTuple

import jax
import jax.numpy as jnp

from repro.core.fd import fd_compress
from repro.sketch.api import SlidingSketch, make_sketch
from repro.sketch.basis import topr_basis
from repro.train.optimizer import Optimizer


@dataclasses.dataclass(frozen=True)
class SketchyConfig:
    lr: float = 1e-2
    rank: int = 8
    eps: float = 0.25                # DS-FD resolution (ℓ = 1/eps)
    window: int = 64                 # steps the curvature window spans
    rho: float = 1e-6                # isotropic tail
    momentum: float = 0.9
    summary_rows: int = 4            # FD-compressed rows fed per step
    min_dim: int = 8                 # cols below this → diagonal path
    warmup: int = 20

    def sketch(self, d: int) -> SlidingSketch:
        return make_sketch("dsfd", d=d, eps=self.eps,
                           window=self.window * self.summary_rows,
                           mode="fast")


class SketchyState(NamedTuple):
    sketch: Any        # per-leaf DS-FD state (or None)
    diag: Any          # per-leaf diagonal v (1-D fallback)
    mom: Any


def _sketched(p, cfg: SketchyConfig) -> bool:
    return p.ndim >= 2 and p.shape[-1] >= cfg.min_dim


def sketchy_dsfd(cfg: SketchyConfig = SketchyConfig()) -> Optimizer:
    def init(params):
        def sk(p):
            return (cfg.sketch(p.shape[-1]).init()
                    if _sketched(p, cfg) else None)

        def dg(p):
            return (jnp.zeros((), jnp.float32) if _sketched(p, cfg)
                    else jnp.zeros(p.shape, jnp.float32))

        mom = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        return SketchyState(sketch=jax.tree.map(sk, params),
                            diag=jax.tree.map(dg, params), mom=mom)

    def update(grads, state, params, step):
        stepf = step.astype(jnp.float32) + 1.0
        sched = cfg.lr * jnp.minimum(1.0, stepf / cfg.warmup)

        flat_g, treedef = jax.tree_util.tree_flatten(grads)
        flat_p = treedef.flatten_up_to(params)
        flat_sk = treedef.flatten_up_to(state.sketch)
        flat_dg = treedef.flatten_up_to(state.diag)
        flat_m = treedef.flatten_up_to(state.mom)

        new_p, new_sk, new_dg, new_m = [], [], [], []
        for g, p, sk, dg, m in zip(flat_g, flat_p, flat_sk, flat_dg, flat_m):
            gf = g.astype(jnp.float32)
            if sk is None:
                dg2 = 0.99 * dg + 0.01 * jnp.square(gf)
                upd = gf / jnp.maximum(jnp.sqrt(dg2), 1e-8)
            else:
                d = p.shape[-1]
                sliding = cfg.sketch(d)
                g2 = gf.reshape(-1, d)
                # feed FD-compressed row summary, unit-normalized: FD at
                # ℓ keeps its top ℓ−1 directions, so ℓ = summary_rows + 1
                # (ℓ = 1 keeps none and the sketch stays empty)
                summary = fd_compress(
                    g2, cfg.summary_rows + 1)[: cfg.summary_rows]
                scale2 = jnp.sum(g2 * g2)
                nrm = jnp.linalg.norm(summary, axis=1, keepdims=True)
                unit = summary / jnp.maximum(nrm, 1e-30)
                base = step.astype(jnp.int32) * cfg.summary_rows + 1
                # one fused block absorb instead of a per-row python loop
                sk = sliding.update_block(
                    sk, unit, base + jnp.arange(cfg.summary_rows))
                rows = sliding.query_rows(sk)
                lam, V = topr_basis(rows, cfg.rank)      # directions only
                # rescale eigenvalues from unit rows to gradient energy
                lam = lam * scale2 / jnp.maximum(jnp.sum(lam), 1e-30)
                coef = g2 @ V.T                          # (n, r)
                inv = 1.0 / jnp.sqrt(lam + cfg.rho)
                low = (coef * inv[None, :]) @ V
                # isotropic tail at the smallest live eigenvalue (Sketchy's
                # escaped-mass estimate).  Scaled by the bare 1/√ρ instead,
                # the tail swamps the kept directions and the trust region
                # turns every step into a unit-RMS normalized-gradient step.
                floor = jnp.min(jnp.where(lam > 0.0, lam, jnp.inf))
                floor = jnp.where(jnp.isfinite(floor), floor, 0.0)
                tail = (g2 - coef @ V) / jnp.sqrt(floor + cfg.rho)
                upd = (low + tail).reshape(p.shape)
                # trust-region style normalization (Sketchy App. B)
                rms = jnp.sqrt(jnp.mean(jnp.square(upd)) + 1e-30)
                upd = upd / jnp.maximum(rms, 1.0)
                dg2 = dg
            m2 = cfg.momentum * m + upd
            new_p.append((p.astype(jnp.float32) - sched * m2).astype(p.dtype))
            new_sk.append(sk)
            new_dg.append(dg2)
            new_m.append(m2)

        unf = jax.tree_util.tree_unflatten
        return unf(treedef, new_p), SketchyState(
            sketch=unf(treedef, new_sk), diag=unf(treedef, new_dg),
            mom=unf(treedef, new_m))

    return Optimizer("sketchy_dsfd", init, update)
