"""Mixture-of-Experts with shard_map expert parallelism.

Design (DESIGN.md §5): tokens arrive replicated over the 'model' axis (same
as the dense-TP MLP input); experts are sharded over 'model'.  Each device
dispatches its local tokens to its *local* experts with a capacity-bounded
sort-free scatter, runs the grouped SwiGLU, and the final ``psum`` over
'model' plays the role of the dense MLP's TP all-reduce — MoE adds no extra
collective volume per layer.

**Virtual experts**: when n_experts < model-axis size M (grok: 8 experts on
a 16-way axis) each expert is split into ``M/E`` column-shards of its FFN
(w_up/w_gate split along F, w_down along rows).  A token routed to expert e
visits all of e's virtual shards; the combine psum adds the partial sums.
This makes EP degree always equal M with zero redundant compute.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.base import MoECfg
from repro.parallel.sharding import current_mesh, current_rules


@dataclasses.dataclass(frozen=True)
class MoEMeshInfo:
    msize: int                 # model-axis size (EP degree)
    axis: Optional[str]        # model axis name (None → single device)
    batch_axes: Tuple[str, ...]


def _mesh_info() -> MoEMeshInfo:
    mesh = current_mesh()
    if mesh is None or "model" not in mesh.shape:
        return MoEMeshInfo(1, None, ())
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    return MoEMeshInfo(mesh.shape["model"], "model", batch_axes)


def virtual_split(moe: MoECfg, msize: int) -> int:
    if moe.n_experts >= msize:
        assert moe.n_experts % msize == 0, (moe.n_experts, msize)
        return 1
    assert msize % moe.n_experts == 0, (moe.n_experts, msize)
    return msize // moe.n_experts


def _local_moe(x, wr, wg, wu, wd, *, moe: MoECfg, split: int,
               msize: int, axis: Optional[str]):
    """Per-device MoE body.  x: (B_l, S, D).  wg/wu: (E_lv, D, Fv),
    wd: (E_lv, Fv, D) — local virtual experts."""
    B, S, D = x.shape
    T = B * S
    E_v = moe.n_experts * split
    E_l = E_v // msize
    k = moe.top_k
    ks = k * split
    xf = x.reshape(T, D)

    probs = jax.nn.softmax(
        jnp.einsum("td,de->te", xf.astype(jnp.float32),
                   wr.astype(jnp.float32)), axis=-1)      # (T, E)
    topw, topi = jax.lax.top_k(probs, k)                  # (T, k)
    topw = topw / jnp.maximum(topw.sum(-1, keepdims=True), 1e-9)

    # aux load-balance loss (Switch): E · Σ_e f_e · P̄_e
    ohe = jax.nn.one_hot(topi[:, 0], moe.n_experts, dtype=jnp.float32)
    aux = moe.n_experts * jnp.mean(
        jnp.mean(ohe, axis=0) * jnp.mean(probs, axis=0))

    # virtual assignment ids / weights
    v_ids = (topi[:, :, None] * split
             + jnp.arange(split)[None, None, :]).reshape(T, ks)
    w_rep = jnp.repeat(topw, split, axis=1)               # (T, ks)

    m_idx = jax.lax.axis_index(axis) if axis else 0
    local = (v_ids // E_l) == m_idx
    local_e = jnp.where(local, v_ids - m_idx * E_l, E_l)  # sentinel E_l

    # capacity-bounded positions (one-hot running count).  Everything below
    # is buffer-centric: the only (⋅, D) tensors are (E_l·C, D) — the token
    # side stays int32, so peak memory is O(E_l·C·D), not O(T·ks·D).
    C = max(8, int((T * ks) / E_v * moe.capacity_factor) + 1)
    C = min(C, T)
    oh = jax.nn.one_hot(local_e.reshape(-1), E_l, dtype=jnp.int32)
    pos = (jnp.cumsum(oh, axis=0) - oh)                    # (T·ks, E_l)
    pos_of = jnp.sum(pos * oh, axis=-1)                    # (T·ks,)
    keep = local.reshape(-1) & (pos_of < C)
    slot = jnp.where(keep, local_e.reshape(-1) * C + pos_of, E_l * C)

    tok_ids = jnp.arange(T * ks, dtype=jnp.int32) // ks
    src = jnp.full((E_l * C + 1,), T, jnp.int32).at[slot].set(
        jnp.where(keep, tok_ids, T))                       # slot → token
    wslot = jnp.zeros((E_l * C + 1,), jnp.float32).at[slot].set(
        jnp.where(keep, w_rep.reshape(-1), 0.0))

    xpad = jnp.concatenate([xf, jnp.zeros((1, D), xf.dtype)], axis=0)
    ebuf = jnp.take(xpad, src[: E_l * C], axis=0).reshape(E_l, C, D)

    g = jnp.einsum("ecd,edf->ecf", ebuf, wg)
    u = jnp.einsum("ecd,edf->ecf", ebuf, wu)
    h = (jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u)
    out = jnp.einsum("ecf,efd->ecd", h, wd)                # (E_l, C, D)

    weighted = (out.reshape(E_l * C, D)
                * wslot[: E_l * C, None].astype(out.dtype))
    y = jnp.zeros((T + 1, D), out.dtype).at[src[: E_l * C]].add(weighted)
    y = y[:T].reshape(B, S, D)
    if axis:
        y = jax.lax.psum(y, axis)
        aux = jax.lax.pmean(aux, axis)
    return y.astype(x.dtype), aux


def moe_block(x: jax.Array, wr: jax.Array, wg: jax.Array, wu: jax.Array,
              wd: jax.Array, *, moe: MoECfg):
    """x: (B, S, D) global.  wg/wu: (E_v, D, Fv), wd: (E_v, Fv, D) global
    *virtual-expert* weights (see ``virtual_expert_shapes``).  Returns
    (y, aux_loss)."""
    info = _mesh_info()
    split = virtual_split(moe, info.msize)
    mesh = current_mesh()
    if mesh is None or info.axis is None:
        return _local_moe(x, wr, wg, wu, wd, moe=moe, split=split,
                          msize=1, axis=None)

    rules = current_rules() or {}
    bspec = rules.get("batch")
    x_spec = P(bspec, None, None)
    body = partial(_local_moe, moe=moe, split=split, msize=info.msize,
                   axis=info.axis)
    y, aux = jax.shard_map(
        body, mesh=mesh,
        in_specs=(x_spec, P(None, None), P("model", None, None),
                  P("model", None, None), P("model", None, None)),
        out_specs=(x_spec, P()),
        check_vma=False,
    )(x, wr, wg, wu, wd)
    return y, aux


def virtual_expert_shapes(moe: MoECfg, d_model: int, msize: int):
    """Global parameter shapes after virtual splitting."""
    split = virtual_split(moe, msize)
    E_v = moe.n_experts * split
    Fv = moe.d_expert // split
    return E_v, Fv
