"""Logical-axis sharding layer (MaxText-style, compact).

Params and activations are annotated with *logical* axis names; a per-run
rule table maps logical names → mesh axes.  Rules are computed per
architecture so that a dimension is sharded only when it divides the mesh
axis (otherwise it falls back to replication — recorded per-arch in the
dry-run artifact).  ``constrain`` is a no-op outside a mesh context so the
same model code runs on 1 CPU device and on the 512-chip production mesh.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Dict, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_ctx = threading.local()


def _axis_size(mesh: Mesh, axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, (tuple, list)):
        n = 1
        for a in axis:
            n *= mesh.shape[a]
        return n
    return mesh.shape[axis]


def make_rules(mesh: Mesh, dims: Dict[str, int], *,
               fsdp: bool = False) -> Dict[str, object]:
    """Build the logical→mesh table for one architecture.

    ``dims`` maps logical name → dimension size (0/absent → replicate).
    A name maps to the 'model' axis only if its size divides it; 'batch'
    maps to every data-like axis present in the mesh.

    ``fsdp=True`` additionally shards the 'embed' logical axis over the
    data axes (ZeRO-3 / FSDP semantics): *weights* get their d_model dim
    sharded over (pod, data) and are all-gathered per layer inside the
    scan, while *activations* keep 'batch' on the data axes (to_pspec
    drops the duplicate axis).  Enabled for configs whose per-chip bf16
    params would not fit otherwise (kimi-k2, grok-1).
    """
    data_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    model = "model" if "model" in mesh.shape else None
    dsize = 1
    for a in data_axes:
        dsize *= mesh.shape[a]
    embed = None
    if fsdp and data_axes and dims.get("embed", 0) \
            and dims.get("embed", 0) % max(dsize, 1) == 0:
        embed = data_axes
    rules: Dict[str, object] = {
        "batch": data_axes if data_axes else None,
        "seq": None, "embed": embed, "frames": None, "pos": None,
        "state": None, "conv": None, "qk": None,
    }
    msize = _axis_size(mesh, model)
    for name in ("heads", "kv", "ff", "vocab", "experts", "expert_ff",
                 "lru", "inner"):
        size = dims.get(name, 0)
        rules[name] = model if (model and size and size % msize == 0) else None
    # KV-cache seq dim: shard over 'model' exactly when the KV heads can't
    # be (GQA head counts like 3/8/20 vs a 16-way axis) — one of the two
    # always carries the model axis so decode caches never replicate.
    rules["kv_seq"] = model if (model and dims.get("kv", 0)
                                and rules.get("kv") is None) else None
    # Sequence-parallel attention fallback: when the Q heads don't divide
    # the model axis (smollm 9H, whisper 20H, qwen2-vl 12H on a 16-way
    # axis), the attention section shards the *sequence* over 'model'
    # instead of replicating all head compute (§Perf iteration 2).
    rules["seq_attn"] = model if (model and dims.get("heads", 0)
                                  and rules.get("heads") is None) else None
    return rules


def constrain_divisible(x: jax.Array, *axes: Optional[str]) -> jax.Array:
    """Like ``constrain`` but drops any axis that does not divide its
    dimension (e.g. 'seq_attn' during single-token decode)."""
    st = getattr(_ctx, "state", None)
    if st is None:
        return x
    mesh, rules = st
    spec = to_pspec(tuple(axes), rules)
    parts = []
    for dim, p in zip(x.shape, tuple(spec) + (None,) * (x.ndim - len(spec))):
        if p is None:
            parts.append(None)
            continue
        names = p if isinstance(p, (tuple, list)) else (p,)
        n = _axis_size(mesh, tuple(names))
        parts.append(p if dim % max(n, 1) == 0 else None)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*parts)))


@contextlib.contextmanager
def axis_rules(mesh: Mesh, rules: Dict[str, object]):
    prev = getattr(_ctx, "state", None)
    _ctx.state = (mesh, rules)
    try:
        yield
    finally:
        _ctx.state = prev


def current_mesh() -> Optional[Mesh]:
    st = getattr(_ctx, "state", None)
    return st[0] if st else None


def current_rules() -> Optional[Dict[str, object]]:
    st = getattr(_ctx, "state", None)
    return st[1] if st else None


def to_pspec(axes: Tuple[Optional[str], ...],
             rules: Optional[Dict[str, object]] = None) -> P:
    rules = rules if rules is not None else (current_rules() or {})
    parts = []
    for name in axes:
        parts.append(rules.get(name) if name else None)
    # PartitionSpec disallows repeating a mesh axis: keep first occurrence.
    seen = set()
    clean = []
    for p in parts:
        key = tuple(p) if isinstance(p, (list, tuple)) else p
        if key is not None and key in seen:
            clean.append(None)
        else:
            clean.append(p)
            if key is not None:
                seen.add(key)
    return P(*clean)


def constrain(x: jax.Array, *axes: Optional[str]) -> jax.Array:
    """with_sharding_constraint by logical names (no-op without a mesh)."""
    st = getattr(_ctx, "state", None)
    if st is None:
        return x
    mesh, rules = st
    spec = to_pspec(tuple(axes), rules)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def named_sharding(axes: Tuple[Optional[str], ...]) -> Optional[NamedSharding]:
    st = getattr(_ctx, "state", None)
    if st is None:
        return None
    mesh, rules = st
    return NamedSharding(mesh, to_pspec(tuple(axes), rules))
