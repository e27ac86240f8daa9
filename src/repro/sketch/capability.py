"""Capability protocol for the optional ``SlidingSketch`` surface.

* a capability is an optional ``SlidingSketch`` field (``OPTIONAL_FIELDS``);
* when a sketch lacks one, :func:`install_missing` fills the field with a
  *tagged raiser* whose message is derived from the sketch's actual
  context (:func:`context`) — single vs fleet, host vs JAX, history plane
  attached or not — so the guidance always names a constructor the caller
  can really use;
* real implementations attach through :func:`install`, which tags the
  function and merges any meta the capability needs (e.g. the history
  plane's ``hist_box``);
* :func:`capabilities` introspects the lot — name, availability, and the
  would-be error text — uniformly for every variant, fleet, and engine.

The fleet lift (``vmap_streams`` / ``shard_streams``) calls
:func:`install_missing` on its product: raisers are regenerated for the
*new* context (a fleet without a history plane explains how to attach
one; a single sketch explains how to become a fleet first), while real
implementations pass through untouched.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple, Optional

#: The optional protocol fields, in declaration order: the cohort and
#: interval query planes, then the scoring plane (residual anomaly
#: scores; per-stream adaptive rank).
OPTIONAL_FIELDS = ("query_cohort", "query_interval", "score", "ranks")


class CapabilityInfo(NamedTuple):
    """One row of :func:`capabilities`: is ``name`` available on this
    sketch, and if not, the exact error text its raiser would produce."""

    name: str
    available: bool
    reason: Optional[str]


def context(sk) -> Dict[str, Any]:
    """The facts the availability messages are derived from."""
    meta = sk.meta
    return {
        "name": sk.name,
        "backend": meta.get("backend"),
        "fleet": meta.get("streams") is not None,
        "history": meta.get("hist_box") is not None,
        "adaptive": meta.get("adapt") is not None,
    }


def _missing_message(cap: str, ctx: Dict[str, Any]) -> str:
    """Receiver-correct guidance for a missing capability.

    Every branch names only constructors the *caller's object* can be fed
    to: a single sketch is told to lift first, a fleet is told to attach,
    a host baseline is told which backend serves the feature.
    """
    name = ctx["name"]
    if cap == "query_cohort":
        if ctx["fleet"]:
            return (f"fleet {name!r} exposes no cohort query plane — "
                    "rebuild it with vmap_streams/shard_streams so the "
                    "AggTree is attached")
        return (f"{name!r} is a single sketch — cohort queries need a "
                "fleet: lift it with vmap_streams/shard_streams, then call "
                "query_cohort(state, cohort, t)")
    if cap == "query_interval":
        if ctx["backend"] == "host":
            return (f"{name!r} is a host-side baseline — query_interval "
                    "(time-travel over retired window content) is served "
                    "by the JAX fleet path only: serve a JAX variant "
                    "through SketchFleetEngine(..., history=True)")
        if ctx["fleet"]:
            return (f"fleet {name!r} has no history plane — time-travel "
                    "interval queries need retired window content to be "
                    "recorded: serve the fleet through "
                    "SketchFleetEngine(..., history=True) or attach a "
                    "plane with repro.sketch.history."
                    "install_query_interval(fleet, plane)")
        return (f"{name!r} is a single sketch — time-travel interval "
                "queries need a fleet with a history plane: serve it "
                "through SketchFleetEngine(..., history=True), or lift it "
                "first with fleet = vmap_streams(sk, S) and then attach a "
                "plane with repro.sketch.history."
                "install_query_interval(fleet, plane)")
    if cap == "score":
        if ctx["backend"] == "host":
            return (f"{name!r} exposes no residual scorer — host "
                    "baselines built via make_sketch() carry the numpy "
                    "adapter; hand-built instances can attach one with "
                    "repro.sketch.capability.install(sk, 'score', fn)")
        return (f"{name!r} exposes no residual scorer — build it via "
                "make_sketch() (every registered variant installs score) "
                "or attach one with "
                "repro.sketch.capability.install(sk, 'score', fn)")
    if cap == "ranks":
        return (f"{name!r} runs at a fixed rank — per-stream adaptive "
                "rank is opt-in: build the base sketch with "
                "make_sketch('fd', ..., adapt_target=...) so ell "
                "grows/shrinks toward the target residual error and "
                "ranks(state) reports the per-stream working rank")
    return f"{name!r} does not implement capability {cap!r}"


def missing(cap: str, sk) -> Callable:
    """A tagged raiser for ``cap`` derived from ``sk``'s current context."""
    reason = _missing_message(cap, context(sk))

    def raiser(*args, **kwargs):
        raise ValueError(reason)

    raiser.capability = cap
    raiser.capability_missing = True
    raiser.capability_reason = reason
    return raiser


def is_missing(fn: Optional[Callable]) -> bool:
    """True when the field is empty or holds a tagged raiser."""
    return fn is None or getattr(fn, "capability_missing", False)


def has(sk, cap: str) -> bool:
    """True when ``sk`` carries a *real* implementation of ``cap``."""
    return not is_missing(getattr(sk, cap, None))


def install(sk, cap: str, impl: Callable, **meta_update):
    """Attach a real implementation of ``cap``; merges ``meta_update``
    (e.g. the history plane's ``hist_box``) so :func:`context` and every
    later :func:`install_missing` see the new fact."""
    if cap not in OPTIONAL_FIELDS:
        raise ValueError(
            f"unknown capability {cap!r}; declared: {OPTIONAL_FIELDS}")
    impl.capability = cap
    impl.capability_missing = False
    kw = {cap: impl}
    if meta_update:
        kw["meta"] = dict(sk.meta, **meta_update)
    return sk._replace(**kw)


def install_missing(sk):
    """Fill every absent capability with a context-derived raiser.

    Idempotent, and *re-derives* stale raisers: a raiser minted for a
    single sketch that was since lifted into a fleet (or gained a history
    plane via :func:`install`) is replaced with one whose guidance matches
    the new context.  Real implementations are never touched.
    """
    repl = {}
    for cap in OPTIONAL_FIELDS:
        if is_missing(getattr(sk, cap, None)):
            repl[cap] = missing(cap, sk)
    return sk._replace(**repl) if repl else sk


def capabilities(sk) -> Dict[str, CapabilityInfo]:
    """Uniform introspection over every declared capability."""
    out: Dict[str, CapabilityInfo] = {}
    ctx = context(sk)
    for cap in OPTIONAL_FIELDS:
        fn = getattr(sk, cap, None)
        if is_missing(fn):
            reason = (getattr(fn, "capability_reason", None)
                      or _missing_message(cap, ctx))
            out[cap] = CapabilityInfo(cap, False, reason)
        else:
            out[cap] = CapabilityInfo(cap, True, None)
    return out
