"""Sliding-window sketches behind one protocol, lifted into fleets:

* ``api``      — the unified ``SlidingSketch`` protocol + registry: every
  sketch variant (DS-FD family and baselines) behind one
  init/update/update_block/query_rows/query/space/merge contract, and the
  one fleet lift (``vmap_streams`` / ``shard_streams``) for fleet-scale
  serving.
* ``query``    — the fleet query plane: ``Cohort`` algebra (unions of
  stream ranges) + ``AggTree`` cached merge trees; ``query_cohort``
  answers aggregate queries over any cohort in O(log S) warm node merges
  (``merge_streams`` is its deprecated whole-fleet alias).
* ``history``  — the persistent sketch plane: expiring window content is
  *retired* into a time-dyadic index of compressed (2ℓ, d) snapshots
  (hot LRU tier + cold spill through ``train/checkpoint.py``), so
  ``query_interval`` answers ANY historical interval ``[t1, t2)`` in
  O(log(t2−t1)) node merges with the FD additive-error guarantee.
* ``capability`` — the optional-protocol mechanism: capabilities
  (``query_cohort`` / ``query_interval`` / ``score`` / ``ranks``) are
  declared once with context-derived availability and error text,
  installed uniformly, introspected via ``capabilities(sk)``.
* ``score``    — the scoring plane: residual anomaly scores against the
  sketch basis (``score`` on every variant, slab scoring on fleets) and
  the per-user EWMA ``ScorePlane`` the serving engine runs at ingest.
* ``monitor``  — SlidingGradSketch: windowed streaming PCA of gradients.
* ``compress`` — FD low-rank gradient compression with error feedback for
  the cross-pod all-reduce.
* ``sketchy``  — sliding-window Sketchy optimizer (FD preconditioning with
  curvature forgetting).
"""

from repro.sketch.api import ALL, AggTree, Cohort, FleetSpace, \
    SlidingSketch, agg_tree, available_sketches, make_sketch, \
    merge_streams, query_cohort, query_interval, register, \
    shard_streams, vmap_streams                                 # noqa: F401
from repro.sketch.capability import CapabilityInfo, OPTIONAL_FIELDS, \
    capabilities                                                # noqa: F401
from repro.sketch.score import ScorePlane                       # noqa: F401
from repro.sketch.history import HistoryPlane, dyadic_cover, \
    install_query_interval, interval_merge_budget               # noqa: F401
from repro.sketch.monitor import SketchConfig, sketch_init, sketch_update, \
    sketch_query, sketch_score, cohort_sketch_query, \
    subspace_drift                                              # noqa: F401
from repro.sketch.compress import CompressConfig, compress_grads, \
    compress_init, wire_bytes, compressed_psum                  # noqa: F401
from repro.sketch.sketchy import SketchyConfig, sketchy_dsfd    # noqa: F401
