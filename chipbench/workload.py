"""The traffic generator: reads a mix file and makes, from the seed, the
rows a run offers to the engine.

A mix (``traffic/<name>.json``) holds only parameters:

* ``keys``: ``{"dist": "uniform"}`` -- rows spread evenly over the
  fleet's streams;
* ``arrivals``: ``{"kind": "saturate", "pending_blocks": 2}`` -- before
  each tick every stream is topped up to that many blocks pending, so
  each tick absorbs a full slab.

Every seed gets the same amount of work; row contents come from the
configuration's row model as a function of (user, that user's row
ordinal), so they do not depend on timing.
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
KINDS = {("uniform", "saturate")}


def load_mix(name: str) -> dict:
    path = HERE / "traffic" / f"{name}.json"
    if not path.is_file():
        raise SystemExit(f"chipbench: no traffic mix {path}")
    mix = json.loads(path.read_text())
    kind = (mix["keys"]["dist"], mix["arrivals"]["kind"])
    if kind not in KINDS:
        raise SystemExit(f"chipbench: mix {name}: keys {kind[0]!r} with "
                         f"arrivals {kind[1]!r} is not generated")
    return mix


def rng(seed: int, *tags: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32,
                                  *tags])


def fill_users(streams: int, block: int) -> np.ndarray:
    """Users of one full uniform slab: ``block`` rows for every stream."""
    return np.repeat(np.arange(streams, dtype=np.int32), block)


def load_json(path: Path) -> dict:
    return json.loads(Path(path).read_text())
