"""The benchmark's own tests; run by hand from the repo root:

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q chipbench/tests
"""

import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
for p in (HERE.parent, HERE.parent / "metrics", HERE.parents[1] / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
