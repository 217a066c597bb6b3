"""Puts ``bench/`` (for ``lib``, ``reducers``, ``reference``) and the repo root
(for ``accelerate_tpu``) on the path.  Run from the repo root:
``JAX_PLATFORMS=cpu python -m pytest bench/tests -q``."""

import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = Path(__file__).resolve().parents[1]
for p in (str(BENCH.parent), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)
