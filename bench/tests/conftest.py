"""Tests of the benchmark.  Run from the checkout's root:

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/tests
"""

import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
