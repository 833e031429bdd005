"""The benchmark's own tests run on the CPU, at tiny sizes.

The harness modules live in ``bench/`` and import each other by name;
the program is under ``src/``.
"""

import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH = Path(__file__).resolve().parents[1]
for p in (str(BENCH), str(BENCH.parent / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
