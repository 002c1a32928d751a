"""Self-tests of the benchmark: CPU, no chip.  ``benchmark/`` is the
import root, as it is for ``run.py``."""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
for p in (os.path.join(ROOT, "src", "python"), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)
