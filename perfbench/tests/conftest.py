"""Tests of the benchmark's own yardstick. They live with the
benchmark and are run by hand on the CPU:

    JAX_PLATFORMS=cpu python -m pytest perfbench/tests -q

tier-1 (tests/) does not collect them.
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for path in (BENCH, os.path.join(BENCH, "drivers"), os.path.dirname(BENCH)):
    if path not in sys.path:
        sys.path.insert(0, path)
