"""CPU tests of the benchmark itself (not part of the repo's tier-1 suite):

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for path in (BENCH, ROOT):
    if path not in sys.path:
        sys.path.insert(0, path)
