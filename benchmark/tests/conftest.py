"""benchmark/tests: CPU rehearsals of the harness at tiny sizes.

Run by hand: JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
Not part of tests/ (tier-1's count is untouched). No topology or device
call happens at import time.
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
for path in (BENCH_DIR, ROOT):
    if path not in sys.path:
        sys.path.insert(0, path)
