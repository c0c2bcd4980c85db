"""benchmark/tests: CPU rehearsals of the harness at tiny sizes.

Run by hand: JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
Not part of tests/ (tier-1's count is untouched). No topology or device
call happens at import time.
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# The chip runs the pools' native backward (ops/pooling.py picks it by
# platform); the CPU's default is the scatter-free one, which under jit
# gives other gradients than the same code run eagerly (PERF.md, Open
# questions). The rehearsals follow the chip.
os.environ.setdefault("T2R_POOL_BACKWARD", "native")
BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
for path in (BENCH_DIR, ROOT):
    if path not in sys.path:
        sys.path.insert(0, path)
