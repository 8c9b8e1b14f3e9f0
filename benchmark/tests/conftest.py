"""The benchmark's own tests run on the CPU, apart from the repository's
tier-1 suite: `JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q`."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (os.path.dirname(BENCH), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)
