"""``pytest benchmarks/tests``: the benchmark's own checks, outside the
repo's tier-1 suite. They run on the CPU and need no chip."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
