"""``pytest benchmark/tests`` — the benchmark's own checks, run by hand on
the CPU (they are not part of the repo's tier-1 suite)."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
