"""The benchmark's own checks: run by hand, in seconds, on the CPU.

    python -m pytest benchmark/tests -q

Not part of the repo's tier-1 suite (``tests/``)."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
for p in (BENCH, os.path.join(BENCH, "reference"), os.path.dirname(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)
