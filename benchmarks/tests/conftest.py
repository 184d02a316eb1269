"""Tests of the benchmark's own code (``python -m pytest benchmarks/tests -q``);
not part of the repo's ``tests/``. Everything here runs on the CPU."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
