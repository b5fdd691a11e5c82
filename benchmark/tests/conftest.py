"""The benchmark's own tests run on the CPU: `python -m pytest
benchmark/tests -q` from the root of the repository. Nothing here reports
a time; the chip check of `run.py` is lifted in the tests only."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("JAX_ENABLE_X64", "1")

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
