"""Tests of the benchmark itself: all on the CPU, none describes a TPU
topology.  ``python -m pytest perfbench/tests -q -p no:cacheprovider``."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
