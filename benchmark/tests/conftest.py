"""Rehearsal tests of the benchmark, on the CPU at tiny sizes. They are
run by hand (`python -m pytest benchmark/tests`), not by the repository's
tier-1 suite."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
