"""Selftests of the benchmark's own arithmetic and checks. Run by hand:

  JAX_PLATFORMS=cpu python -m pytest benchmarks/selftest -q

They are outside tier-1 (`tests/`): later PRs may not change the yardstick,
and these show that it measures and refuses what it says."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
