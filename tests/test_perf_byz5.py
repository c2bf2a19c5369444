"""Tier-1's hold on PR 46's cell ``n16-byz5-ycsb-a`` (its fourteen per-layer
readers, its configuration, its warm-up, the reference of members), collected
from ``perf/tests`` the way ``tests/test_perf_resync.py`` collects PR 37's.
``tests/test_byzantine_mix.py`` holds the product's side."""

import os
import sys

PERF = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "perf")
for p in (PERF, os.path.join(PERF, "tests")):
    if p not in sys.path:
        sys.path.insert(0, p)

from test_byz5_readers import *  # noqa: E402,F401,F403
