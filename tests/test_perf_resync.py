"""Tier-1's hold on PR 37's cell ``rf4-30k-resync`` (its six per-layer
readers, its traffic file, its verb and its 120 s limit, its configuration,
its warm-up, the plain reference), collected from ``perf/tests`` the way
``tests/test_perf_rehydrate.py`` collects PR 33's.
``tests/test_resync_boot.py`` holds the product's side."""

import os
import sys

PERF = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "perf")
for p in (PERF, os.path.join(PERF, "tests")):
    if p not in sys.path:
        sys.path.insert(0, p)

from test_resync_readers import *  # noqa: E402,F401,F403
