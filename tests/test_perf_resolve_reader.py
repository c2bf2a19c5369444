"""Tier-1's hold on PR 25's per-layer reader (``perf/layer_metrics/
verifier.resolve_wait_ms.py``), collected from ``perf/tests`` the way
``tests/test_perf_hostspans.py`` collects the trace reduction's tests."""

import os
import sys

PERF = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "perf")
for p in (PERF, os.path.join(PERF, "tests")):
    if p not in sys.path:
        sys.path.insert(0, p)

from test_resolve_reader import *  # noqa: E402,F401,F403
