"""Tier-1's hold on perf/ycsb.py, the load generator: the tests live with the benchmark
(``perf/tests/test_ycsb.py``); this file collects them from there, the way
``tests/test_perf_hostspans.py`` collects the trace reduction's."""

import os
import sys

PERF = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "perf")
for p in (PERF, os.path.join(PERF, "tests")):
    if p not in sys.path:
        sys.path.insert(0, p)

from test_ycsb import *  # noqa: E402,F401,F403
