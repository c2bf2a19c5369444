"""Tier-1's hold on perf/reference.py, the plain reference that decides ``correct``: the tests live with the benchmark
(``perf/tests/test_reference.py``); this file collects them from there, the way
``tests/test_perf_hostspans.py`` collects the trace reduction's."""

import os
import sys

PERF = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "perf")
for p in (PERF, os.path.join(PERF, "tests")):
    if p not in sys.path:
        sys.path.insert(0, p)

from test_reference import *  # noqa: E402,F401,F403
