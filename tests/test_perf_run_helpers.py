"""Tier-1's hold on perf/run.py's helpers (the warm-up's reach, the trace's start, the keyed metrics): the tests live with the benchmark
(``perf/tests/test_run_helpers.py``); this file collects them from there, the way
``tests/test_perf_hostspans.py`` collects the trace reduction's."""

import os
import sys

PERF = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "perf")
for p in (PERF, os.path.join(PERF, "tests")):
    if p not in sys.path:
        sys.path.insert(0, p)

from test_run_helpers import *  # noqa: E402,F401,F403
