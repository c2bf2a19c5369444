"""Tier-1's hold on the benchmark's trace reduction: ``perf/hostspans.py`` and
the readers over it are what every later PR's per-layer numbers pass through,
and ``perf/tests`` is otherwise run by hand.  The tests live with the
benchmark; this file collects them from there."""

import os
import sys

PERF = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "perf")
for p in (PERF, os.path.join(PERF, "tests")):
    if p not in sys.path:
        sys.path.insert(0, p)

from test_hostspans import *  # noqa: E402,F401,F403
from test_span_readers import *  # noqa: E402,F401,F403
