"""Tier-1's hold on the recovery's per-layer readers that PRs 28 and 30 added
(``perf/layer_metrics/recovery.verify_wait_ms.py``,
``recovery.memo_settle_us_per_item.py``), collected from ``perf/tests`` the way
``tests/test_perf_resolve_reader.py`` collects PR 25's."""

import os
import sys

PERF = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "perf")
for p in (PERF, os.path.join(PERF, "tests")):
    if p not in sys.path:
        sys.path.insert(0, p)

from test_memo_settle_reader import *  # noqa: E402,F401,F403
from test_verify_wait_reader import *  # noqa: E402,F401,F403
