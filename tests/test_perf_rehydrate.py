"""Tier-1's hold on PR 33's cell ``rf4-30k-rehydrate`` (its five per-layer
readers, its traffic file, its configuration, its warm-up), collected from
``perf/tests`` the way ``tests/test_perf_verify_wait_reader.py`` collects the
recovery's readers.  ``tests/test_rehydrate.py`` holds the product's side."""

import os
import sys

PERF = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "perf")
for p in (PERF, os.path.join(PERF, "tests")):
    if p not in sys.path:
        sys.path.insert(0, p)

from test_rehydrate_readers import *  # noqa: E402,F401,F403
