"""Tier-1's hold on the benchmark's fault schedule (``perf/schedule.py``, what
the reference and the readers make of a fault's records): the tests live with
the benchmark in ``perf/tests/test_schedule.py``.  ``tests/test_schedule.py`` is
the product's own module of that name, so this one is loaded by path under
another name, not star-imported as ``tests/test_perf_hostspans.py`` does it."""

import importlib.util
import os
import sys

PERF = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "perf")
for p in (PERF, os.path.join(PERF, "tests")):
    if p not in sys.path:
        sys.path.insert(0, p)

_spec = importlib.util.spec_from_file_location(
    "perf_test_schedule", os.path.join(PERF, "tests", "test_schedule.py")
)
_mod = importlib.util.module_from_spec(_spec)
sys.modules[_spec.name] = _mod
_spec.loader.exec_module(_mod)
globals().update({k: v for k, v in vars(_mod).items() if not k.startswith("_")})
